"""The span recorder (``repro.spans``) and where the program records
spans: the recorder's ids, steps, attributes, aggregates and bounds;
the coding layer's spans against the runtime's own counters, with the
weights unchanged; the train driver's ``spans`` table; and the name
scopes the compiled dedup step carries."""

import re
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.configs import CodingConfig, get_config
from repro.data.pipeline import CodedBatcher, SyntheticLM
from repro.dist import coded_train
from repro.models import model as M
from repro.optim import optimizers as opt_mod


def test_nesting_sets_parent_ids():
    rec = spans.Recorder()
    with rec.span("outer") as outer:
        with rec.span("inner") as inner:
            with rec.span("leaf") as leaf:
                pass
        with rec.span("sibling") as sibling:
            pass
    assert outer.parent is None
    assert inner.parent == outer.id and sibling.parent == outer.id
    assert leaf.parent == inner.id
    assert len({outer.id, inner.id, leaf.id, sibling.id}) == 4
    # Records are kept as they close: innermost first.
    assert [r.name for r in rec.records()] == ["leaf", "inner", "sibling",
                                               "outer"]
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_span_without_step_inherits_its_threads_step():
    rec = spans.Recorder()
    with rec.span("first") as a:
        pass
    with rec.span("tagged", step=7):
        pass
    with rec.span("untagged") as b:
        pass
    with rec.span("retagged", step=9):
        pass
    with rec.span("untagged") as c:
        pass
    assert a.step is None and b.step == 7 and c.step == 9


def test_attributes_given_and_set_inside_the_span():
    rec = spans.Recorder()
    with rec.span("coding.lookahead", step=3, rounds=8) as s:
        s.set(novel=5)
    (r,) = rec.records("coding.lookahead")
    assert r.attrs == {"rounds": 8, "novel": 5}
    assert r.step == 3 and r.ms >= 0


def test_aggregates_count_total_and_max():
    rec = spans.Recorder()
    for _ in range(3):
        with rec.span("a"):
            pass
    with rec.span("b"):
        pass
    t = rec.totals()
    assert set(t) == {"a", "b"}
    assert t["a"]["count"] == 3 and t["b"]["count"] == 1
    ms = [r.ms for r in rec.records("a")]
    assert t["a"]["total_s"] == pytest.approx(sum(ms) / 1e3)
    assert t["a"]["max_ms"] == pytest.approx(max(ms))
    assert t["a"]["mean_ms"] == pytest.approx(sum(ms) / 3)
    rec.clear()
    assert rec.records() == [] and rec.totals() == {}


def test_deque_keeps_the_newest_records_and_aggregates_all():
    rec = spans.Recorder(maxlen=4)
    for k in range(10):
        with rec.span("s", step=k):
            pass
    assert [r.step for r in rec.records()] == [6, 7, 8, 9]
    assert rec.totals()["s"]["count"] == 10


def test_span_is_recorded_when_its_body_raises():
    rec = spans.Recorder()
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("fails", step=1):
                raise ValueError("boom")
    assert [r.name for r in rec.records()] == ["fails", "outer"]
    # The thread's stack is empty again: a new span has no parent.
    with rec.span("after") as after:
        pass
    assert after.parent is None and after.step == 1


def test_threads_keep_their_own_parents_and_steps():
    rec = spans.Recorder()
    barrier = threading.Barrier(2, timeout=10)
    seen = {}

    def work(tag, step):
        with rec.span(f"outer.{tag}", step=step) as outer:
            barrier.wait()   # both outer spans are open at once
            with rec.span(f"inner.{tag}") as inner:
                barrier.wait()
        seen[tag] = (outer, inner)

    threads = [threading.Thread(target=work, args=(t, s))
               for t, s in (("a", 1), ("b", 2))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    for tag, step in (("a", 1), ("b", 2)):
        outer, inner = seen[tag]
        assert inner.parent == outer.id
        assert inner.step == step
        assert inner.thread == outer.thread
    assert seen["a"][0].thread != seen["b"][0].thread


def test_data_layer_spans_share_the_batchs_step():
    from repro.core import expander_assignment

    spans.clear()
    A = expander_assignment(4, 2, vertex_transitive=False, seed=1)
    batcher = CodedBatcher(A, shuffle_seed=0)
    source = SyntheticLM(64, 8, seed=0)
    raw = source.batch(A.n * 2, 5)
    batcher.unique_blocks(raw)
    batcher.code_batch(raw)
    recs = spans.records()
    assert [(r.name, r.step) for r in recs] == [
        ("data.batch", 5), ("data.blocks", 5), ("data.blocks", 5)]
    assert recs[1].attrs == {"blocks": A.n}


def _runtime(seed=9, **kw):
    return coded_train.CodingRuntime(
        CodingConfig(scheme="expander", replication=2, seed=seed,
                     straggler_model="bernoulli", straggler_p=0.3, **kw),
        m=8)


@pytest.mark.parametrize("skip", [0, 5], ids=["fresh", "resumed"])
def test_coding_spans_match_the_runtimes_counters(skip):
    steps, horizon = 23, 6
    spans.clear()
    rt_sync = _runtime()
    rt_sync.skip(skip)
    sync = [rt_sync.step_weights() for _ in range(steps)]
    per_step = spans.records("coding.step_weights")
    assert [r.step for r in per_step] == list(range(skip, skip + steps))
    assert sum(r.attrs["novel"] for r in per_step) == rt_sync.decode_calls

    spans.clear()
    rt_pre = _runtime()
    rt_pre.skip(skip)
    with ThreadPoolExecutor(max_workers=1) as pool:
        pre_fetch = coded_train.LookaheadPrefetcher(rt_pre, pool, horizon,
                                                    steps)
        pre = [pre_fetch.next() for _ in range(steps)]
    chunks = spans.records("coding.lookahead")
    assert [r.step for r in chunks] == list(range(skip, skip + steps,
                                                  horizon))
    assert sum(r.attrs["rounds"] for r in chunks) \
        == rt_pre.steps_sampled - skip == steps
    assert sum(r.attrs["novel"] for r in chunks) == rt_pre.decode_calls
    waits = spans.records("coding.wait")
    assert [r.step for r in waits] == list(range(skip, skip + steps))
    # The spans touch no data or RNG: the weights are the per-step
    # loop's, bit for bit.
    np.testing.assert_array_equal(np.stack([w for w, _ in sync]),
                                  np.stack([w for w, _ in pre]))
    np.testing.assert_array_equal(np.stack([a for _, a in sync]),
                                  np.stack([a for _, a in pre]))


def test_train_driver_summary_has_a_spans_table():
    from repro.launch import train as train_mod

    try:
        summary = train_mod.main([
            "--arch", "qwen1.5-4b", "--machines", "4", "--steps", "12",
            "--seq-len", "32", "--block-size", "2", "--lookahead", "3",
            "--log-every", "4"])
    finally:
        M.set_residual_sharding()
    table = summary["spans"]
    for name in ("data.batch", "data.blocks", "coding.lookahead",
                 "coding.wait", "train.dispatch", "train.batch_wait",
                 "train.sync"):
        assert name in table, name
    assert table["train.dispatch"]["count"] == 12
    assert table["coding.wait"]["count"] == 12
    assert table["coding.lookahead"]["count"] == 4
    assert table["data.batch"]["count"] == 12
    for row in table.values():
        assert set(row) == {"count", "total_s", "mean_ms", "max_ms"}
        assert row["max_ms"] <= row["total_s"] * 1e3 + 1e-9


def test_compiled_dedup_step_carries_the_phase_scopes():
    from repro.core import expander_assignment

    cfg = get_config("qwen1.5-4b").smoke_variant()
    A = expander_assignment(4, 2, vertex_transitive=False, seed=1)
    raw = SyntheticLM(cfg.vocab_size, 16, seed=0).batch(A.n, 0)
    blocks = {k: jnp.asarray(v) for k, v in
              CodedBatcher(A, shuffle_seed=0).unique_blocks(raw).items()}
    opt = opt_mod.get_optimizer("adamw", 1e-3)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    step = coded_train.make_train_step(
        cfg, opt, dedup=True, norm_scale=coded_train.dedup_norm_scale(A),
        alpha_weights=coded_train.alpha_bar_weights(A))
    text = jax.jit(step).lower(params, opt.init(params), blocks,
                               jnp.ones((A.n,), jnp.float32)
                               ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("jvp(coded.loss)", "transpose(jvp(coded.loss))",
                  "model.head", "model.layers", "model.embed",
                  "coded.optimizer", "coded.metrics"):
        assert any(scope in n for n in names), scope
    assert any("transpose(jvp(coded.loss))/model.head" in n for n in names)
