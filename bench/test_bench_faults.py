"""The checks that decide ``correct`` fail what they must, at a size the
CPU can hold: the control (the reference computed in float8) fails a
cell's limits, and a run with the timed path broken underneath comes
out not correct, once for each fault a cell can have: a step that
returns its state unchanged, half of the batch left out with the mean
taken over the rest, the exchange between chips left out, and a served
token altered where it is produced."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path[:0] = [str(REPO / "src"), str(BENCH), str(BENCH / "drivers")]

jax = pytest.importorskip("jax")

import small_cells  # noqa: E402


def _fails_a_limit(numbers, limits):
    return any(numbers[k] > limits[k] for k in limits)


def test_train_control_fails_the_limits():
    import train

    spec = small_cells.train_spec()
    rows = train.control(spec, [2**31 + 5, 17, 23], jax.devices())
    for row in rows:
        assert _fails_a_limit(row["fp8"], spec["traffic"]["limits"]), row
        assert _fails_a_limit(row["half_batch"],
                              spec["traffic"]["limits"]), row


def test_serve_control_fails_the_limit():
    import serve

    spec = small_cells.serve_spec()
    limit = spec["traffic"]["limits"]["logit_gap"]
    for row in serve.control(spec, [2**31 + 5, 17, 23], jax.devices(), 1.0):
        assert row["f32"] <= limit < row["fp8"], row


def _unchanged_state(*args, **kw):
    from repro.dist import coded_train

    real = coded_train.make_train_step(*args, **kw)

    def step(params, opt_state, batch, w):
        return (params, opt_state) + tuple(real(params, opt_state, batch,
                                                w)[2:])
    return step


def _half_batch(*args, **kw):
    from repro.dist import coded_train

    real = coded_train.make_train_step(*args, **kw)

    def step(params, opt_state, batch, w):
        n = w.shape[0] // 2
        return real(params, opt_state, {k: v[:n] for k, v in batch.items()},
                    w[:n])
    return step


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch],
                         ids=["unchanged_state", "half_batch"])
def test_train_fault_is_not_correct(fault):
    import train

    spec = small_cells.train_spec()
    res = train.run(spec, 2**31 + 9, 0.5, 0, jax.devices(), 0.0,
                    fault=fault)
    assert res["correct"] is False, res["checks"]


NO_EXCHANGE = textwrap.dedent("""
    import json, sys
    sys.path[:0] = {paths!r}
    import jax
    from jax.sharding import PartitionSpec as P
    import small_cells, train
    from repro.dist import coded_train

    def no_exchange(cfg, optimizer, *, norm_scale, **kw):
        # Each chip's step on its own blocks, normalised as the whole
        # batch, with no all-reduce of the gradients between chips.
        real = coded_train.make_train_step(
            cfg, optimizer, norm_scale=norm_scale * 4, **kw)
        def step(params, opt_state, batch, w):
            return jax.shard_map(
                real, in_specs=(P(), P(), P("data"), P("data")),
                out_specs=P(), check_vma=False)(params, opt_state, batch, w)
        return step

    spec = small_cells.train_spec(traffic="train_coded_dp4")
    out = {{}}
    for name, fault in (("sound", None), ("no_exchange", no_exchange)):
        res = train.run(spec, 2**31 + 9, 0.5, 0, jax.devices(), 0.0,
                        fault=fault)
        out[name] = [res["correct"], res["checks"]]
    print(json.dumps(out))
""")


def test_train_exchange_left_out_is_not_correct():
    paths = [str(REPO / "src"), str(BENCH), str(BENCH / "drivers")]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c",
                          NO_EXCHANGE.format(paths=paths)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["sound"][0] is True, got
    assert got["no_exchange"][0] is False, got


def test_serve_altered_token_is_not_correct():
    import serve

    spec = small_cells.serve_spec()
    vocab = spec["config"]["vocab_size"]

    def altered(step_fn):
        def step(*args):
            tok, cache = step_fn(*args)
            return (tok + 1) % vocab, cache
        return step

    res = serve.run(spec, 2**31 + 9, 1.0, 0, jax.devices(), 0.0,
                    fault=altered)
    assert res["correct"] is False, res["checks"]
