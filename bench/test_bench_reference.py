"""The plain float32 reference against the program at smoke size, on the
CPU: the dedup coded step's loss, gradient and AdamW update, the
optimal decode's combine weights, and serving (prompt replay, then
decode through the pool cache and ``decode_attention``) on logits."""

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

jax = pytest.importorskip("jax")
jnp = jax.numpy

import reference as ref  # noqa: E402
import weights  # noqa: E402


def _cfg(**kw):
    from repro.configs import get_config

    base = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                vocab_size=200, dtype="float32", param_dtype="float32",
                rope_theta=10000.0)
    base.update(kw)
    return get_config("qwen1.5-4b").with_overrides(**base)


def _dims(cfg):
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
            "eps": cfg.norm_eps}


@pytest.fixture
def pallas_rmsnorm():
    from repro.kernels.rmsnorm import ops

    old, ops._FORCE = ops._FORCE, "pallas"
    yield
    ops._FORCE = old


def test_optimal_alpha_matches_program_decoder():
    from repro.configs import CodingConfig
    from repro.dist import coded_train

    rt = coded_train.CodingRuntime(
        CodingConfig(scheme="expander", replication=2, straggler_p=0.2,
                     seed=7), 12)
    A = rt.assignment
    assert ref.debias_scale(A.A, 0.2, 256, 7 + 0x5EED) == pytest.approx(
        rt.scale, rel=1e-9)
    rng = np.random.default_rng(3)
    for _ in range(20):
        alive = rng.random(12) >= 0.3
        w = rt.weights_for(alive)
        v_prog = rt.block_weights(w)
        v_ref = rt.scale * ref.optimal_alpha(A.A, alive)
        np.testing.assert_allclose(v_prog, v_ref, rtol=2e-6, atol=1e-6)


def test_worst_leaf_error_by_hand():
    reference = {"a": np.array([3.0, 4.0]), "b": np.array([0.0, 1.0])}
    norms = {"a": 5.0, "b": 1.0}
    program = {"a": np.array([1.5, 2.25]), "b": np.array([0.0, 0.5])}
    # a: |2 * [1.5, 2.25] - [3, 4]| = 0.5 over its own norm 5; b: 0
    # over the median norm 3, which is larger than its own
    assert ref.worst_leaf_error(program, reference, norms, scale=2.0) == (
        pytest.approx(0.1), "a")
    program["b"] = np.array([0.0, 2.0])
    assert ref.worst_leaf_error(program, reference, norms, scale=2.0) == (
        pytest.approx(1.0), "b")


def test_coded_step_loss_grad_update_match_reference(pallas_rmsnorm):
    from repro.dist import coded_train
    from repro.optim import optimizers as opt_mod
    from repro.configs import CodingConfig

    cfg = _cfg()
    dims = _dims(cfg)
    rt = coded_train.CodingRuntime(
        CodingConfig(scheme="expander", replication=2, straggler_p=0.2,
                     seed=1), 12)
    A = rt.assignment
    hp = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
          "weight_decay": 0.0}
    opt = opt_mod.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"])
    step = jax.jit(coded_train.make_train_step(
        cfg, opt, dedup=True, norm_scale=coded_train.dedup_norm_scale(A),
        alpha_weights=coded_train.alpha_bar_weights(A)))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (12, 1, 17)).astype(np.int32)
    blocks = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    alive = np.ones(12, bool)
    alive[[2, 5]] = False
    v = rt.block_weights(rt.weights_for(alive))

    params = weights.make_params(cfg, 5)
    p0 = weights.make_params(cfg, 5)
    state = opt.init(params)
    params, state, metrics = step(params, state, blocks, jnp.asarray(v))

    norm = blocks["labels"].size * coded_train.dedup_norm_scale(A)
    v_ref = rt.scale * ref.optimal_alpha(A.A, alive)
    loss, g = ref.coded_loss_and_grad([p0], blocks, v_ref, norm, dims)
    g = ref.pad_like(g, p0)
    assert float(metrics["loss"]) == pytest.approx(loss, rel=1e-5)
    prog_g = ref.leaf_norms(jax.tree.map(lambda m: m / (1 - hp["b1"]),
                                         state["m"]))
    gap, _ = ref.worst_leaf_gap(prog_g, ref.leaf_norms(g))
    assert gap < 1e-4
    error, _ = ref.worst_leaf_error(ref.host_leaves(state["m"]),
                                    ref.host_leaves(g), ref.leaf_norms(g),
                                    scale=1 / (1 - hp["b1"]))
    assert error < 1e-3
    zeros = jax.tree.map(jnp.zeros_like, p0)
    p1, _, _ = ref.adamw_step(jax.tree.map(jnp.copy, p0), g, zeros,
                              jax.tree.map(jnp.zeros_like, p0), 1, hp)
    change = lambda a: ref.leaf_norms(jax.tree.map(jnp.subtract, a, p0))
    gap, _ = ref.worst_leaf_gap(change(params), change(p1))
    assert gap < 1e-3


def test_serving_replay_then_decode_matches_reference_logits():
    from repro.dist import coded_train
    from repro.kernels.decode_attention import ops as da_ops
    from repro.models import model as M

    cfg = _cfg(n_heads=8, n_kv_heads=2, d_model=64, param_dtype="bfloat16")
    dims = _dims(cfg)
    params = weights.make_params(cfg, 9)
    step = jax.jit(coded_train.make_serve_step(cfg))
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, 24)
    cache = M.init_decode_cache(cfg, 1, 32)
    old, da_ops._FORCE = da_ops._FORCE, "pallas"
    try:
        out = []
        for t in seq:                 # replay, then "decode" the same ids
            lg, cache = step(params, jnp.asarray([t], jnp.int32), cache)
            out.append(np.asarray(lg[0, :cfg.vocab_size]))
    finally:
        da_ops._FORCE = old
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(params, jnp.asarray(seq), dims))
    np.testing.assert_allclose(np.stack(out), want, atol=2e-3, rtol=2e-3)
