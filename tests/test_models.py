"""Per-architecture smoke tests: reduced same-family config, one
forward/train step on CPU, shape + NaN assertions; decode path; exact
sequence-mixer equivalences."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.xent_grad import ops as xent_ops
from repro.models import attention as attn
from repro.models import model as M
from repro.models.attention import blockwise_attention

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=24):
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.arch_type == "vlm":
        batch["prefix"] = jax.random.normal(
            KEY, (B, cfg.prefix_len, cfg.d_model)) * 0.02
    if cfg.arch_type == "audio":
        batch["src"] = jax.random.normal(
            KEY, (B, cfg.prefix_len, cfg.d_model)) * 0.02
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_grad(arch):
    cfg = get_config(arch).smoke_variant()
    assert cfg.n_layers == 2 and cfg.d_model <= 512
    if cfg.n_experts:
        assert cfg.n_experts <= 4
    params = M.init_params(cfg, KEY)
    batch = _batch(cfg)
    logits = M.forward(params, batch["tokens"], cfg,
                       prefix=batch.get("prefix"), src=batch.get("src"))
    S_total = batch["tokens"].shape[1] + (
        cfg.prefix_len if cfg.arch_type == "vlm" else 0)
    assert logits.shape == (2, S_total, cfg.padded_vocab())
    assert not bool(jnp.isnan(logits).any())
    loss, grads = jax.value_and_grad(
        lambda p: M.train_loss(p, batch, cfg))(params)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch):
    cfg = get_config(arch).smoke_variant()
    params = M.init_params(cfg, KEY)
    B = 2
    cache = M.init_decode_cache(
        cfg, B, 48, pos=7,
        src_len=cfg.prefix_len if cfg.arch_type == "audio" else 0)
    if cfg.arch_type == "audio":
        src = jax.random.normal(KEY, (B, cfg.prefix_len, cfg.d_model))
        cache["enc"] = M.encode(params, src * 0.02, cfg)
    tok = jnp.asarray([1, 2], jnp.int32)
    logits, cache2 = M.decode_step(params, tok, cache, cfg)
    assert logits.shape == (B, cfg.padded_vocab())
    assert not bool(jnp.isnan(logits).any())
    jax.tree.map(lambda a, b: None, cache, cache2)  # same structure


def test_prefill_matches_forward_last_position():
    cfg = get_config("granite-3-8b").smoke_variant()
    params = M.init_params(cfg, KEY)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    full = M.forward(params, tokens, cfg)[:, -1]
    pre = M.prefill(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(full), np.asarray(pre),
                               rtol=2e-4, atol=2e-4)


def test_decode_matches_forward_teacher_forcing():
    """Autoregressive decode over a prompt must reproduce the full
    forward logits position by position (dense arch)."""
    cfg = get_config("granite-3-8b").smoke_variant()
    params = M.init_params(cfg, KEY)
    B, S = 1, 12
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full = M.forward(params, tokens, cfg)  # (B, S, V)
    cache = M.init_decode_cache(cfg, B, S)
    outs = []
    for t in range(S):
        logits, cache = M.decode_step(params, tokens[:, t], cache, cfg)
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


def test_decode_matches_forward_ssm_family():
    cfg = get_config("xlstm-1.3b").smoke_variant()
    params = M.init_params(cfg, KEY)
    B, S = 1, 10
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full = M.forward(params, tokens, cfg)
    cache = M.init_decode_cache(cfg, B, S)
    outs = []
    for t in range(S):
        logits, cache = M.decode_step(params, tokens[:, t], cache, cfg)
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


def test_decode_matches_forward_hybrid():
    cfg = get_config("zamba2-1.2b").smoke_variant()
    params = M.init_params(cfg, KEY)
    B, S = 1, 9
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full = M.forward(params, tokens, cfg)
    cache = M.init_decode_cache(cfg, B, S)
    outs = []
    for t in range(S):
        logits, cache = M.decode_step(params, tokens[:, t], cache, cfg)
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


def test_sliding_window_attention_blockwise():
    rng = np.random.default_rng(0)
    B, S, H, KVH, D = 1, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KVH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KVH, D)), jnp.float32)
    full = blockwise_attention(q, k, v, causal=True, window=None,
                               block_q=16, block_k=16)
    win = blockwise_attention(q, k, v, causal=True, window=8,
                              block_q=16, block_k=16)
    # early positions (< window) agree; late positions differ
    np.testing.assert_allclose(full[:, :8], win[:, :8], atol=1e-5)
    assert float(jnp.abs(full[:, -1] - win[:, -1]).max()) > 1e-3


# --- flash-attention dispatch ------------------------------------------------

FLASH_DISPATCH = [
    # (what changes from a causal self-attention of 256 tokens in heads
    #  of 128, 4 query heads over 2 kv heads; takes the kernel)
    ({}, True),
    ({"window": 64}, False),
    ({"cross": True}, False),
    ({"q_offset": 128}, False),
    ({"S": 192}, False),       # not a multiple of 128
    ({"Dh": 64}, False),
    ({"KVH": 3}, False),       # no whole query groups
]


@pytest.mark.parametrize("change,takes", FLASH_DISPATCH)
def test_flash_kernel_dispatch(change, takes, monkeypatch):
    """The kernel is taken on observed shapes alone, where enabled: on
    TPU, or under the test hook; never on CPU or under "ref"."""
    S, Dh = change.get("S", 256), change.get("Dh", 128)
    q_shape, k_shape = (2, S, 4, Dh), (2, S, change.get("KVH", 2), Dh)
    kw = dict(cross=change.get("cross", False), window=change.get("window"),
              q_offset=change.get("q_offset", 0))
    for force, enabled in (("pallas", True), ("ref", False), (None, False)):
        monkeypatch.setattr(flash_ops, "_FORCE", force)
        assert attn.uses_flash_kernel(q_shape, k_shape, **kw) is (
            takes and enabled)


def _attn_layer(d_model=512, n_heads=4, n_kv_heads=2):
    p = attn.init_attention(KEY, d_model, n_heads, n_kv_heads,
                            d_model // n_heads)
    return p, dict(n_heads=n_heads, n_kv_heads=n_kv_heads,
                   head_dim=d_model // n_heads, rope_theta=1e4)


@pytest.mark.parametrize("case", ["window", "cross", "ragged", "head64"])
def test_attention_outside_the_kernel_stays_blockwise(case, monkeypatch):
    """Inputs the dispatch turns away run ``blockwise_attention`` bit
    for bit as under "ref", even with the kernel enabled."""
    p, kw = _attn_layer(n_heads=8 if case == "head64" else 4)
    S = 200 if case == "ragged" else 256
    x = jax.random.normal(KEY, (2, S, 512)) * 0.5
    extra = {"window": {"window": 64},
             "cross": {"kv": x[:, :128] * 2, "causal": False}}.get(case, {})

    def refuse(*a, **k):
        raise AssertionError("the flash kernel was called")

    monkeypatch.setattr(flash_ops, "flash_attention", refuse)
    monkeypatch.setattr(flash_ops, "_FORCE", "pallas")
    got = attn.attention_forward(p, x, **kw, **extra)
    monkeypatch.setattr(flash_ops, "_FORCE", "ref")
    want = attn.attention_forward(p, x, **kw, **extra)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_attention_takes_the_kernel_and_matches_blockwise(monkeypatch):
    p, kw = _attn_layer()
    x = jax.random.normal(KEY, (2, 256, 512)) * 0.5
    calls = []
    real = flash_ops.flash_attention
    monkeypatch.setattr(flash_ops, "flash_attention",
                        lambda *a, **k: calls.append(k) or real(*a, **k))
    monkeypatch.setattr(flash_ops, "_FORCE", "pallas")
    got = attn.attention_forward(p, x, **kw)
    monkeypatch.setattr(flash_ops, "_FORCE", "ref")
    want = attn.attention_forward(p, x, **kw)
    assert calls == [{"causal": True}]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_train_loss_and_grad_equal_with_and_without_the_kernel(monkeypatch):
    """A tiny dense model whose heads the kernel tiles (2 query heads of
    128 over 1 kv head, 128 tokens): the loss and every gradient leaf
    agree to float32 tolerance between the kernel (interpret mode) and
    ``blockwise_attention``."""
    cfg = get_config("qwen1.5-4b").smoke_variant().with_overrides(
        n_heads=2, n_kv_heads=1)
    assert cfg.head_dim == 128
    params = M.init_params(cfg, KEY)
    batch = _batch(cfg, B=2, S=128)
    run = jax.jit(jax.value_and_grad(lambda p: M.train_loss(p, batch, cfg)))
    calls = []
    real = flash_ops.flash_attention
    monkeypatch.setattr(flash_ops, "flash_attention",
                        lambda *a, **k: calls.append(k) or real(*a, **k))
    monkeypatch.setattr(flash_ops, "_FORCE", "pallas")
    loss_k, grads_k = run(params)
    assert calls
    jax.clear_caches()
    calls.clear()
    monkeypatch.setattr(flash_ops, "_FORCE", "ref")
    loss_b, grads_b = run(params)
    assert not calls
    np.testing.assert_allclose(float(loss_k), float(loss_b), rtol=1e-5)
    for gk, gb in zip(jax.tree.leaves(grads_k), jax.tree.leaves(grads_b)):
        gk, gb = np.asarray(gk), np.asarray(gb)
        assert np.linalg.norm(gk - gb) <= 1e-5 * np.linalg.norm(gb)


# --- the head's cross-entropy backward (xent_grad) ---------------------------

def _parent_train_loss(params, batch, cfg, per_example=False):
    """``train_loss`` as it was before the head took the kernel: autodiff
    of the head's float32 logits and their cross entropy."""
    x = M.forward_hidden(params, batch["tokens"], cfg,
                         prefix=batch.get("prefix"), src=batch.get("src"))
    logits = M._lm_head(params, x, cfg)
    labels = batch["labels"]
    if batch.get("prefix") is not None:
        logits = logits[:, batch["prefix"].shape[1]:]
    vocab = cfg.padded_vocab()
    if vocab != cfg.vocab_size:
        vmask = jnp.arange(vocab) < cfg.vocab_size
        logits = jnp.where(vmask, logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ll = picked - lse
    mask = (labels >= 0).astype(jnp.float32)
    loss = -(ll * mask)
    return loss.sum(axis=-1) if per_example else loss.sum()


XENT_CASES = {
    # name: (arch, config overrides, per_example)
    "per_example": ("qwen1.5-4b", {}, True),
    "summed": ("qwen1.5-4b", {}, False),
    "bf16_compute": ("qwen1.5-4b", {"dtype": "bfloat16"}, True),
    "tied_head": ("qwen1.5-4b", {"tie_embeddings": True}, True),
    "vlm_prefix": ("pixtral-12b", {}, True),
    "padded_vocab": ("qwen1.5-4b", {"vocab_size": 500}, True),
}


def _xent_setup(case):
    arch, over, per_example = XENT_CASES[case]
    cfg = get_config(arch).smoke_variant().with_overrides(**over)
    params = M.init_params(cfg, KEY)
    batch = _batch(cfg)
    batch["labels"] = batch["labels"].at[0, :3].set(-1)  # ignored rows
    return cfg, params, batch, per_example


def _loss_and_grads(loss_fn, params, batch, cfg, per_example):
    def total(p):
        out = loss_fn(p, batch, cfg, per_example=per_example)
        # weight the sequences differently, as the coded combine does
        return (out * jnp.arange(1, out.size + 1)).sum()
    return jax.jit(jax.value_and_grad(total))(params)


@pytest.mark.parametrize("case", sorted(XENT_CASES))
def test_train_loss_with_the_xent_kernel_matches_autodiff(case, monkeypatch):
    """The kernel path (interpret mode) against autodiff: the loss to
    float32 rounding; each gradient leaf to one bfloat16 epsilon, the
    rounding of the head's gradient, where the layers compute in
    float32, and to eight where they compute in bfloat16 and round it
    again at every layer. The kernel runs once."""
    cfg, params, batch, per_example = _xent_setup(case)
    calls = []
    real = xent_ops.xent_grad
    monkeypatch.setattr(xent_ops, "xent_grad",
                        lambda *a, **k: calls.append(k) or real(*a, **k))
    monkeypatch.setattr(xent_ops, "_FORCE", "pallas")
    loss_k, grads_k = _loss_and_grads(M.train_loss, params, batch, cfg,
                                      per_example)
    assert calls == [{"vocab_size": cfg.vocab_size}]
    monkeypatch.setattr(xent_ops, "_FORCE", "ref")
    loss_r, grads_r = _loss_and_grads(M.train_loss, params, batch, cfg,
                                      per_example)
    assert len(calls) == 1
    np.testing.assert_allclose(float(loss_k), float(loss_r), rtol=1e-5)
    tol = 2.0 ** -8 * (8 if cfg.dtype == "bfloat16" else 1)
    for gk, gr in zip(jax.tree.leaves(grads_k), jax.tree.leaves(grads_r)):
        gk = np.asarray(gk, np.float32)
        gr = np.asarray(gr, np.float32)
        assert np.linalg.norm(gk - gr) <= tol * np.linalg.norm(gr)


@pytest.mark.parametrize("case", sorted(XENT_CASES))
def test_train_loss_without_the_kernel_is_the_parent_expression(case,
                                                                monkeypatch):
    """With the kernel off (the CPU default, and "ref"), ``train_loss``
    and its gradient are bit for bit the head's autodiff as it was."""
    cfg, params, batch, per_example = _xent_setup(case)
    want = _loss_and_grads(_parent_train_loss, params, batch, cfg,
                           per_example)
    for force in (None, "ref"):
        monkeypatch.setattr(xent_ops, "_FORCE", force)
        got = _loss_and_grads(M.train_loss, params, batch, cfg, per_example)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("precision,takes", [
    (None, True), ("default", True), ("float32", False), ("highest", False),
])
def test_xent_kernel_dispatch_follows_the_matmul_precision(precision, takes,
                                                           monkeypatch):
    """Under a precision above the default the head's dots are exact
    float32, so the bfloat16 gradient is not taken: ``train_loss`` is
    then the parent's expression bit for bit, kernel forced or not."""
    monkeypatch.setattr(xent_ops, "_FORCE", "pallas")
    with jax.default_matmul_precision(precision):
        assert M.uses_xent_kernel((48, 512)) is takes
        if not takes:
            cfg, params, batch, per_example = _xent_setup("per_example")
            got = _loss_and_grads(M.train_loss, params, batch, cfg,
                                  per_example)
            want = _loss_and_grads(_parent_train_loss, params, batch, cfg,
                                   per_example)
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("shape,takes", [
    ((48, 512), True), ((37, 38144), True), ((48, 500), False),
])
def test_xent_kernel_dispatch_on_shapes(shape, takes, monkeypatch):
    for force, enabled in (("pallas", True), ("ref", False), (None, False)):
        monkeypatch.setattr(xent_ops, "_FORCE", force)
        assert M.uses_xent_kernel(shape) is (takes and enabled)


def test_serving_paths_never_take_the_xent_kernel(monkeypatch):
    """``forward``, ``prefill`` and ``decode_step`` keep ``_lm_head``
    whatever the dispatch says."""
    def refuse(*a, **k):
        raise AssertionError("the xent kernel was called")

    monkeypatch.setattr(xent_ops, "xent_grad", refuse)
    monkeypatch.setattr(xent_ops, "_FORCE", "pallas")
    cfg = get_config("qwen1.5-4b").smoke_variant()
    params = M.init_params(cfg, KEY)
    tokens = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
    logits = M.forward(params, tokens, cfg)
    np.testing.assert_array_equal(
        np.asarray(logits), np.asarray(M._lm_head(
            params, M.forward_hidden(params, tokens, cfg), cfg)))
    M.prefill(params, tokens, cfg)
    cache = M.init_decode_cache(cfg, 2, 16)
    M.decode_step(params, tokens[:, 0], cache, cfg)
    jax.grad(lambda p: M.forward(p, tokens, cfg).sum())(params)
