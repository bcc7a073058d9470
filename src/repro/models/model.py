"""Unified model assembly for all assigned architecture families.

Families (dispatch on ``config.arch_type``):

- dense / vlm : decoder-only GQA transformer (vlm consumes a stubbed
  patch-embedding prefix).
- moe         : same backbone with MoE FFN (shared + routed experts).
- hybrid      : Mamba2 (SSD) backbone with a single *shared* attention
  block applied at ``attn_positions`` (Zamba2).
- ssm         : xLSTM -- super-blocks of ``slstm_ratio`` mLSTM + 1 sLSTM.
- audio       : encoder-decoder; encoder consumes stubbed frame
  embeddings, decoder is causal with cross-attention (Seamless).

All layer stacks run under ``lax.scan`` over stacked per-layer params
with ``jax.checkpoint`` on the block body, so the lowered HLO is
layer-count independent and activations are rematerialised.

Public API: init_params, forward, train_loss, prefill, decode_step,
init_decode_cache.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import attention as attn

# Activation-sharding hook (set by the distributed launcher): the
# residual stream (batch, seq, d_model) is constrained at scan-carry
# boundaries to (batch -> data axes, d_model -> model axis) so saved
# activations are both data- and tensor-sharded. PartitionSpec None
# means *replicated*, so the batch axes must be carried explicitly --
# constraining only the last dim silently replicates the batch across
# the data axes (observed: 1.8e12 B/step of gathers).
_RESIDUAL_AXES = None  # (batch_axes, model_axis, mode, model_size)


def set_residual_sharding(batch_axes=None, model_axis=None,
                          mode: str = "dmodel", model_size: int = 1):
    """batch_axes: mesh axis (or tuple) for dim 0; model_axis: mesh axis
    for the constrained dim. mode: 'dmodel' shards the last (d_model)
    dim; 'seq' shards the sequence dim (Megatron-style sequence
    parallelism -- the MLP then needs *no* activation collective and
    attention gathers only the small GQA K/V), falling back to 'dmodel'
    when the seq dim does not divide model_size (e.g. decode, S=1).
    Pass no args to disable."""
    global _RESIDUAL_AXES
    if batch_axes is None and model_axis is None:
        _RESIDUAL_AXES = None
    else:
        _RESIDUAL_AXES = (batch_axes, model_axis, mode, model_size)


def _constrain(x):
    if _RESIDUAL_AXES is None:
        return x
    from jax.sharding import PartitionSpec as P
    batch_axes, model_axis, mode, msize = _RESIDUAL_AXES
    dims = [batch_axes] + [None] * (x.ndim - 1)
    if (mode == "seq" and x.ndim >= 3
            and x.shape[1] % max(msize, 1) == 0 and x.shape[1] >= msize):
        dims[1] = model_axis
    elif x.shape[-1] % max(msize, 1) == 0:
        dims[-1] = model_axis
    spec = P(*dims)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:  # outside a mesh context
        return x
from . import moe as moe_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .layers import (embed, init_embedding, init_mlp, init_rmsnorm, linear,
                     init_linear, mlp, rmsnorm)

# ---------------------------------------------------------------------------
# Parameter initialisation
# ---------------------------------------------------------------------------


def _init_attn_block(key, cfg: ModelConfig, *, cross: bool = False):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "ln_attn": init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "attn": attn.init_attention(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qkv_bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "ln_mlp": init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.param_dtype),
    }
    if cross:
        p["ln_cross"] = init_rmsnorm(cfg.d_model, cfg.param_dtype)
        p["cross"] = attn.init_attention(
            k3, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            dtype=cfg.param_dtype)
    return p


def _init_moe_block(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln_attn": init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "attn": attn.init_attention(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qkv_bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "ln_mlp": init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "moe": moe_mod.init_moe(
            k2, cfg.d_model, cfg.expert_d_ff, cfg.n_experts,
            cfg.n_shared_experts,
            cfg.expert_d_ff * max(cfg.n_shared_experts, 1),
            cfg.param_dtype),
    }


def _init_ssm_block(key, cfg: ModelConfig):
    return {
        "ln": init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "ssm": ssm_mod.init_ssm(
            key, cfg.d_model, cfg.ssm_state, expand=cfg.ssm_expand,
            conv_k=cfg.ssm_conv, dtype=cfg.param_dtype),
    }


def _init_mlstm_block(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln": init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "mlstm": xlstm_mod.init_mlstm(k1, cfg.d_model, cfg.n_heads,
                                      cfg.param_dtype),
        "ln_mlp": init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "mlp": init_mlp(k2, cfg.d_model, 2 * cfg.d_model, cfg.param_dtype),
    }


def _init_slstm_block(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln": init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "slstm": xlstm_mod.init_slstm(k1, cfg.d_model, cfg.param_dtype),
        "ln_mlp": init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "mlp": init_mlp(k2, cfg.d_model, 2 * cfg.d_model, cfg.param_dtype),
    }


def _stack(init_fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def init_params(cfg: ModelConfig, key) -> dict:
    ke, kb, kh, kf = jax.random.split(key, 4)
    vocab = cfg.padded_vocab()
    params = {
        "embed": init_embedding(ke, vocab, cfg.d_model, cfg.param_dtype),
        "final_norm": init_rmsnorm(cfg.d_model, cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(kf, cfg.d_model, vocab,
                                        dtype=cfg.param_dtype)
    at = cfg.arch_type
    if at in ("dense", "vlm"):
        params["blocks"] = _stack(
            lambda k: _init_attn_block(k, cfg), kb, cfg.n_layers)
    elif at == "moe":
        params["blocks"] = _stack(
            lambda k: _init_moe_block(k, cfg), kb, cfg.n_layers)
    elif at == "hybrid":
        params["blocks"] = _stack(
            lambda k: _init_ssm_block(k, cfg), kb, cfg.n_layers)
        params["shared_attn"] = _init_attn_block(kh, cfg)
    elif at == "ssm":
        r = cfg.slstm_ratio
        n_super = cfg.n_layers // (r + 1)
        params["mlstm_blocks"] = _stack(
            lambda k: _init_mlstm_block(k, cfg), kb, n_super * r)
        params["slstm_blocks"] = _stack(
            lambda k: _init_slstm_block(k, cfg), kh, n_super)
    elif at == "audio":
        params["encoder"] = _stack(
            lambda k: _init_attn_block(k, cfg), kh, cfg.n_encoder_layers)
        params["enc_norm"] = init_rmsnorm(cfg.d_model, cfg.param_dtype)
        params["blocks"] = _stack(
            lambda k: _init_attn_block(k, cfg, cross=True), kb,
            cfg.n_layers)
    else:
        raise ValueError(f"unknown arch_type {at!r}")
    return params


# ---------------------------------------------------------------------------
# Block application (full-sequence)
# ---------------------------------------------------------------------------


def _attn_kw(cfg: ModelConfig, window):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                window=window)


def _attn_block(p, x, cfg: ModelConfig, *, window, causal=True,
                cross_kv=None):
    h = attn.attention_forward(
        p["attn"], rmsnorm(p["ln_attn"], x, cfg.norm_eps),
        causal=causal, **_attn_kw(cfg, window))
    x = x + h
    if cross_kv is not None:
        h = attn.attention_forward(
            p["cross"], rmsnorm(p["ln_cross"], x, cfg.norm_eps),
            kv=cross_kv, **_attn_kw(cfg, None))
        x = x + h
    x = x + mlp(p["mlp"], rmsnorm(p["ln_mlp"], x, cfg.norm_eps))
    return x


def _moe_block(p, x, cfg: ModelConfig, *, window):
    h = attn.attention_forward(
        p["attn"], rmsnorm(p["ln_attn"], x, cfg.norm_eps),
        causal=True, **_attn_kw(cfg, window))
    x = x + h
    y, aux = moe_mod.moe_expert_choice(
        p["moe"], rmsnorm(p["ln_mlp"], x, cfg.norm_eps), top_k=cfg.top_k)
    return x + y


def _ssm_block(p, x, cfg: ModelConfig):
    return x + ssm_mod.ssm_forward(
        p["ssm"], rmsnorm(p["ln"], x, cfg.norm_eps),
        d_state=cfg.ssm_state, expand=cfg.ssm_expand)


def _mlstm_block(p, x, cfg: ModelConfig):
    x = x + xlstm_mod.mlstm_forward(
        p["mlstm"], rmsnorm(p["ln"], x, cfg.norm_eps), n_heads=cfg.n_heads)
    return x + mlp(p["mlp"], rmsnorm(p["ln_mlp"], x, cfg.norm_eps))


def _slstm_block(p, x, cfg: ModelConfig):
    x = x + xlstm_mod.slstm_forward(
        p["slstm"], rmsnorm(p["ln"], x, cfg.norm_eps))
    return x + mlp(p["mlp"], rmsnorm(p["ln_mlp"], x, cfg.norm_eps))


def _scan_blocks(blocks, x, body):
    """lax.scan over stacked layer params with remat on the body; the
    carry (the saved residual) is sharding-constrained so per-layer
    checkpoints don't replicate over the model axis."""
    def step(carry, layer_params):
        return _constrain(body(layer_params, carry)), None
    step = jax.checkpoint(step)
    x, _ = jax.lax.scan(step, _constrain(x), blocks)
    return x


def _backbone(params, x, cfg: ModelConfig, *, window, src=None):
    """Apply the layer stack to embedded inputs x (B, S, D)."""
    at = cfg.arch_type
    if at in ("dense", "vlm"):
        x = _scan_blocks(params["blocks"], x,
                         lambda p, h: _attn_block(p, h, cfg, window=window))
    elif at == "moe":
        x = _scan_blocks(params["blocks"], x,
                         lambda p, h: _moe_block(p, h, cfg, window=window))
    elif at == "hybrid":
        positions = sorted(cfg.attn_positions)
        bounds = [0] + list(positions) + [cfg.n_layers]
        for seg in range(len(bounds) - 1):
            lo, hi = bounds[seg], bounds[seg + 1]
            if hi > lo:
                sub = jax.tree.map(lambda a: a[lo:hi], params["blocks"])
                x = _scan_blocks(sub, x,
                                 lambda p, h: _ssm_block(p, h, cfg))
            if seg < len(bounds) - 2:  # shared attention insertion
                x = _attn_block(params["shared_attn"], x, cfg,
                                window=window)
    elif at == "ssm":
        r = cfg.slstm_ratio
        n_super = cfg.n_layers // (r + 1)
        mshape = jax.tree.map(
            lambda a: a.reshape((n_super, r) + a.shape[1:]),
            params["mlstm_blocks"])

        def super_block(carry, layer_params):
            mp, sp = layer_params
            h = _scan_blocks(mp, carry,
                             lambda p, hh: _mlstm_block(p, hh, cfg))
            h = _slstm_block(sp, h, cfg)
            return h, None
        x, _ = jax.lax.scan(jax.checkpoint(super_block), x,
                            (mshape, params["slstm_blocks"]))
    elif at == "audio":
        x = _scan_blocks(
            params["blocks"], x,
            lambda p, h: _attn_block(p, h, cfg, window=window,
                                     cross_kv=src))
    else:
        raise ValueError(at)
    return x


def encode(params, src_embeds, cfg: ModelConfig):
    """Audio/enc-dec encoder: bidirectional attention over frame
    embeddings (B, Ssrc, D)."""
    x = src_embeds.astype(jnp.dtype(cfg.dtype))
    x = _scan_blocks(params["encoder"], x,
                     lambda p, h: _attn_block(p, h, cfg, window=None,
                                              causal=False))
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _lm_head(params, x, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return x.astype(jnp.float32) @ params["embed"]["table"].astype(
            jnp.float32).T
    return x.astype(jnp.float32) @ params["lm_head"]["w"].astype(
        jnp.float32)


def forward_hidden(params, tokens, cfg: ModelConfig, *,
                   prefix: Optional[jnp.ndarray] = None,
                   src: Optional[jnp.ndarray] = None,
                   window: Optional[int] = None) -> jnp.ndarray:
    """Final-norm hidden states (B, S_total, D)."""
    window = window if window is not None else cfg.sliding_window
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("model.embed"):
        x = embed(params["embed"], tokens).astype(dt)
    if prefix is not None:
        x = jnp.concatenate([prefix.astype(dt), x], axis=1)
    enc = encode(params, src, cfg) if src is not None else None
    with jax.named_scope("model.layers"):
        x = _backbone(params, x, cfg, window=window, src=enc)
    return rmsnorm(params["final_norm"], x, cfg.norm_eps)


def forward(params, tokens, cfg: ModelConfig, *,
            prefix: Optional[jnp.ndarray] = None,
            src: Optional[jnp.ndarray] = None,
            window: Optional[int] = None) -> jnp.ndarray:
    """Full-sequence logits.

    tokens: (B, St) int32. prefix: (B, P, D) stub embeddings prepended
    (vlm). src: (B, Ssrc, D) stub frame embeddings (audio enc-dec).
    window: overrides cfg.sliding_window when not None.
    Returns fp32 logits (B, S_total, V_pad).
    """
    x = forward_hidden(params, tokens, cfg, prefix=prefix, src=src,
                       window=window)
    with jax.named_scope("model.head"):
        return _lm_head(params, x, cfg)


def train_loss(params, batch, cfg: ModelConfig, *,
               per_example: bool = False) -> jnp.ndarray:
    """Summed next-token cross entropy over real (non-pad) label
    positions. Sum (not mean) so per-block losses add like the paper's
    f = sum_i f_i; the caller normalises by the global token count.
    ``per_example`` returns per-sequence sums (B,) for the coded
    per-block combine."""
    x = forward_hidden(params, batch["tokens"], cfg,
                       prefix=batch.get("prefix"), src=batch.get("src"))
    with jax.named_scope("model.head"):
        logits = _lm_head(params, x, cfg)
        labels = batch["labels"]
        if batch.get("prefix") is not None:
            logits = logits[:, batch["prefix"].shape[1]:]
        # mask padded vocab entries out of the softmax (iota mask
        # instead of a scatter: cheaper under a vocab-sharded layout)
        vocab = cfg.padded_vocab()
        if vocab != cfg.vocab_size:
            vmask = jnp.arange(vocab) < cfg.vocab_size
            logits = jnp.where(vmask, logits, -1e30)
        # ll = logits[label] - logsumexp(logits): avoids a second
        # (B, S, V) log-softmax intermediate.
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None],
                                     axis=-1)[..., 0]
        ll = picked - lse
        mask = (labels >= 0).astype(jnp.float32)
        loss = -(ll * mask)
        if per_example:
            return loss.sum(axis=-1)
        return loss.sum()


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode with caches
# ---------------------------------------------------------------------------


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                      pos: int = 0, src_len: int = 0) -> dict:
    """Cache pytree for decode_step. ``max_len`` is the KV capacity
    (window size for sliding-window archs). ``pos`` pre-fills the cache
    position (dry-run decodes at a full cache)."""
    at = cfg.arch_type
    dt = cfg.dtype
    if at in ("dense", "vlm", "moe"):
        kv_len = min(max_len, cfg.sliding_window or max_len)
        cache = jax.vmap(
            lambda _: attn.init_cache(batch, kv_len, cfg.n_kv_heads,
                                      cfg.head_dim, dt, pos=pos)
        )(jnp.arange(cfg.n_layers))
        return {"layers": cache}
    if at == "hybrid":
        ssm_states = jax.vmap(
            lambda _: ssm_mod.init_ssm_state(
                batch, cfg.d_model, cfg.ssm_state, expand=cfg.ssm_expand,
                conv_k=cfg.ssm_conv))(jnp.arange(cfg.n_layers))
        kv_len = min(max_len, cfg.sliding_window or max_len)
        n_attn = len(cfg.attn_positions)
        attn_cache = jax.vmap(
            lambda _: attn.init_cache(batch, kv_len, cfg.n_kv_heads,
                                      cfg.head_dim, dt, pos=pos)
        )(jnp.arange(max(n_attn, 1)))
        return {"ssm": ssm_states, "attn": attn_cache}
    if at == "ssm":
        r = cfg.slstm_ratio
        n_super = cfg.n_layers // (r + 1)
        m_states = jax.vmap(
            lambda _: xlstm_mod.init_mlstm_state(batch, cfg.d_model,
                                                 cfg.n_heads)
        )(jnp.arange(n_super * r))
        s_states = jax.vmap(
            lambda _: xlstm_mod.init_slstm_state(batch, cfg.d_model)
        )(jnp.arange(n_super))
        return {"mlstm": m_states, "slstm": s_states}
    if at == "audio":
        kv_len = min(max_len, cfg.sliding_window or max_len)
        cache = jax.vmap(
            lambda _: attn.init_cache(batch, kv_len, cfg.n_kv_heads,
                                      cfg.head_dim, dt, pos=pos)
        )(jnp.arange(cfg.n_layers))
        return {"layers": cache,
                "enc": jnp.zeros((batch, src_len, cfg.d_model),
                                 jnp.dtype(dt))}
    raise ValueError(at)


def _attn_block_decode(p, x, cache, cfg: ModelConfig, *, window,
                       enc=None):
    h, cache = attn.attention_decode(
        p["attn"], rmsnorm(p["ln_attn"], x, cfg.norm_eps), cache,
        **_attn_kw(cfg, window))
    x = x + h
    if enc is not None:
        B = x.shape[0]
        # cross attention over the (precomputed) encoder output
        h = attn.attention_forward(
            p["cross"], rmsnorm(p["ln_cross"], x, cfg.norm_eps),
            kv=enc, causal=False, **_attn_kw(cfg, None))
        x = x + h
    x = x + mlp(p["mlp"], rmsnorm(p["ln_mlp"], x, cfg.norm_eps))
    return x, cache


def _moe_block_decode(p, x, cache, cfg: ModelConfig, *, window):
    h, cache = attn.attention_decode(
        p["attn"], rmsnorm(p["ln_attn"], x, cfg.norm_eps), cache,
        **_attn_kw(cfg, window))
    x = x + h
    y, _ = moe_mod.moe_expert_choice(
        p["moe"], rmsnorm(p["ln_mlp"], x, cfg.norm_eps),
        top_k=cfg.top_k, capacity_factor=float(cfg.n_experts) /
        max(cfg.top_k, 1))
    return x + y, cache


def decode_step(params, token, cache, cfg: ModelConfig, *,
                window: Optional[int] = None):
    """One decode step. token: (B,) int32. Returns (logits (B, V_pad),
    new cache)."""
    window = window if window is not None else cfg.sliding_window
    dt = jnp.dtype(cfg.dtype)
    x = embed(params["embed"], token[:, None]).astype(dt)
    at = cfg.arch_type

    if at in ("dense", "vlm", "moe", "audio"):
        enc = cache.get("enc") if at == "audio" else None
        body = _moe_block_decode if at == "moe" else functools.partial(
            _attn_block_decode, enc=enc) if at == "audio" else \
            _attn_block_decode

        def step(carry, inp):
            layer_p, layer_c = inp
            if at == "moe":
                h, c = _moe_block_decode(layer_p, carry, layer_c, cfg,
                                         window=window)
            elif at == "audio":
                h, c = _attn_block_decode(layer_p, carry, layer_c, cfg,
                                          window=window, enc=enc)
            else:
                h, c = _attn_block_decode(layer_p, carry, layer_c, cfg,
                                          window=window)
            return h, c
        x, new_layers = jax.lax.scan(step, x,
                                     (params["blocks"], cache["layers"]))
        new_cache = dict(cache)
        new_cache["layers"] = new_layers
    elif at == "hybrid":
        positions = sorted(cfg.attn_positions)

        def ssm_step(carry, inp):
            layer_p, layer_s = inp
            h = carry
            y, s = ssm_mod.ssm_decode(
                layer_p["ssm"], rmsnorm(layer_p["ln"], h, cfg.norm_eps),
                layer_s, d_state=cfg.ssm_state, expand=cfg.ssm_expand)
            return h + y, s

        bounds = [0] + list(positions) + [cfg.n_layers]
        new_ssm = []
        new_attn = []
        for seg in range(len(bounds) - 1):
            lo, hi = bounds[seg], bounds[seg + 1]
            if hi > lo:
                sub_p = jax.tree.map(lambda a: a[lo:hi], params["blocks"])
                sub_s = jax.tree.map(lambda a: a[lo:hi], cache["ssm"])
                x, s = jax.lax.scan(ssm_step, x, (sub_p, sub_s))
                new_ssm.append(s)
            if seg < len(bounds) - 2:
                layer_c = jax.tree.map(lambda a: a[seg], cache["attn"])
                x, c = _attn_block_decode(params["shared_attn"], x,
                                          layer_c, cfg, window=window)
                new_attn.append(c)
        new_cache = {
            "ssm": jax.tree.map(lambda *xs: jnp.concatenate(xs), *new_ssm)
            if len(new_ssm) > 1 else new_ssm[0],
            "attn": jax.tree.map(lambda *xs: jnp.stack(xs), *new_attn)
            if new_attn else cache["attn"],
        }
    elif at == "ssm":
        r = cfg.slstm_ratio
        n_super = cfg.n_layers // (r + 1)

        def m_step(carry, inp):
            layer_p, layer_s = inp
            h = carry
            y, s = xlstm_mod.mlstm_decode(
                layer_p["mlstm"],
                rmsnorm(layer_p["ln"], h, cfg.norm_eps), layer_s,
                n_heads=cfg.n_heads)
            h = h + y
            h = h + mlp(layer_p["mlp"],
                        rmsnorm(layer_p["ln_mlp"], h, cfg.norm_eps))
            return h, s

        mshape_p = jax.tree.map(
            lambda a: a.reshape((n_super, r) + a.shape[1:]),
            params["mlstm_blocks"])
        mshape_s = jax.tree.map(
            lambda a: a.reshape((n_super, r) + a.shape[1:]),
            cache["mlstm"])

        def super_step(carry, inp):
            (mp, ms), (sp, ss) = inp[0], inp[1]
            h, new_ms = jax.lax.scan(m_step, carry, (mp, ms))
            y, new_ss = xlstm_mod.slstm_decode(
                sp["slstm"], rmsnorm(sp["ln"], h, cfg.norm_eps), ss)
            h = h + y
            h = h + mlp(sp["mlp"], rmsnorm(sp["ln_mlp"], h, cfg.norm_eps))
            return h, (new_ms, new_ss)

        x, (new_m, new_s) = jax.lax.scan(
            super_step, x,
            ((mshape_p, mshape_s), (params["slstm_blocks"],
                                    cache["slstm"])))
        new_cache = {
            "mlstm": jax.tree.map(
                lambda a: a.reshape((n_super * r,) + a.shape[2:]), new_m),
            "slstm": new_s,
        }
    else:
        raise ValueError(at)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x.astype(jnp.float32) @ params["embed"]["table"].astype(
            jnp.float32).T
    else:
        logits = x.astype(jnp.float32) @ params["lm_head"]["w"].astype(
            jnp.float32)
    return logits[:, 0], new_cache


def prefill(params, tokens, cfg: ModelConfig, *,
            prefix: Optional[jnp.ndarray] = None,
            src: Optional[jnp.ndarray] = None,
            window: Optional[int] = None):
    """Prefill: full forward returning last-position logits (the KV
    cache materialisation is exercised by decode; prefill benchmarks the
    forward compute). The LM head runs on the last position only --
    a (B, S, V) logits tensor at 32k would dominate memory for nothing."""
    x = forward_hidden(params, tokens, cfg, prefix=prefix, src=src,
                       window=window)
    return _lm_head(params, x[:, -1:], cfg)[:, 0]
