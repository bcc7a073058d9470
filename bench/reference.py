"""The plain float32 reference: decoder, coded loss, gradient, AdamW,
and the optimal decode of a straggler mask.

Written from the published descriptions in straightforward
``jax.numpy``, with no kernels, cache or batching of the program under
test, and imported by nothing of it. Every matmul goes through one
hook, ``mm``: the float32 reference runs it at
``jax.default_matmul_precision("highest")``; the control (``FP8``)
rounds both operands to float8 e4m3 with a per-tensor scale first, and
in the backward pass the cotangent and the saved operands alike, which
is the precision step below the bfloat16 the configurations compute
in.

The decoder is the Llama / Qwen2 block: RMSNorm, grouped-query
attention with rotary positions (halves rotated, as in ``rotate_half``)
and optional QKV bias, a SwiGLU MLP, a final RMSNorm and an untied
head. Weights are read from the program's parameter tree by name
(``blocks`` stacked over layers), upcast to float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = "f32"
FP8 = "fp8"
_FP8_MAX = 448.0


def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale, in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision="highest")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec, a, b):
    return _einsum(spec, _fp8(a), _fp8(b))


def _mm_fp8_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return _einsum(spec, qa, qb), (qa, qb)


def _mm_fp8_bwd(spec, res, g):
    """The backward matmuls on float8 operands too: the saved operands
    and the cotangent, each under its own per-tensor scale."""
    _, vjp = jax.vjp(functools.partial(_einsum, spec), *res)
    return vjp(_fp8(g))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(spec: str, a, b, precision: str):
    """einsum(spec, a, b) in float32, or with float8 operands in the
    forward and the backward pass."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == FP8:
        return _mm_fp8(spec, a, b)
    return _einsum(spec, a, b)


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """x: (S, H, Dh); rotate the two halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _linear(p, x, precision):
    y = mm("sd,df->sf", x, p["w"], precision)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y


def layer(p, x, dims, precision):
    """One decoder layer over one sequence x: (S, D) float32."""
    S = x.shape[0]
    h, kvh, dh = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    a = rmsnorm(x, p["ln_attn"]["scale"], dims["eps"])
    pos = jnp.arange(S)
    q = rope(_linear(p["attn"]["wq"], a, precision).reshape(S, h, dh),
             pos, dims["rope_theta"])
    k = rope(_linear(p["attn"]["wk"], a, precision).reshape(S, kvh, dh),
             pos, dims["rope_theta"])
    v = _linear(p["attn"]["wv"], a, precision).reshape(S, kvh, dh)
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    s = mm("qhd,khd->hqk", q, k, precision) * dh ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    probs = jax.nn.softmax(s, axis=-1)
    o = mm("hqk,khd->qhd", probs, v, precision).reshape(S, h * dh)
    x = x + _linear(p["attn"]["wo"], o, precision)
    m = rmsnorm(x, p["ln_mlp"]["scale"], dims["eps"])
    g = _linear(p["mlp"]["wi_gate"], m, precision)
    u = _linear(p["mlp"]["wi_up"], m, precision)
    return x + _linear(p["mlp"]["wo"], jax.nn.silu(g) * u, precision)


def layer_params(params, i):
    return jax.tree.map(lambda a: a[i], params["blocks"])


def embed(params, tokens):
    return params["embed"]["table"][tokens].astype(jnp.float32)


def head(params, x, dims, precision):
    """Final norm and LM head over the configured vocabulary."""
    x = rmsnorm(x, params["final_norm"]["scale"], dims["eps"])
    w = params["lm_head"]["w"][:, : dims["vocab_size"]]
    return mm("sd,dv->sv", x, w, precision)


def logits(params, tokens, dims, precision=F32):
    """(S,) tokens -> (S, vocab) float32 logits, all layers in one
    trace (for models that fit in float32 with their activations)."""
    x = embed(params, tokens)
    for i in range(dims["n_layers"]):
        x = layer(layer_params(params, i), x, dims, precision)
    return head(params, x, dims, precision)


def token_loss_sum(params, tokens, labels, dims, precision=F32):
    """Summed next-token cross entropy of one sequence."""
    lg = logits(params, tokens, dims, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


@functools.lru_cache(maxsize=8)
def _row_grad(dims_key, precision):
    dims = dict(dims_key)

    def f(params, tokens, labels, weight):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda p: weight * token_loss_sum(p, tokens, labels, dims,
                                                  precision))(params)
    return jax.jit(f)


def dims_key(dims: dict):
    return tuple(sorted(dims.items()))


def coded_loss_and_grad(params_by_device, blocks, v, norm, dims,
                        precision=F32, rows=None):
    """Loss and gradient of the coded objective
    sum_i v_i * CE(block i) / norm, one sequence at a time.

    ``params_by_device``: the float32 parameters, one copy per device;
    sequence r runs on device r mod len(copies), and each device sums
    its own sequences' gradients, which are then summed on the first
    device. ``blocks``: {"tokens", "labels"} of shape (n, rows, S).
    ``rows`` limits which block rows enter (a planted fault's hook);
    None takes them all. Returns (loss float, grads on device 0)."""
    fn = _row_grad(dims_key(dims), precision)
    n, per_block = blocks["tokens"].shape[:2]
    devs = [jax.tree.leaves(p)[0].devices().pop() for p in params_by_device]
    acc = [None] * len(devs)
    losses = []
    r = 0
    for i in range(n):
        for j in range(per_block):
            if rows is not None and (i, j) not in rows:
                continue
            d = r % len(devs)
            put = functools.partial(jax.device_put, device=devs[d])
            loss, g = fn(params_by_device[d], put(blocks["tokens"][i, j]),
                         put(blocks["labels"][i, j]),
                         put(np.float32(v[i] / norm)))
            acc[d] = g if acc[d] is None else jax.tree.map(jnp.add, acc[d], g)
            losses.append(loss)
            r += 1
    total = None
    for g in acc:
        if g is None:
            continue
        g = jax.device_put(g, devs[0])
        total = g if total is None else jax.tree.map(jnp.add, total, g)
    return float(sum(float(x) for x in jax.device_get(losses))), total


def pad_like(grads, params):
    """Zero-pad the head's gradient to the padded vocabulary columns the
    parameter tree holds (those columns carry no gradient)."""
    def pad(g, p):
        if g.shape == p.shape:
            return g
        return jnp.pad(g, [(0, ps - gs) for gs, ps in zip(g.shape, p.shape)])
    return jax.tree.map(pad, grads, params)


@functools.lru_cache(maxsize=8)
def _adamw(b1, b2, eps, lr, wd):
    def upd(p, g, m, v, t):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = -lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p + u - lr * wd * p, m, v

    def step(params, grads, ms, vs, t):
        out = jax.tree.map(lambda p, g, m, v: upd(p, g, m, v, t),
                           params, grads, ms, vs)
        pick = lambda k: jax.tree.map(lambda o: o[k], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)
    return jax.jit(step, donate_argnums=(0, 2, 3))


def adamw_step(params, grads, ms, vs, t: int, hp: dict):
    """AdamW as published (Loshchilov & Hutter), bias-corrected, with
    decoupled weight decay. Returns (params, m, v)."""
    fn = _adamw(hp["b1"], hp["b2"], hp["eps"], hp["lr"],
                hp.get("weight_decay", 0.0))
    return fn(params, grads, ms, vs, jnp.float32(t))


def optimal_alpha(A: np.ndarray, alive: np.ndarray) -> np.ndarray:
    """The optimal decode (Glasgow & Wootters, Eq. 9): alpha = A w with
    w the least-squares solution of A[:, alive] w = 1, i.e. the
    projection of the all-ones vector onto the survivors' columns."""
    alive = np.asarray(alive, bool)
    if not alive.any():
        return np.zeros(A.shape[0])
    w, *_ = np.linalg.lstsq(A[:, alive].astype(np.float64),
                            np.ones(A.shape[0]), rcond=None)
    return A[:, alive] @ w


def debias_scale(A: np.ndarray, p: float, trials: int, seed: int) -> float:
    """|1|_2 / |E[alpha]|_2 over ``trials`` Bernoulli(p) masks drawn as
    uniforms >= p from ``np.random.default_rng(seed)``."""
    u = np.random.default_rng(seed).random((trials, A.shape[1]))
    alphas = np.stack([optimal_alpha(A, row >= p) for row in u])
    return float(np.sqrt(A.shape[0])
                 / max(np.linalg.norm(alphas.mean(axis=0)), 1e-30))


def leaf_norms(tree) -> dict:
    """{path: float32 L2 norm} of every leaf."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.device_get([jnp.linalg.norm(x.astype(jnp.float32).ravel())
                            for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in
            zip(flat, norms)}


def host_leaves(tree) -> dict:
    """{path: numpy array} of every leaf, copied to the host one leaf at
    a time."""
    return {jax.tree_util.keystr(p): np.asarray(jax.device_get(x))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@jax.jit
def _diff_norm(a, b, scale):
    return jnp.linalg.norm((a.astype(jnp.float32) * scale
                            - b.astype(jnp.float32)).ravel())


def worst_leaf_error(program: dict, reference: dict, ref_norms: dict,
                     skip=(), scale=1.0) -> tuple:
    """max over leaves of |scale * program - reference| / max(reference
    norm, median reference norm): the norm of the difference of a leaf
    (``{path: host array}`` on both sides), against the reference norm
    of that leaf or of the median leaf, whichever is larger. Returns
    (error, leaf)."""
    keys = [k for k in reference if k not in skip]
    med = float(np.median([ref_norms[k] for k in keys]))
    errs = {k: float(_diff_norm(jnp.asarray(program[k]),
                                jnp.asarray(reference[k]),
                                np.float32(scale)))
            / max(ref_norms[k], med, 1e-30) for k in keys}
    worst = max(errs, key=errs.get)
    return errs[worst], worst


def worst_leaf_gap(program: dict, reference: dict, skip=()) -> tuple:
    """max over leaves of |program - reference| / max(reference,
    median reference): the gap between the two norms of a leaf, against
    the reference norm of that leaf or of the median leaf, whichever is
    larger. Returns (gap, leaf)."""
    keys = [k for k in reference if k not in skip]
    med = float(np.median([reference[k] for k in keys]))
    gaps = {k: abs(program[k] - reference[k]) / max(reference[k], med,
                                                    1e-30) for k in keys}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst
