"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs the pure
jnp oracle in ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.batched_alpha import kernel as ba_k, ops as ba_ops, \
    ref as ba_r
from repro.kernels.coded_combine import kernel as cc_k, ref as cc_r
from repro.kernels.decode_attention import kernel as da_k, ref as da_r
from repro.kernels.flash_attention import kernel as fa_k, ref as fa_r
from repro.kernels.rmsnorm import kernel as rn_k, ops as rn_ops, \
    ref as rn_r
from repro.kernels.spectral_matvec import kernel as sm_k, ops as sm_ops, \
    ref as sm_r
from repro.kernels.xent_grad import kernel as xg_k, ref as xg_r

RNG = np.random.default_rng(0)


def _tol(dt):
    return dict(atol=3e-2, rtol=3e-2) if dt == "bfloat16" else \
        dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", [(4, 128), (3, 5, 256), (64, 512),
                                   (1, 1024), (7, 384)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rmsnorm_kernel_matches_ref(shape, dtype):
    x = jnp.asarray(RNG.normal(size=shape), jnp.dtype(dtype))
    s = jnp.asarray(RNG.normal(size=shape[-1]), jnp.dtype(dtype))
    out = rn_k.rmsnorm(x, s, interpret=True)
    ref = rn_r.rmsnorm(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_rmsnorm_vjp_matches_autodiff():
    x = jnp.asarray(RNG.normal(size=(6, 64)), jnp.float32)
    s = jnp.asarray(RNG.normal(size=64), jnp.float32)

    def via_ops(x, s):
        return (rn_ops.rmsnorm(x, s) ** 2).sum()

    def via_raw(x, s):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, -1, keepdims=True)
        return (((xf * (var + 1e-6) ** -0.5) * s) ** 2).sum()

    g1 = jax.grad(via_ops, (0, 1))(x, s)
    g2 = jax.grad(via_raw, (0, 1))(x, s)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("B,H,KVH,S,Dh,bk", [
    (2, 8, 2, 256, 64, 64),
    (1, 4, 4, 128, 32, 128),
    (2, 16, 4, 512, 128, 256),
    (3, 4, 1, 192, 64, 64),
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_attention_kernel_matches_ref(B, H, KVH, S, Dh, bk,
                                             dtype):
    dt = jnp.dtype(dtype)
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), dt)
    k = jnp.asarray(RNG.normal(size=(B, S, KVH, Dh)), dt)
    v = jnp.asarray(RNG.normal(size=(B, S, KVH, Dh)), dt)
    lengths = jnp.asarray(RNG.integers(1, S + 1, size=B), jnp.int32)
    out = da_k.decode_attention(q, k, v, lengths, block_k=bk,
                                interpret=True)
    ref = da_r.decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_decode_attention_respects_lengths():
    """Tokens beyond `length` must not affect the result."""
    B, H, KVH, S, Dh = 1, 4, 2, 128, 32
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, KVH, Dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, KVH, Dh)), jnp.float32)
    lengths = jnp.asarray([40], jnp.int32)
    out1 = da_k.decode_attention(q, k, v, lengths, block_k=32,
                                 interpret=True)
    k2 = k.at[:, 40:].set(999.0)
    v2 = v.at[:, 40:].set(-999.0)
    out2 = da_k.decode_attention(q, k2, v2, lengths, block_k=32,
                                 interpret=True)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


def _flash_inputs(H, KVH, S, dtype, B=1, Dh=128):
    rng = np.random.default_rng(S * 10 + H * KVH)
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.normal(size=(B, S, H, Dh)), dt)
    k = jnp.asarray(rng.normal(size=(B, S, KVH, Dh)), dt)
    v = jnp.asarray(rng.normal(size=(B, S, KVH, Dh)), dt)
    return q, k, v


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


FLASH_CASES = pytest.mark.parametrize("H,KVH", [(4, 4), (4, 1)],
                                      ids=["mha", "gqa4"])
FLASH_SHAPES = pytest.mark.parametrize("S", [128, 256, 1024])
FLASH_MASKS = pytest.mark.parametrize("causal", [True, False],
                                      ids=["causal", "full"])
FLASH_DTYPES = pytest.mark.parametrize("dtype", ["float32", "bfloat16"])


@FLASH_CASES
@FLASH_SHAPES
@FLASH_MASKS
@FLASH_DTYPES
def test_flash_attention_forward_matches_oracle(H, KVH, S, causal, dtype):
    q, k, v = _flash_inputs(H, KVH, S, dtype)
    out = fa_k.flash_attention(q, k, v, causal, True)
    assert out.shape == q.shape and out.dtype == q.dtype
    ref = fa_r.flash_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                               causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), **_tol(dtype))


@FLASH_CASES
@FLASH_SHAPES
@FLASH_MASKS
@FLASH_DTYPES
def test_flash_attention_grads_match_oracle(H, KVH, S, causal, dtype):
    """dq, dk, dv of the custom VJP's two backward kernels against
    autodiff of the float32 oracle."""
    q, k, v = _flash_inputs(H, KVH, S, dtype)
    w = jnp.asarray(np.random.default_rng(S).normal(size=q.shape),
                    jnp.float32)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    got = jax.grad(loss(lambda *a: fa_k.flash_attention(*a, causal, True)),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda *a: fa_r.flash_attention(*a, causal)),
                    (0, 1, 2))(*(x.astype(jnp.float32) for x in (q, k, v)))
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    for g, x, r in zip(got, (q, k, v), want):
        assert g.shape == x.shape and g.dtype == x.dtype
        assert _rel(g, r) < tol


@pytest.mark.parametrize("blocks", [(1024, 256, 1024), (1024, 512, 128),
                                    (256, 128, 128), (128, 128, 128)])
def test_flash_attention_tilings_agree(blocks, monkeypatch):
    """Every (block, forward tile, backward tile) split of 1024 causal
    tokens gives the oracle's output and gradients: one grid step of
    tiles, 4 x 4 grid steps of 2 x 2 tiles (whole grid steps above the
    diagonal skipped), 8 x 8 grid steps of one tile."""
    monkeypatch.setattr(fa_k, "block_sizes", lambda S: blocks)
    jax.clear_caches()
    q, k, v = _flash_inputs(4, 2, 1024, "float32")
    w = jnp.asarray(np.random.default_rng(7).normal(size=q.shape),
                    jnp.float32)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    out, got = jax.value_and_grad(
        loss(lambda *a: fa_k.flash_attention(*a, True, True)),
        (0, 1, 2))(q, k, v)
    ref, want = jax.value_and_grad(
        loss(lambda *a: fa_r.flash_attention(*a, True)), (0, 1, 2))(q, k, v)
    assert abs(float(out - ref)) <= 1e-5 * abs(float(ref))
    for g, r in zip(got, want):
        assert _rel(g, r) < 1e-5
    jax.clear_caches()


@FLASH_CASES
def test_flash_attention_masked_keys_cannot_change_output(H, KVH):
    """Under the causal mask, keys and values after position t reach no
    query at or before t: in a tile that is masked element by element
    nor in one that is skipped."""
    q, k, v = _flash_inputs(H, KVH, 1024, "float32")
    t = 300
    out = fa_k.flash_attention(q, k, v, True, True)
    k2 = k.at[:, t + 1:].set(999.0)
    v2 = v.at[:, t + 1:].set(-999.0)
    out2 = fa_k.flash_attention(q, k2, v2, True, True)
    np.testing.assert_array_equal(np.asarray(out[:, :t + 1]),
                                  np.asarray(out2[:, :t + 1]))
    assert float(jnp.abs(out[:, t + 1:] - out2[:, t + 1:]).max()) > 1.0


def _xent_inputs(T, V, vocab_size, seed=0):
    """Logits with the padded ids masked as the model masks them, their
    log-sum-exp, labels with some rows ignored (-1), a scale per row."""
    rng = np.random.default_rng(seed)
    logits = rng.normal(size=(T, V)).astype(np.float32) * 3
    logits[:, vocab_size:] = -1e30
    lse = jax.nn.logsumexp(jnp.asarray(logits), axis=-1)
    labels = rng.integers(0, vocab_size, T).astype(np.int32)
    labels[::5] = -1
    scale = rng.normal(size=T).astype(np.float32)
    return jnp.asarray(logits), lse, jnp.asarray(labels), jnp.asarray(scale)


XENT_SHAPES = pytest.mark.parametrize("T,V,vocab_size", [
    (37, 512, 500),        # odd rows, padded ids
    (8, 256, 256),         # no padding
    (24, 38144, 37984),    # the training cell's padded vocabulary
    (40, 1024, 1000),      # rows in more than one block (small budget)
])


@XENT_SHAPES
def test_xent_grad_kernel_within_one_bf16_ulp_of_oracle(T, V, vocab_size,
                                                        monkeypatch):
    if T == 40:   # 16-row blocks of 1024 columns: three row blocks
        monkeypatch.setattr(xg_k, "VMEM_BUDGET", 16 * 1024 * 12)
        assert xg_k.block_sizes(T, V, xg_k.VMEM_BUDGET) == (16, V)
    args = _xent_inputs(T, V, vocab_size)
    got = xg_k.xent_grad(*args, 0, vocab_size, True)
    want = np.asarray(xg_r.xent_grad(*args, 0, vocab_size))
    assert got.dtype == jnp.bfloat16 and got.shape == (T, V)
    got = np.asarray(got.astype(jnp.float32))
    mag = np.abs(want)
    ulp = np.where(mag > 0, 2.0 ** (np.floor(np.log2(np.maximum(mag, 1e-38)))
                                    - 7), 0.0)
    assert np.all(np.abs(got - want) <= ulp)


@XENT_SHAPES
def test_xent_grad_zeroes_padded_ids_and_ignored_rows(T, V, vocab_size):
    logits, lse, labels, scale = _xent_inputs(T, V, vocab_size)
    # Even where the padded logits are not masked, nothing reaches them.
    logits = logits.at[:, vocab_size:].set(5.0)
    got = np.asarray(xg_k.xent_grad(logits, lse, labels, scale, 0,
                                    vocab_size, True).astype(jnp.float32))
    assert np.all(got[:, vocab_size:] == 0)
    assert np.all(got[np.asarray(labels) < 0] == 0)
    live = np.asarray(labels) >= 0
    assert np.all(np.abs(got[live][:, :vocab_size]).sum(-1) > 0)


def test_xent_grad_applies_the_row_scale():
    """Doubling a row's scale doubles its gradient exactly; a zero
    scale gives zeros; the one-hot term is negative at the label."""
    logits, lse, labels, scale = _xent_inputs(37, 512, 500)
    run = lambda s: np.asarray(xg_k.xent_grad(  # noqa: E731
        logits, lse, labels, s, 0, 500, True).astype(jnp.float32))
    base = run(jnp.ones_like(scale))
    np.testing.assert_array_equal(run(2 * jnp.ones_like(scale)), 2 * base)
    assert np.all(run(jnp.zeros_like(scale)) == 0)
    rows = np.flatnonzero(np.asarray(labels) >= 0)
    at_label = base[rows, np.asarray(labels)[rows]]
    assert np.all(at_label < 0) and np.all(base[rows].sum(-1) < 1e-2)


def test_xent_grad_column_offset_is_a_vocabulary_slice():
    """A device holding columns from ``offset`` on (a vocabulary-sharded
    head) computes exactly that slice of the whole gradient."""
    args = _xent_inputs(24, 1024, 1000)
    whole = xg_k.xent_grad(*args, 0, 1000, True)
    logits, rest = args[0], args[1:]
    for off in (0, 512):
        part = xg_k.xent_grad(logits[:, off:off + 512], *rest, off, 1000,
                              True)
        np.testing.assert_array_equal(np.asarray(part),
                                      np.asarray(whole[:, off:off + 512]))


@pytest.mark.parametrize("T,V", [(12288, 38144), (37, 512), (4096, 256000),
                                 (8, 128)])
def test_xent_grad_blocks_fit_the_vmem_budget(T, V):
    tm, tv = xg_k.block_sizes(T, V, xg_k.VMEM_BUDGET)
    assert tv % 128 == 0 and tv <= V
    assert tm == T or tm % 16 == 0
    assert 2 * tm * tv * (4 + 2) <= xg_k.VMEM_BUDGET
    if 16 * V * 12 <= xg_k.VMEM_BUDGET:
        assert tv == V     # whole rows where they fit


@pytest.mark.parametrize("n,D", [(8, 1000), (24, 4096), (3, 130),
                                 (1, 256), (16, 65536)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_coded_combine_kernel_matches_ref(n, D, dtype):
    dt = jnp.dtype(dtype)
    g = jnp.asarray(RNG.normal(size=(n, D)), dt)
    w = jnp.asarray(RNG.normal(size=n), jnp.float32)
    out = cc_k.coded_combine(g, w, interpret=True)
    ref = cc_r.coded_combine(g, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def _exact_qsw(rng, n, D, payload):
    """Exactness-preserving quantized-combine inputs: integer payload,
    power-of-two scales and weights (with straggler zeros). Every
    float32 partial sum is exact (n * 127 * 2^spread << 2^24), so the
    combine's bits are independent of accumulation order and FMA
    contraction -- the regime where a bitwise pin is meaningful."""
    q = rng.integers(-127, 128, size=(n, D)).astype(
        np.int8 if payload == "int8" else np.float32)
    s = (2.0 ** rng.integers(-4, 1, size=n)).astype(np.float32)
    w = (rng.choice([-1.0, 0.0, 1.0], size=n)
         * 2.0 ** rng.integers(-2, 3, size=n)).astype(np.float32)
    return q, s, w


@pytest.mark.parametrize("n,D", [(1, 256), (2, 130), (4, 1000),
                                 (7, 61), (16, 4096), (3, 129)])
@pytest.mark.parametrize("payload", ["int8", "float32"])
def test_quantized_combine_kernel_bit_identical_to_np(n, D, payload):
    """The fused dequantize-weight-combine pins BITWISE against the
    exact NumPy oracle on exactness-preserving inputs -- across
    payload dtypes, odd widths that force lane padding, and zeroed
    straggler rows. The jnp fallback must land on the same bits."""
    rng = np.random.default_rng(n * 1000 + D)
    q, s, w = _exact_qsw(rng, n, D, payload)
    ref = cc_r.quantized_combine_np(q, s, w)
    out = cc_k.quantized_combine(jnp.asarray(q), jnp.asarray(s),
                                 jnp.asarray(w), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), ref)
    fallback = jax.jit(cc_r.quantized_combine)(
        jnp.asarray(q), jnp.asarray(s), jnp.asarray(w))
    np.testing.assert_array_equal(np.asarray(fallback), ref)


@pytest.mark.parametrize("n,D", [(2, 73), (5, 700), (6, 69), (16, 4096)])
def test_quantized_combine_general_inputs_tolerance(n, D):
    """General scales/weights: the float32 chain differs from the
    exact f64 oracle by accumulation rounding only (XLA's per-lane FMA
    contraction mix -- see ref.quantized_combine_np), bounded by the
    repo's float32 kernel tolerance."""
    rng = np.random.default_rng(n * 1000 + D)
    q = rng.integers(-127, 128, size=(n, D)).astype(np.int8)
    s = (rng.uniform(0.1, 2.0, size=n)
         * 10.0 ** rng.integers(-2, 3, size=n)).astype(np.float32)
    w = rng.normal(size=n).astype(np.float32)
    ref = np.asarray(cc_r.quantized_combine_np(q, s, w), np.float64)
    out = cc_k.quantized_combine(jnp.asarray(q), jnp.asarray(s),
                                 jnp.asarray(w), interpret=True)
    scale = max(1.0, float(np.abs(ref).max()))
    np.testing.assert_allclose(np.asarray(out, np.float64) / scale,
                               ref / scale, atol=2e-5, rtol=0)
    eager = cc_r.quantized_combine(jnp.asarray(q), jnp.asarray(s),
                                   jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(eager, np.float64) / scale,
                               ref / scale, atol=2e-5, rtol=0)


def test_quantized_combine_matches_dequantized_coded_combine():
    """Semantics, not bit patterns: the fused path equals dequantize-
    then-coded_combine at float tolerance."""
    q = RNG.integers(-127, 128, size=(6, 513)).astype(np.int8)
    s = RNG.uniform(0.1, 2.0, size=6).astype(np.float32)
    w = RNG.normal(size=6).astype(np.float32)
    g = jnp.asarray(q, jnp.float32) * jnp.asarray(s)[:, None]
    out = cc_k.quantized_combine(jnp.asarray(q), jnp.asarray(s),
                                 jnp.asarray(w), interpret=True)
    ref = cc_r.coded_combine(g, jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_quantized_combine_matches_dequantized_coded_combine():
    """Semantics, not bit patterns: the fused path equals dequantize-
    then-coded_combine at float tolerance."""
    q = RNG.integers(-127, 128, size=(6, 513)).astype(np.int8)
    s = RNG.uniform(0.1, 2.0, size=6).astype(np.float32)
    w = RNG.normal(size=6).astype(np.float32)
    g = jnp.asarray(q, jnp.float32) * jnp.asarray(s)[:, None]
    out = cc_k.quantized_combine(jnp.asarray(q), jnp.asarray(s),
                                 jnp.asarray(w), interpret=True)
    ref = cc_r.coded_combine(g, jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _exact_packed(rng, n, D):
    """Exactness-preserving packed inputs: arbitrary bit payload,
    power-of-two scales, {-1, 0, 1} x power-of-two weights -- every
    product and partial sum is a small exact float32."""
    q = rng.integers(0, 256, size=(n, (D + 7) // 8)).astype(np.uint8)
    s = (2.0 ** rng.integers(-4, 1, size=n)).astype(np.float32)
    w = (rng.choice([-1.0, 0.0, 1.0], size=n)
         * 2.0 ** rng.integers(-2, 3, size=n)).astype(np.float32)
    return q, s, w


@pytest.mark.parametrize("n,D", [(1, 256), (2, 130), (4, 1000),
                                 (7, 61), (16, 4096), (3, 129),
                                 (5, 8)])
def test_packed_sign_combine_kernel_bit_identical_to_np(n, D):
    """The fused unpack-weight-combine pins BITWISE against the exact
    float64 NumPy oracle (np.unpackbits decoder) on exactness-
    preserving inputs -- across widths that are and are not multiples
    of 8 (trailing-byte padding) and zeroed straggler rows. The jnp
    fallback must land on the same bits."""
    rng = np.random.default_rng(n * 1000 + D)
    q, s, w = _exact_packed(rng, n, D)
    ref = cc_r.packed_sign_combine_np(q, s, w, D)
    out = cc_k.packed_sign_combine(jnp.asarray(q), jnp.asarray(s),
                                   jnp.asarray(w), d=D, interpret=True)
    assert out.shape == (D,)
    np.testing.assert_array_equal(np.asarray(out), ref)
    fallback = cc_r.packed_sign_combine(jnp.asarray(q), jnp.asarray(s),
                                        jnp.asarray(w), D)
    np.testing.assert_array_equal(np.asarray(fallback), ref)


@pytest.mark.parametrize("block_db", [8, 128, None])
def test_packed_sign_combine_block_db_variants(block_db):
    """Grid tiling over the packed axis cannot change a single bit."""
    rng = np.random.default_rng(9)
    D = 3000  # padded packed axis: 375 bytes -> lane-aligned tiles
    q, s, w = _exact_packed(rng, 4, D)
    ref = cc_r.packed_sign_combine_np(q, s, w, D)
    out = cc_k.packed_sign_combine(jnp.asarray(q), jnp.asarray(s),
                                   jnp.asarray(w), d=D,
                                   block_db=block_db, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_packed_sign_combine_general_inputs_tolerance():
    """General scales/weights: float32 accumulation vs the f64 oracle,
    bounded by the repo's kernel tolerance."""
    rng = np.random.default_rng(17)
    n, D = 6, 700
    q = rng.integers(0, 256, size=(n, (D + 7) // 8)).astype(np.uint8)
    s = (rng.uniform(0.1, 2.0, size=n)
         * 10.0 ** rng.integers(-2, 3, size=n)).astype(np.float32)
    w = rng.normal(size=n).astype(np.float32)
    ref = np.asarray(cc_r.packed_sign_combine_np(q, s, w, D),
                     np.float64)
    out = cc_k.packed_sign_combine(jnp.asarray(q), jnp.asarray(s),
                                   jnp.asarray(w), d=D, interpret=True)
    scale = max(1.0, float(np.abs(ref).max()))
    np.testing.assert_allclose(np.asarray(out, np.float64) / scale,
                               ref / scale, atol=2e-5, rtol=0)


def test_dead_rows_cannot_influence_packed_combine():
    """w_j == 0 zeroes u_j = w_j * s_j exactly: perturbing a straggler
    row's packed payload must leave the combine BITWISE unchanged."""
    rng = np.random.default_rng(5)
    D = 400
    q, s, w = _exact_packed(rng, 5, D)
    w[1] = 0.0
    w[3] = 0.0
    q2 = q.copy()
    q2[1] = 0xFF
    q2[3] = 0x00
    for fn in (lambda *a: cc_r.packed_sign_combine_np(*a, D),
               lambda *a: cc_k.packed_sign_combine(
                   *map(jnp.asarray, a), d=D, interpret=True)):
        np.testing.assert_array_equal(np.asarray(fn(q, s, w)),
                                      np.asarray(fn(q2, s, w)))


def test_packed_sign_combine_rejects_mismatched_width():
    q = jnp.zeros((2, 4), jnp.uint8)
    with pytest.raises(ValueError, match="width"):
        cc_k.packed_sign_combine(q, jnp.ones(2), jnp.ones(2), d=64,
                                 interpret=True)


@pytest.mark.parametrize("T,n,bt", [(4, 128, None), (10, 130, 8),
                                    (64, 1000, 16), (1, 256, None),
                                    (33, 384, 8)])
def test_batched_alpha_fused_error_kernel_matches_ref(T, n, bt):
    a = RNG.normal(loc=1.0, scale=0.2, size=(T, n))
    scale = float(RNG.uniform(0.5, 1.5))
    out = ba_k.fused_error(jnp.asarray(a, jnp.float32),
                           jnp.float32(scale), block_t=bt,
                           interpret=True)
    ref = ba_r.fused_error(a, scale)
    np.testing.assert_allclose(np.asarray(out, np.float64), ref,
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("R,k,br", [(64, 16, None), (100, 1, 16),
                                    (256, 130, 32), (33, 64, None),
                                    (17, 384, 8), (2184, 30, None)])
def test_spectral_matvec_kernel_matches_ref(R, k, br):
    x = RNG.normal(size=(R, k))
    v = RNG.normal(size=k)
    out = sm_k.gram_matvec(jnp.asarray(x, jnp.float32),
                           jnp.asarray(v, jnp.float32), block_r=br,
                           interpret=True)
    ref = sm_r.gram_matvec(x, v)
    scale = max(1.0, float(np.abs(ref).max()))
    np.testing.assert_allclose(np.asarray(out, np.float64) / scale,
                               ref / scale, atol=5e-6, rtol=0)


def test_spectral_matvec_ops_is_float64_oracle_on_cpu():
    x = RNG.normal(size=(50, 7))
    v = RNG.normal(size=7)
    np.testing.assert_array_equal(sm_ops.gram_matvec(x, v),
                                  sm_r.gram_matvec(x, v))
    with pytest.raises(ValueError, match="R, k"):
        sm_ops.gram_matvec(x, np.ones(3))


@pytest.mark.parametrize("R,k,bv", [(64, 16, 1), (100, 30, 4),
                                    (33, 130, 7), (2184, 30, 3)])
def test_spectral_matvec_block_kernel_matches_ref(R, k, bv):
    """The widened-tile block form: bv right-hand sides per pass."""
    x = RNG.normal(size=(R, k))
    V = RNG.normal(size=(k, bv))
    out = sm_k.gram_matvec(jnp.asarray(x, jnp.float32),
                           jnp.asarray(V.T, jnp.float32),
                           interpret=True)
    ref = sm_r.gram_matvec_block(x, V)
    scale = max(1.0, float(np.abs(ref).max()))
    np.testing.assert_allclose(np.asarray(out, np.float64).T / scale,
                               ref / scale, atol=5e-6, rtol=0)


@pytest.mark.parametrize("B,R,k,br", [(1, 64, 16, None), (5, 100, 30, 16),
                                      (3, 33, 130, 8), (12, 2184, 30, None)])
def test_spectral_matvec_batch_kernel_matches_ref(B, R, k, br):
    """The lockstep batch form: grid (B, R // br), one accumulator tile
    per slice."""
    x = RNG.normal(size=(B, R, k))
    v = RNG.normal(size=(B, k))
    out = sm_k.gram_matvec_batch(jnp.asarray(x, jnp.float32),
                                 jnp.asarray(v, jnp.float32),
                                 block_r=br, interpret=True)
    ref = sm_r.gram_matvec_batch(x, v)
    scale = max(1.0, float(np.abs(ref).max()))
    np.testing.assert_allclose(np.asarray(out, np.float64) / scale,
                               ref / scale, atol=5e-6, rtol=0)


def test_spectral_matvec_block_and_batch_ops_oracle_on_cpu():
    x = RNG.normal(size=(40, 9))
    V = RNG.normal(size=(9, 3))
    np.testing.assert_array_equal(sm_ops.gram_matvec_block(x, V),
                                  sm_r.gram_matvec_block(x, V))
    xb = RNG.normal(size=(4, 40, 9))
    vb = RNG.normal(size=(4, 9))
    np.testing.assert_array_equal(sm_ops.gram_matvec_batch(xb, vb),
                                  sm_r.gram_matvec_batch(xb, vb))
    # batch oracle == single-slice oracle per slice, by construction
    for i in range(4):
        np.testing.assert_array_equal(
            sm_r.gram_matvec_batch(xb, vb)[i],
            sm_r.gram_matvec(xb[i], vb[i]))
    with pytest.raises(ValueError, match="k, b"):
        sm_ops.gram_matvec_block(x, np.ones((3, 2)))
    with pytest.raises(ValueError, match="B, R, k"):
        sm_ops.gram_matvec_batch(xb, np.ones((4, 3)))


def test_batched_alpha_ops_debias_matches_debias_alpha():
    from repro.core.decoding import debias_alpha

    a = RNG.normal(loc=1.0, scale=0.1, size=(32, 24))
    errs, scale = ba_ops.fused_error(a, debias=True)
    ab = debias_alpha(a)
    np.testing.assert_array_equal(errs, np.mean((ab - 1.0) ** 2, axis=1))
    np.testing.assert_array_equal(a * scale, ab)
    errs0, scale0 = ba_ops.fused_error(a, debias=False)
    assert scale0 == 1.0
    np.testing.assert_array_equal(errs0, np.mean((a - 1.0) ** 2, axis=1))


def test_coded_combine_tree():
    from repro.kernels.coded_combine import ops
    tree = {"a": jnp.arange(12.0).reshape(4, 3),
            "b": jnp.ones((4, 2, 2))}
    w = jnp.asarray([1.0, 0.0, 2.0, 0.5])
    out = ops.coded_combine_tree(tree, w)
    np.testing.assert_allclose(
        out["a"], (tree["a"] * w[:, None]).sum(0), rtol=1e-6)
    np.testing.assert_allclose(out["b"], 3.5 * jnp.ones((2, 2)),
                               rtol=1e-6)


def test_xent_grad_under_a_mesh_matches_one_device():
    """Under (4, 1), (2, 2) and (1, 4) meshes of virtual CPU devices the
    kernel runs in ``shard_map`` over rows and, where the ``model`` axis
    splits it, the vocabulary: the result is the one-device result bit
    for bit, and the head's gradients in ``train_loss`` agree with the
    one-device gradients to 1e-4 (where a split sum moves a float32
    value across a bfloat16 rounding boundary, an element of the
    head's gradient differs by one bfloat16 unit). Subprocess: the
    test process keeps one CPU device."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.dist import sharding as rules
from repro.kernels.xent_grad import ops
from repro.launch.mesh import make_test_mesh
from repro.models import model as M
ops._FORCE = "pallas"
rng = np.random.default_rng(0)
T, V, vs = 48, 1024, 1000
lg = jnp.asarray(rng.normal(size=(T, V)), jnp.float32)
lse = jax.nn.logsumexp(lg[:, :vs], axis=-1)
lab = jnp.asarray(rng.integers(-1, vs, T), jnp.int32)
s = jnp.asarray(rng.normal(size=T), jnp.float32)
run = jax.jit(lambda *a: ops.xent_grad(*a, vocab_size=vs))
one = np.asarray(run(lg, lse, lab, s))
cfg = get_config("qwen1.5-4b").smoke_variant()
params = M.init_params(cfg, jax.random.PRNGKey(0))
tok = jax.random.randint(jax.random.PRNGKey(1), (4, 24), 0, cfg.vocab_size)
batch = {"tokens": tok, "labels": tok}
grad = lambda p: jax.grad(lambda q: M.train_loss(q, batch, cfg))(p)
g1 = jax.jit(grad)(params)
for shape in [(4, 1), (2, 2), (1, 4)]:
    mesh = make_test_mesh(shape)
    with jax.set_mesh(mesh):
        np.testing.assert_array_equal(np.asarray(run(lg, lse, lab, s)), one)
        placed = jax.device_put(params, rules.named(
            mesh, rules.safe_param_specs(params, mesh)))
        gm = jax.jit(grad)(placed)
    for a, b in zip(jax.tree.leaves(gm), jax.tree.leaves(g1)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b), shape
print("OK")
"""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout
