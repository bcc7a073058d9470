"""Compile every Pallas kernel at real widths for a described TPU v5e.

Nothing runs: the TPU compiler (installed with libtpu) compiles each
kernel for a chip that is described, not attached, and raises what the
chip's compiler would raise -- block shapes that break the (8, 128)
tiling, casts Mosaic cannot lower, VMEM overruns. Interpret-mode tests
(tests/test_kernels.py) cannot see any of that.

The widths are the ones the system runs at: Qwen1.5-4B's d_model 2560
and d_ff 6912, its 20 KV heads of 128, 12 coded machines, the
training cell's 12 sequences of 1024 tokens, and the paper-scale
decoder's n = 2184 blocks (m = 6552, d = 6).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and
test workers import every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.batched_alpha import kernel as ba_k
from repro.kernels.coded_combine import kernel as cc_k
from repro.kernels.decode_attention import kernel as da_k
from repro.kernels.flash_attention import kernel as fa_k
from repro.kernels.rmsnorm import kernel as rn_k
from repro.kernels.spectral_matvec import kernel as sm_k

D_MODEL, D_FF, N_MACHINES = 2560, 6912, 12
LEAF = D_MODEL * D_FF  # one MLP weight, flattened


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 -- no TPU library here
            pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        # A described chip's executable cannot be read back from the
        # persistent cache; keep these compiles out of it.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield jax.sharding.SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@jax.jit
def _flash_fwd_bwd(q, k, v, do):
    """The causal flash-attention forward and both backward kernels."""
    o, vjp = jax.vjp(lambda *a: fa_k.flash_attention(*a, True), q, k, v)
    return o, vjp(do)


def _compile(fn, sharding, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, jnp.dtype(dt), sharding=sharding)
            for s, dt in shapes]
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


KERNELS = {
    "rmsnorm": lambda sh: _compile(
        rn_k.rmsnorm, sh, ((16384, D_MODEL), "bfloat16"),
        ((D_MODEL,), "bfloat16")),
    "spectral_matvec": lambda sh: _compile(
        sm_k.gram_matvec, sh, ((4096, 2184), "float32"),
        ((2184,), "float32")),
    "coded_combine": lambda sh: _compile(
        cc_k.coded_combine, sh, ((N_MACHINES, LEAF), "float32"),
        ((N_MACHINES,), "float32")),
    "quantized_combine": lambda sh: _compile(
        cc_k.quantized_combine, sh, ((N_MACHINES, LEAF), "int8"),
        ((N_MACHINES,), "float32"), ((N_MACHINES,), "float32")),
    "packed_sign_combine": lambda sh: _compile(
        cc_k.packed_sign_combine, sh, ((N_MACHINES, LEAF // 8), "uint8"),
        ((N_MACHINES,), "float32"), ((N_MACHINES,), "float32"), d=LEAF),
    "decode_attention": lambda sh: _compile(
        da_k.decode_attention, sh, ((8, 20, 128), "bfloat16"),
        ((8, 4096, 20, 128), "bfloat16"), ((8, 4096, 20, 128), "bfloat16"),
        ((8,), "int32")),
    # The training cell's attention: 12 sequences of 1024 tokens, 20
    # heads of 128, in the model's (B, S, H, Dh) layout.
    "flash_attention": lambda sh: _compile(
        _flash_fwd_bwd, sh, *[((12, 1024, 20, 128), "bfloat16")] * 4),
    "batched_alpha": lambda sh: _compile(
        ba_k.fused_error, sh, ((1000, 2184), "float32"), ((), "float32")),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    KERNELS[name](one_chip)
