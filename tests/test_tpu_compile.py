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

import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.batched_alpha import kernel as ba_k
from repro.kernels.coded_combine import kernel as cc_k
from repro.kernels.decode_attention import kernel as da_k
from repro.kernels.flash_attention import kernel as fa_k
from repro.kernels.rmsnorm import kernel as rn_k
from repro.kernels.spectral_matvec import kernel as sm_k
from repro.kernels.xent_grad import kernel as xg_k

D_MODEL, D_FF, N_MACHINES = 2560, 6912, 12
TOKENS, VOCAB, PADDED_VOCAB = 12 * 1024, 37984, 38144  # the training cell
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
    # The head's softmax gradient over the training cell's tokens and
    # padded vocabulary, float32 logits in, bfloat16 out.
    "xent_grad": lambda sh: _compile(
        xg_k.xent_grad, sh, ((TOKENS, PADDED_VOCAB), "float32"),
        ((TOKENS,), "float32"), ((TOKENS,), "int32"), ((TOKENS,), "float32"),
        ((), "int32"), vocab_size=VOCAB),
    "batched_alpha": lambda sh: _compile(
        ba_k.fused_error, sh, ((1000, 2184), "float32"), ((), "float32")),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    KERNELS[name](one_chip)


def _computations(hlo: str) -> dict:
    """Name -> body text of every computation in an HLO module's text."""
    comps, name, body = {}, None, []
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name, body = head.group(1), []
        elif line == "}" and name:
            comps[name], name = "\n".join(body), None
        elif name:
            body.append(line)
    return comps


def _head_fusions_with_exp(hlo: str) -> list:
    """The fusions that hold a ``model.head`` matmul (a convolution) and,
    in themselves or a computation they call, an exponential."""
    comps = _computations(hlo)

    def reach(c, seen):
        for callee in re.findall(r"(?:calls|to_apply)=%([\w.\-]+)",
                                 comps.get(c, "")):
            if callee not in seen:
                seen.add(callee)
                reach(callee, seen)
        return seen

    return [c for c, body in comps.items() if c.startswith("fused")
            and re.search(r"convolution\(.*op_name=\"[^\"]*model\.head",
                          body)
            and any(re.search(r"\bexponential\(", comps.get(r, ""))
                    for r in reach(c, {c}))]


def test_train_step_computes_the_softmax_gradient_once(one_chip,
                                                       monkeypatch):
    """The training cell's dedup step at its widths (one layer), built as
    on the chip: the softmax gradient is one ``xent_grad`` call, and no
    fusion of the head's matmuls recomputes it (no exponential)."""
    from repro.configs import get_config
    from repro.dist import coded_train
    from repro.models import model as M
    from repro.optim import optimizers as opt_mod

    cfg = get_config("qwen1.5-4b").with_overrides(
        n_layers=1, vocab_size=VOCAB, dtype="bfloat16", param_dtype="float32")
    assert cfg.padded_vocab() == PADDED_VOCAB
    opt = opt_mod.adamw(3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.0)
    step = coded_train.make_train_step(cfg, opt, dedup=True, norm_scale=2.0)

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(opt.init, params)
    blocks = {k: jax.ShapeDtypeStruct((N_MACHINES, 1, 1024), jnp.int32)
              for k in ("tokens", "labels")}
    w = jax.ShapeDtypeStruct((N_MACHINES,), jnp.float32)
    # Code that asks for the backend sees the CPU here; the kernels are
    # taken, and compiled, as on the chip.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = jax.jit(step, donate_argnums=(0, 1)).lower(
        *placed((params, state, blocks, w))).compile().as_text()
    calls = re.findall(r"^\s*%xent_grad[\w.]* = (\w+\[[\d,]*\])\S* "
                       r"custom-call\(", hlo, re.M)
    assert calls == [f"bf16[{TOKENS},{PADDED_VOCAB}]"]
    assert _head_fusions_with_exp(hlo) == []
    assert any("model.head" in body and "convolution(" in body
               for body in _computations(hlo).values())
