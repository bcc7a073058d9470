#!/usr/bin/env python3
"""Bring-up check on a TPU: the system's main paths, end to end.

    python chip_smoke.py             # one chip: train, serve, kernels
    python chip_smoke.py --chips 4   # four chips: the coded train step
                                     # over all four against one chip

Phases (one chip):

* train -- ``repro.launch.train.main`` runs 8 coded steps of the cut
  Qwen1.5-4B config below on the default deduplicated GSPMD path:
  m = 12 coded machines (expander code, d = 2, bernoulli stragglers at
  p = 0.2, optimal decoding), 12 blocks of one 1024-token sequence.
  The losses must be finite, and the driver asserts that their window
  mean falls.
* serve -- ``repro.launch.serve.main`` drains 16 requests through the
  continuous-batching engine (8 slots, 128-token prompts, 32 new
  tokens) with coded prefill; ``--check`` asserts the engine streams
  equal the sequential reference loop token for token.
* kernels -- each of the seven Pallas kernels runs once at the widths
  of tests/test_tpu_compile.py, in float32, and is compared with its
  reference at the tolerance the CPU kernel tests use.

With ``--chips 4`` only the multi-chip path runs: the same 8 train
steps on the trainer's own mesh over all four chips, then on a mesh of
one chip in the same process; the per-step losses must agree to
LOSS_RTOL.

Every line but the last reports a phase: its wall time, the time spent
in XLA compiles, and what it checked. The last line is one JSON object
naming the device. Without a TPU, outside a checkout of the repository,
or when any phase fails, the script exits non-zero and prints no
``"ok"`` line.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Qwen1.5-4B at its published widths (source: the model's config.json,
# huggingface.co/Qwen/Qwen1.5-4B): d_model 2560, 20 heads, 20 KV heads,
# d_ff 6912, QKV bias, bf16 compute over float32 parameters. Cut for
# one v5e chip: n_layers 40 -> 4, and vocab_size 151936 -> 37984, the
# quarter of the vocabulary one chip holds where four chips share each
# layer. It stands for one chip's share of a coded data-parallel
# fine-tuning job: 512.5 M parameters plus Adam state (6.15 GB), and
# 12 coded machines' blocks on the chip.
CUT = {"n_layers": 4, "vocab_size": 37984}

TRAIN_ARGV = ["--steps", "8", "--machines", "12", "--scheme", "expander",
              "--replication", "2", "--straggler-p", "0.2",
              "--decoding", "optimal", "--block-size", "1",
              "--seq-len", "1024", "--lr", "3e-4", "--log-every", "1",
              "--seed", "0"]
SERVE_ARGV = ["--requests", "16", "--slots", "8", "--prompt-len", "128",
              "--max-new-tokens", "32", "--max-len", "256",
              "--scheme", "expander", "--straggler-p", "0.2", "--check",
              "--seed", "0"]
# Four chips against one: bf16 activations reduced in another order
# (row-parallel matmuls, all-reduces) move each loss by well under 1%.
LOSS_RTOL = 1e-2

# Kernel widths: Qwen1.5-4B's d_model, d_ff and KV heads; 12 coded
# machines; the paper-scale decoder's n = 2184 blocks (m = 6552, d = 6).
WIDTHS = {"d_model": 2560, "d_ff": 6912, "machines": 12, "rows": 16384,
          "batch": 8, "kv_len": 4096, "kv_heads": 20, "head_dim": 128,
          "trials": 1000, "gram_rows": 4096, "blocks": 2184}


def qwen_cut():
    from repro.configs import get_config

    return get_config("qwen1.5-4b").with_overrides(**CUT)


def train_phase(cfg, argv=TRAIN_ARGV, mesh=None) -> dict:
    from repro.launch import train

    summary = train.main(argv, cfg=cfg, mesh=mesh)
    import numpy as np

    losses = summary["losses"]
    steps = int(argv[argv.index("--steps") + 1])
    if len(losses) != steps or not np.isfinite(losses).all():
        raise AssertionError(f"expected {steps} finite losses: {losses}")
    return {"m_workers": summary["m_workers"], "path": summary["path"],
            "losses": losses}


def serve_phase(cfg, argv=SERVE_ARGV) -> dict:
    from repro.launch import serve

    summary = serve.main(argv, cfg=cfg)["summary"]
    if summary["check_passed"] is not True:
        raise AssertionError("engine streams differ from the reference")
    return {k: summary[k] for k in ("requests", "new_tokens", "mesh",
                                    "check_passed")}


def kernel_phase(w=WIDTHS, interpret=False) -> dict:
    """Each kernel once on seeded float32 inputs (made on the host),
    against its reference: the jitted XLA oracle at full float32 matmul
    precision, or the NumPy float64 oracle. Returns the largest error
    of each, scaled as its tolerance is."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.batched_alpha import kernel as ba_k, ref as ba_r
    from repro.kernels.coded_combine import kernel as cc_k, ref as cc_r
    from repro.kernels.decode_attention import kernel as da_k, \
        ref as da_r
    from repro.kernels.rmsnorm import kernel as rn_k, ref as rn_r
    from repro.kernels.spectral_matvec import kernel as sm_k, \
        ref as sm_r

    rng = np.random.default_rng(0)

    def normal(*shape):
        return rng.standard_normal(shape, np.float32)

    def close(out, ref, atol, rtol):
        out = np.asarray(out, np.float64)
        ref = np.asarray(ref, np.float64)
        np.testing.assert_allclose(out, ref, atol=atol, rtol=rtol)
        return float(np.max(np.abs(out - ref) / (atol + rtol * np.abs(ref))))

    def close_scaled(out, ref, atol):
        # Errors relative to the result's largest entry (test_kernels).
        scale = max(1.0, float(np.max(np.abs(ref))))
        return close(np.asarray(out, np.float64) / scale,
                     np.asarray(ref, np.float64) / scale, atol, 0.0)

    n, leaf = w["machines"], w["d_model"] * w["d_ff"]
    wts = jnp.asarray(normal(n))
    scales = jnp.asarray(rng.uniform(0.1, 2.0, n).astype(np.float32))
    out = {}
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(normal(w["rows"], w["d_model"]))
        s = jnp.asarray(normal(w["d_model"]))
        out["rmsnorm"] = close(rn_k.rmsnorm(x, s, interpret=interpret),
                               jax.jit(rn_r.rmsnorm)(x, s), 2e-5, 2e-5)

        g = jnp.asarray(normal(n, leaf))
        out["coded_combine"] = close(
            cc_k.coded_combine(g, wts, interpret=interpret),
            jax.jit(cc_r.coded_combine)(g, wts), 2e-5, 2e-5)
        del g

        q8 = jnp.asarray(rng.integers(-127, 128, (n, leaf), np.int8))
        out["quantized_combine"] = close_scaled(
            cc_k.quantized_combine(q8, scales, wts, interpret=interpret),
            jax.jit(cc_r.quantized_combine)(q8, scales, wts), 2e-5)
        del q8

        qp = jnp.asarray(rng.integers(0, 256, (n, leaf // 8), np.uint8))
        out["packed_sign_combine"] = close_scaled(
            cc_k.packed_sign_combine(qp, scales, wts, d=leaf,
                                     interpret=interpret),
            jax.jit(cc_r.packed_sign_combine, static_argnums=3)(
                qp, scales, wts, leaf), 2e-5)
        del qp

        B, S, H, Dh = w["batch"], w["kv_len"], w["kv_heads"], w["head_dim"]
        q = jnp.asarray(normal(B, H, Dh))
        k = jnp.asarray(normal(B, S, H, Dh))
        v = jnp.asarray(normal(B, S, H, Dh))
        lengths = jnp.asarray(rng.integers(1, S + 1, B, np.int32))
        out["decode_attention"] = close(
            da_k.decode_attention(q, k, v, lengths, interpret=interpret),
            jax.jit(da_r.decode_attention)(q, k, v, lengths), 2e-5, 2e-5)
        del q, k, v

    a = (1.0 + 0.2 * normal(w["trials"], w["blocks"])).astype(np.float64)
    out["batched_alpha"] = close(
        ba_k.fused_error(jnp.asarray(a, jnp.float32), jnp.float32(1.1),
                         interpret=interpret),
        ba_r.fused_error(a, 1.1), 2e-5, 2e-5)

    x = normal(w["gram_rows"], w["blocks"]).astype(np.float64)
    vec = normal(w["blocks"]).astype(np.float64)
    out["spectral_matvec"] = close_scaled(
        sm_k.gram_matvec(jnp.asarray(x, jnp.float32),
                         jnp.asarray(vec, jnp.float32), interpret=interpret),
        sm_r.gram_matvec(x, vec), 5e-6)
    return {"max_error_over_tolerance": out}


class CompileClock:
    """Seconds of XLA backend compilation, from JAX's own event."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def run_phase(name, fn, clock) -> bool:
    t0, c0 = time.perf_counter(), clock.seconds
    try:
        # The drivers' own logs go to stderr: stdout carries only the
        # phase lines and the final JSON line.
        with contextlib.redirect_stdout(sys.stderr):
            result = fn()
    except Exception:  # noqa: BLE001 -- reported, and fails the run
        traceback.print_exc()
        print(f"phase {name}: FAILED after "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        return False
    print(f"phase {name}: ok, wall {time.perf_counter() - t0:.1f} s, "
          f"compile {clock.seconds - c0:.1f} s, "
          f"{json.dumps(result)}", flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but the process sees "
              f"{len(devices)} TPU devices", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    clock = CompileClock()
    cfg = qwen_cut()

    if args.chips == 1:
        ok = all([run_phase("train", lambda: train_phase(cfg), clock),
                  run_phase("serve", lambda: serve_phase(cfg), clock),
                  run_phase("kernels", kernel_phase, clock)])
    else:
        from repro.launch.mesh import make_test_mesh

        runs = {}

        def steps(label, mesh=None):
            runs[label] = train_phase(cfg, mesh=mesh)
            return runs[label]

        def compare():
            import numpy as np

            four = np.asarray(runs["train_4_chips"]["losses"])
            one = np.asarray(runs["train_1_chip"]["losses"])
            rel = np.abs(four - one) / np.abs(one)
            if not (rel <= LOSS_RTOL).all():
                raise AssertionError(
                    f"losses differ by up to {rel.max():.3g} "
                    f"(> {LOSS_RTOL}): {four.tolist()} vs {one.tolist()}")
            return {"max_rel_diff": float(rel.max()), "rtol": LOSS_RTOL}

        one_chip = make_test_mesh((1, 1))
        ok = (run_phase("train_4_chips", lambda: steps("train_4_chips"),
                        clock)
              and run_phase("train_1_chip",
                            lambda: steps("train_1_chip", one_chip), clock)
              and run_phase("compare", compare, clock))
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
