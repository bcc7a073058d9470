"""Coded-vs-uncoded train-step benchmark on the devices of this process.

Measures, for the real ``repro.dist`` runtime (smoke config, on
``mesh.make_device_mesh`` over the devices the process sees -- on the
CPU the caller sets ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
for the (4, 2) mesh of virtual devices):

* per-step wall time (median over timed steps, compile excluded),
* unique tokens/s (global batch x seq len / step time -- replicated
  coded compute is overhead, not throughput),
* host-side decode latency: per-step ``CodingRuntime.step_weights``
  (sample + cached O(m) optimal decode) and the batched
  ``decode_batch`` path, in microseconds.

Nine rows, at m = 4 coded machines unless a row says otherwise: the
replicated coded step (GSPMD combine), the
deduplicated coded step (each unique block once, weighted by
v = A @ w -- the path that closes the replication-factor gap), the
manual ``coded_allreduce`` collective, the uncoded baseline, the
compression-composed dedup steps (int8 / sign / packed 1-bit sign
through the fused quantized combine, with measured
comm-bytes-per-step columns), and the streaming-vs-materialising
manual pair at m = 8 machines (two per worker shard on a 4-shard data
axis, so the ``lax.scan`` streaming accumulator genuinely halves the
live per-chunk gradients). Every row carries a ``memory`` column: the
compiled step's XLA ``memory_analysis`` (argument/output/temp/program
bytes) plus the peak host-visible live-buffer bytes sampled across
the timed steps. Inline acceptance pins the dedup step strictly under
the replicated one and the streaming step's temp bytes strictly under
the materialising manual's; the comm-bytes acceptances (int8 <= 0.3x,
sign_packed <= 0.05x float32) live in
``roofline_report.comm_report``.

Everything runs in the calling process, which holds the devices;
``main`` (the ``benchmarks.run`` entry) returns the report, which
run.py writes to BENCH_train.json. Every report names the device it
ran on.
"""

from __future__ import annotations

import json
import time

M_WORKERS = 4  # coded machines per row, independent of the device count


def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _measure_one(scheme: str, decoding: str, *, steps: int,
                 seq_len: int, block_size: int, path: str = "replicated",
                 collective: str = "gspmd",
                 compress: str = "none",
                 machines: int = 0,
                 stream_chunk: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import CodingConfig, get_config
    from repro.core import compress as compress_mod
    from repro.data.pipeline import CodedBatcher, SyntheticLM
    from repro.dist import coded_train, sharding as rules
    from repro.launch.mesh import make_device_mesh
    from repro.models import model as M
    from repro.optim import optimizers as opt_mod

    dedup = path == "dedup"
    codec = (None if compress == "none"
             else compress_mod.get_codec(compress))
    cfg = get_config("qwen1.5-4b").smoke_variant()
    mesh = make_device_mesh()
    # ``machines`` > the data-axis size gives each worker shard a
    # block of several machines -- the regime where the streaming
    # accumulator holds fewer live gradients than the materialised
    # manual combine.
    m_workers = machines or M_WORKERS
    coding = CodingConfig(scheme=scheme, replication=2, decoding=decoding,
                          straggler_p=0.2, seed=0)
    runtime = coded_train.CodingRuntime(coding, m_workers)
    assignment = runtime.assignment
    global_batch = assignment.n * block_size
    source = SyntheticLM(cfg.vocab_size, seq_len, seed=0)
    batcher = CodedBatcher(assignment, shuffle_seed=0)
    emit = batcher.unique_blocks if dedup else batcher.code_batch

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    optimizer = opt_mod.get_optimizer("adamw", 1e-3)
    opt_state = optimizer.init(params)
    pshard = rules.named(mesh, rules.safe_param_specs(params, mesh))
    repl = rules.replicated(mesh)

    comp_rows = assignment.n if dedup else m_workers
    comp_state = (compress_mod.init_state(params, comp_rows)
                  if codec else None)
    if collective == "manual":
        train_step = coded_train.make_manual_collective_train_step(
            cfg, optimizer, mesh, compress=compress if codec else None,
            streaming_chunk=stream_chunk or None)
    else:
        train_step = coded_train.make_train_step(
            cfg, optimizer, dedup=dedup,
            norm_scale=coded_train.dedup_norm_scale(assignment),
            compress=compress if codec else None)
    step_times, decode_times = [], []
    with jax.set_mesh(mesh):
        params = jax.device_put(params, pshard)
        # Shapes are static: shardings + jit once, outside the loop
        # (the same hoisting the async driver does).
        batch0 = emit(source.batch(global_batch, 0))
        bshard = (rules.block_shardings if dedup
                  else rules.batch_shardings)(mesh, batch0)
        if codec:
            comp_state = jax.device_put(comp_state, repl)
            step_fn = jax.jit(
                train_step,
                in_shardings=(pshard, None, repl, bshard, repl),
                out_shardings=(pshard, None, repl, None))
        else:
            step_fn = jax.jit(train_step,
                              in_shardings=(pshard, None, bshard, repl),
                              out_shardings=(pshard, None, None))
        # Compiled-program memory accounting: lower the jitted step on
        # abstract stand-ins (no allocation) and read XLA's
        # memory_analysis -- the column the streaming-vs-materialising
        # acceptance compares.
        sds = lambda t: jax.tree.map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(jnp.asarray(x).shape,
                                           jnp.asarray(x).dtype), t)
        wv_sds = jax.ShapeDtypeStruct((m_workers,), jnp.float32)
        abstract = ((sds(params), sds(opt_state), sds(comp_state),
                     sds(batch0), wv_sds) if codec else
                    (sds(params), sds(opt_state), sds(batch0), wv_sds))
        mem = step_fn.lower(*abstract).compile().memory_analysis()
        memory = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "generated_code_bytes": int(
                mem.generated_code_size_in_bytes),
        }
        live_peak = 0
        for step in range(steps):
            batch_np = batch0 if step == 0 else \
                emit(source.batch(global_batch, step))
            batch = {k: jax.device_put(jnp.asarray(v), bshard[k])
                     for k, v in batch_np.items()}
            t0 = time.perf_counter()
            w, _ = runtime.step_weights()
            wv = runtime.block_weights(w) if dedup else w
            decode_times.append(time.perf_counter() - t0)
            wv = jax.device_put(jnp.asarray(wv, jnp.float32), repl)
            t0 = time.perf_counter()
            if codec:
                params, opt_state, comp_state, metrics = step_fn(
                    params, opt_state, comp_state, batch, wv)
            else:
                params, opt_state, metrics = step_fn(params, opt_state,
                                                     batch, wv)
            jax.block_until_ready(metrics["loss"])
            step_times.append(time.perf_counter() - t0)
            # Live-buffer sample: every jax.Array alive after the step
            # (params, opt state, batch, metrics, residuals), peak
            # across steps -- the host-visible companion to the
            # compiled temp-bytes column.
            live_peak = max(live_peak, sum(
                int(x.nbytes) for x in jax.live_arrays()))
    memory["live_bytes_peak"] = live_peak
    warm = step_times[2:] or step_times  # first steps pay compile
    step_s = float(np.median(warm))
    # Batched host decode over one lookahead horizon of fresh masks.
    rng = np.random.default_rng(1)
    masks = rng.random((256, m_workers)) >= 0.2
    t0 = time.perf_counter()
    runtime.decode_batch(masks)
    batched_us = (time.perf_counter() - t0) / masks.shape[0] * 1e6
    # Measured comm payload: the bytes of the arrays the combine
    # actually consumed this run (quantized payload + scale sideband,
    # or full float32 gradients), next to the float32 baseline at the
    # same row count -- the columns the roofline comm report audits.
    comm = compress_mod.comm_bytes_per_step(codec, comp_rows, params)
    comm_f32 = compress_mod.comm_bytes_per_step(None, comp_rows, params)
    return {
        "scheme": scheme,
        "decoding": decoding,
        "path": path,
        "collective": collective,
        "compress": compress,
        "stream_chunk": stream_chunk,
        "memory": memory,
        "comm_bytes_per_step": comm,
        "comm_bytes_per_step_float32": comm_f32,
        "m_workers": m_workers,
        "global_batch": global_batch,
        "seq_len": seq_len,
        "step_ms": round(step_s * 1e3, 2),
        "tokens_per_s": round(global_batch * seq_len / step_s, 1),
        "decode_us_per_step": round(
            float(np.mean(decode_times[1:] or decode_times)) * 1e6, 1),
        "decode_us_per_mask_batched": round(batched_us, 1),
        "decode_calls": runtime.decode_calls,
        "final_loss": float(metrics["loss"]),
    }


def _measure_chaos(steps: int) -> dict:
    """Chaos row: the full elastic-fault-tolerance loop through the
    real train driver, in-process. Kills one of the 4 coded machines a
    third of the way in and reports detection latency, steps trained on
    the degraded mask, the elastic re-assignment record, and the final
    loss against the identical no-failure run -- straggler sampling
    off on both sides so injected chaos is the only difference."""
    from repro.launch import train as train_mod

    kill_step = max(2, steps // 3)
    base = ["--arch", "qwen1.5-4b", "--steps", str(steps),
            "--seq-len", "32", "--block-size", "2",
            "--straggler-p", "0", "--machines", str(M_WORKERS),
            "--log-every", str(max(1, steps // 2))]
    clean = train_mod.main(base)
    t0 = time.perf_counter()
    chaotic = train_mod.main(base + ["--chaos", f"kill:1@{kill_step}"])
    wall = time.perf_counter() - t0
    ch = chaotic["chaos"]
    return {
        "spec": f"kill:1@{kill_step}",
        "steps": steps,
        "wall_s": round(wall, 2),
        "steps_to_detect": ch["steps_to_detect"],
        "degraded_steps": ch["degraded_steps"],
        "reassignments": ch["reassignments"],
        "events": ch["events"],
        "m_final": ch["m_final"],
        "generations": ch["generations"],
        "final_loss": chaotic["last_loss"],
        "final_loss_clean": clean["last_loss"],
        "loss_gap": round(chaotic["last_loss"] - clean["last_loss"],
                          4),
    }


def measure(full: bool) -> dict:
    steps = 24 if full else 8
    kw = dict(steps=steps, seq_len=64, block_size=4)
    return {
        "device": device_info(),
        "steps_timed": steps,
        "runs": [
            _measure_one("expander", "optimal", path="replicated", **kw),
            _measure_one("expander", "optimal", path="dedup", **kw),
            _measure_one("expander", "optimal", path="replicated",
                         collective="manual", **kw),
            _measure_one("uncoded", "fixed", path="replicated", **kw),
            # compression-composed rows: same dedup geometry, int8 /
            # sign / packed 1-bit sign codecs through the fused
            # quantized (or packed-sign) combine
            _measure_one("expander", "optimal", path="dedup",
                         compress="int8", **kw),
            _measure_one("expander", "optimal", path="dedup",
                         compress="sign", **kw),
            _measure_one("expander", "optimal", path="dedup",
                         compress="sign_packed", **kw),
            # streaming-vs-materialising manual pair: m = 8 machines on
            # the 4-shard data axis (two per shard) so the scan-chunked
            # combine holds half the live gradients
            _measure_one("expander", "optimal", path="replicated",
                         collective="manual", machines=8, **kw),
            _measure_one("expander", "optimal", path="replicated",
                         collective="manual", machines=8,
                         stream_chunk=1, **kw),
        ],
        # elastic fault tolerance: kill + detect + re-assign vs the
        # no-failure run, through the real driver
        "chaos": _measure_chaos(steps),
    }


def find_run(runs, **want) -> dict:
    return next(r for r in runs
                if all(r.get(k) == v for k, v in want.items()))


def main(fast: bool = True) -> dict:
    report = measure(full=not fast)
    for run in report["runs"]:
        label = f"{run['scheme']}/{run['path']}/{run['collective']}"
        if run.get("compress", "none") != "none":
            label += f"/{run['compress']}"
        if run.get("stream_chunk"):
            label += f"/stream{run['stream_chunk']}"
        mem = run.get("memory", {})
        mb = 1024 ** 2
        print(f"  {label}: {run['step_ms']:.1f} ms/step, "
              f"{run['tokens_per_s']:.0f} tok/s, decode "
              f"{run['decode_us_per_step']:.0f} us/step "
              f"(batched {run['decode_us_per_mask_batched']:.0f} us/mask)"
              f", temp {mem.get('temp_bytes', 0) / mb:.0f}MB "
              f"live {mem.get('live_bytes_peak', 0) / mb:.0f}MB")
    runs = report["runs"]
    repl = find_run(runs, scheme="expander", path="replicated",
                    collective="gspmd", compress="none")
    dedup = find_run(runs, scheme="expander", path="dedup",
                     compress="none")
    uncoded = find_run(runs, scheme="uncoded")
    # Acceptance: deduplication must beat recomputing every block d
    # times; host decode must stay off the step critical path.
    assert dedup["step_ms"] < repl["step_ms"], \
        (f"dedup step ({dedup['step_ms']} ms) must beat the replicated "
         f"coded step ({repl['step_ms']} ms)")
    assert repl["decode_us_per_step"] < 0.2 * repl["step_ms"] * 1e3, \
        "host decode must stay off the step critical path"
    # Memory acceptance: the scan-chunked streaming combine must hold
    # strictly fewer compiled temp bytes (the per-machine gradient
    # working set) than the materialising manual step at the same
    # m = 8 geometry.
    manual8 = find_run(runs, collective="manual", m_workers=8,
                       stream_chunk=0)
    stream8 = find_run(runs, collective="manual", m_workers=8,
                       stream_chunk=1)
    assert stream8["memory"]["temp_bytes"] < \
        manual8["memory"]["temp_bytes"], \
        (f"streaming temp bytes ({stream8['memory']['temp_bytes']}) "
         f"must undercut the materialising manual step "
         f"({manual8['memory']['temp_bytes']})")
    print(f"  streaming/materialising temp bytes: "
          f"{stream8['memory']['temp_bytes'] / manual8['memory']['temp_bytes']:.2f}x")
    print(f"  dedup/uncoded step ratio: "
          f"{dedup['step_ms'] / uncoded['step_ms']:.2f}x "
          f"(replicated was {repl['step_ms'] / uncoded['step_ms']:.2f}x)")
    # Chaos acceptance: the kill must be detected and re-assigned
    # exactly once, and the post-failure run must land at the clean
    # run's noise floor (the paper's convergence-under-stragglers
    # claim, under real detection instead of sampled masks).
    chaos = report["chaos"]
    assert len(chaos["reassignments"]) == 1, \
        f"expected one elastic re-assignment, got {chaos}"
    assert chaos["m_final"] == 3 and chaos["generations"] == 2
    assert all(v <= 4 for v in chaos["steps_to_detect"].values()), \
        f"detection latency too high: {chaos['steps_to_detect']}"
    assert abs(chaos["loss_gap"]) < 0.6, \
        (f"chaos run ended {chaos['loss_gap']} off the clean run "
         f"({chaos['final_loss']} vs {chaos['final_loss_clean']})")
    print(f"  chaos {chaos['spec']}: detect "
          f"{chaos['steps_to_detect']} steps, degraded "
          f"{chaos['degraded_steps']}, final loss "
          f"{chaos['final_loss']:.3f} vs clean "
          f"{chaos['final_loss_clean']:.3f}")
    return report


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    print(json.dumps(main(fast=not args.full), indent=2))
