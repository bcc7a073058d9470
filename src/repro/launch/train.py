"""End-to-end coded LM training driver.

Runs REAL training (not a dry-run): synthetic LM corpus -> coded block
partitioner -> shard_map/pjit coded train step with host-side straggler
sampling + O(m) optimal decoding. The mesh is built over the devices
the process sees (``mesh.make_device_mesh``). On the CPU a caller that
wants several devices sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in the
environment of the process it starts. The driver uses the reduced
smoke configs unless ``--full-config`` is given or ``main`` is passed
a ``cfg``.

``--machines M`` sets the number m of coded machines independently of
the chips: the data axis carries the blocks of all m machines, so one
chip can run the paper's m >> chips regime. The default 0 keeps
m = the data-axis size.

The loop is an async pipeline: shardings and the jitted step are built
once per *generation* (shapes are static until an elastic
re-assignment changes them), host batch construction is
double-buffered against device compute on a worker thread, straggler
masks are pre-sampled and decoded ``--lookahead`` rounds at a time on
that same worker thread (``coded_train.LookaheadPrefetcher``, one
chunk ahead of the device), and metrics stay on device (alpha-bar
included) until a ``--log-every`` boundary -- the host never blocks on
the device inside the steady-state loop. A failure on the worker
thread is never swallowed: the pending future re-raises on the main
loop, queued work is cancelled, and the driver exits with the original
traceback (tests/test_smoke_train.py injects one via
``REPRO_FAIL_BATCH_AT``).

Execution path: ``--dedup`` (default) runs every unique block once,
weighted by v = A @ w (~1x uncoded FLOPs); ``--no-dedup`` materialises
the replicated (m, load, ...) machine batch, the faithful simulation of
a real straggling cluster; ``--collective manual`` additionally routes
the combine through the explicit ``coded_allreduce`` shard_map psum
(replicated path only), and ``--stream-chunk N`` swaps its
materialised combine for the ``lax.scan`` streaming accumulator that
keeps only one machine chunk of gradients live per worker shard.
``--compress sign|sign_packed|int8`` composes the coding layer with
gradient compression: per-worker quantization with error feedback, the
fused quantized (or packed-sign) combine, comm-bytes-per-step in the
on-device metrics, and the residual state checkpointed alongside
opt_state so resumes stay bit-identical. ``--fsdp`` shards params and
Adam moments over the worker axes (``rules.fsdp_specs``) instead of
replicating them.

Every layer of the loop is timed by ``repro.spans``: the data layer
(``data.batch``, ``data.blocks``) and the coding layer
(``coding.lookahead``, ``coding.wait``, ``coding.step_weights``) from
inside, and the loop's own phases here (``train.batch_wait``,
``train.dispatch``, ``train.sync``, ``train.checkpoint``,
``train.reassign``), each step also marked by a profiler
``StepTraceAnnotation``. The summary's ``spans`` table gives each
name's count, total_s, mean_ms and max_ms over the run.

``--chaos <spec>`` flips the straggler masks from *sampled* to
*observed*: a seeded ``dist.chaos.ChaosInjector`` simulates per-step
per-machine completion timestamps (kills, delays, rack failures,
flapping -- see ``dist/chaos.py`` for the spec grammar), a
``dist.failures.HeartbeatMonitor`` derives each round's alive mask by
deadline, and ``dead_after`` consecutive missed heartbeats trigger an
elastic re-assignment: the expander is re-drawn over the m-1 survivors
(``coded_train.elastic_reassign``), block shards remap through the
sharding rules' divisibility fallback, and training resumes from the
live {params, opt_state} without a restart. Every detection and
re-assignment lands in the structured failure-event log (summary
``chaos`` key; ``--event-log FILE`` writes it as a JSON artifact).

  python -m repro.launch.train --arch qwen1.5-4b --steps 20 \
      --straggler-p 0.2 --scheme expander --decoding optimal
"""

import argparse
import contextlib
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.checkpoint import checkpoint as ckpt
from repro.configs import CodingConfig, get_config
from repro.core import compress as compress_mod
from repro.core import step_weights as sw
from repro.data.pipeline import CodedBatcher, SyntheticLM
from repro.dist import chaos as chaos_mod
from repro.dist import coded_train, failures, sharding as rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_device_mesh, make_production_mesh
from repro.models import model as M
from repro.optim import optimizers as opt_mod


def main(argv=None, *, cfg=None, mesh=None) -> dict:
    """Run the driver on ``argv``. ``cfg`` (a ModelConfig) replaces
    the registry config that ``--arch`` / ``--full-config`` select;
    ``mesh`` (with a "data" and a "model" axis) replaces the mesh over
    every device."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--machines", type=int, default=0,
                    help="coded machines m (0: the mesh's data-axis "
                         "size); the data axis carries all m machines' "
                         "blocks, so m may exceed the chip count")
    ap.add_argument("--scheme", default="expander",
                    choices=("expander", "frc", "uncoded", "cyclic_mds",
                             "bibd", "random_regular"))
    ap.add_argument("--decoding", default="optimal",
                    choices=("optimal", "fixed"))
    ap.add_argument("--adaptive", default="none",
                    choices=("none", "adaptive", "always_optimal",
                             "always_fixed"),
                    help="per-step decoding policy (core.adaptive): "
                         "estimate p-hat online from the observed mask "
                         "stream and switch optimal-vs-fixed decoding "
                         "per step ('adaptive'); the always_* anchors "
                         "pin the static behaviours ('none': the "
                         "configured --decoding, no estimator)")
    ap.add_argument("--straggler-model", default="bernoulli",
                    choices=("bernoulli", "markov", "adversarial"))
    ap.add_argument("--straggler-p", type=float, default=0.2)
    ap.add_argument("--replication", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--dedup", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="run each unique block once, weighted by "
                         "v = A @ w; on by default under --collective "
                         "gspmd (--no-dedup: replicate blocks onto "
                         "machines as a real cluster would)")
    ap.add_argument("--collective", default="gspmd",
                    choices=("gspmd", "manual"),
                    help="gradient combine: GSPMD-inserted psum vs the "
                         "explicit coded_allreduce shard_map (manual "
                         "implies the replicated path)")
    ap.add_argument("--compress", default="none",
                    choices=("none", "sign", "sign_packed", "int8"),
                    help="quantize per-worker gradients before the "
                         "coded combine (error feedback on; the fused "
                         "quantized_combine / packed_sign_combine "
                         "kernel consumes the payload directly)")
    ap.add_argument("--stream-chunk", type=int, default=0,
                    help="stream the manual-collective combine over "
                         "machine chunks of this size per worker shard "
                         "(0: materialise all per-machine gradients; "
                         "requires --collective manual)")
    ap.add_argument("--fsdp", action="store_true",
                    help="shard params and Adam moments over the "
                         "worker axes (rules.fsdp_specs) instead of "
                         "replicating them")
    ap.add_argument("--lookahead", type=int, default=8,
                    help="straggler rounds pre-sampled and decoded per "
                         "batched decode_batch call (ignored under "
                         "--chaos: observed masks decode per step)")
    ap.add_argument("--log-every", type=int, default=0,
                    help="steps between host metric fetches "
                         "(0: steps // 10)")
    ap.add_argument("--full-config", action="store_true",
                    help="use the full architecture (TPU pods)")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save a full {params, opt_state} checkpoint "
                         "(plus the error-feedback residual under "
                         "--compress) every N steps (0: only at the "
                         "end); a later "
                         "run with the same flags and --ckpt-dir "
                         "resumes from the latest step bit-identically")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="inject seeded virtual failures and derive "
                         "straggler masks from heartbeats instead of "
                         "sampling them; SPEC is semicolon-separated "
                         "kill:J@S / rack:J,K@S / delay:J@S-E[:X] / "
                         "flap:J@S-E[:K] events (dist/chaos.py); a "
                         "machine declared dead triggers elastic "
                         "re-assignment over the survivors")
    ap.add_argument("--dead-after", type=int, default=3,
                    help="consecutive missed heartbeats before a "
                         "machine is declared dead (chaos mode)")
    ap.add_argument("--heartbeat-deadline", type=float, default=0.5,
                    help="base per-step completion deadline in virtual "
                         "seconds (chaos mode; exponential backoff "
                         "widens it per consecutive miss)")
    ap.add_argument("--event-log", default=None, metavar="FILE",
                    help="write the structured failure-event log (the "
                         "summary's chaos object) to FILE as JSON")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.collective == "manual" and args.microbatches != 1:
        ap.error("--microbatches is only supported with "
                 "--collective gspmd")
    if args.collective == "manual" and args.dedup:
        # The manual collective reduces the per-machine gradients the
        # replicated batch produces; dedup has no machine axis.
        ap.error("--dedup is only supported with --collective gspmd")
    if args.compress != "none" and args.microbatches != 1:
        # The error-feedback residual updates once per compression
        # round, i.e. per full-batch step.
        ap.error("--compress does not compose with --microbatches")
    if args.stream_chunk and args.collective != "manual":
        ap.error("--stream-chunk requires --collective manual (the "
                 "streaming accumulator replaces the materialised "
                 "manual combine)")
    if args.chaos:
        if args.collective != "gspmd" or args.dedup is False:
            # Elastic re-assignment changes the machine count; only
            # the dedup path's block axis has the divisibility-fallback
            # shardings that absorb the new geometry.
            ap.error("--chaos requires the default gspmd dedup path")
        if args.ckpt_dir:
            ap.error("--chaos does not compose with --ckpt-dir: a "
                     "checkpoint records no failure history, so a "
                     "resumed chaos run could not replay the observed "
                     "masks bit-identically")
    elif args.event_log:
        ap.error("--event-log only applies under --chaos")

    if args.machines < 0:
        ap.error("--machines must be >= 0")
    spans.clear()   # the summary's spans table covers this run alone
    if cfg is None:
        cfg = get_config(args.arch)
        if not args.full_config:
            cfg = cfg.smoke_variant()
    dedup = args.collective == "gspmd" and args.dedup is not False
    coding = CodingConfig(
        scheme=args.scheme, replication=args.replication,
        decoding=args.decoding, straggler_model=args.straggler_model,
        straggler_p=args.straggler_p, seed=args.seed)

    adaptive = None if args.adaptive == "none" else args.adaptive

    def coded_runtime(m):
        """The coding runtime over m machines (and, under --chaos, its
        failure injector, heartbeat monitor and survivor map). A
        (scheme, m, d) the code cannot take fails here, naming the flag
        that sets m."""
        chaos = (None, None, None)
        kw = {"adaptive": adaptive}
        if args.chaos:
            # Chaos mode swaps the runtime's mask source from sampled
            # to observed: masks are pushed per step from the heartbeat
            # monitor instead of drawn from the straggler model.
            schedule = chaos_mod.parse_chaos_spec(args.chaos, m)
            chaos = (chaos_mod.ChaosInjector(schedule, m, seed=args.seed),
                     failures.HeartbeatMonitor(
                         m, deadline=args.heartbeat_deadline,
                         dead_after=args.dead_after),
                     failures.SurvivorMap(m))
            kw["mask_source"] = sw.ObservedMaskSource(m)
        try:
            return (coded_train.CodingRuntime(coding, m, **kw), *chaos)
        except (ValueError, RuntimeError) as e:
            ap.error(f"--machines: no {args.scheme} code with m={m} "
                     f"machines and --replication {args.replication} "
                     f"({e})")

    m_workers = args.machines
    if m_workers:
        # Known before the device is touched: validate the code first.
        runtime, injector, monitor, surv = coded_runtime(m_workers)
    enable_compile_cache()

    if mesh is None:
        mesh = (make_production_mesh() if args.production_mesh
                else make_device_mesh())

    if not m_workers:
        m_workers = mesh.shape["data"] * mesh.shape.get("pod", 1)
        runtime, injector, monitor, surv = coded_runtime(m_workers)
    lookahead = max(1, args.lookahead)
    log_every = args.log_every or max(1, args.steps // 10)

    source = SyntheticLM(cfg.vocab_size, args.seq_len, seed=args.seed)

    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key)
    optimizer = opt_mod.get_optimizer("adamw", args.lr)
    opt_state = optimizer.init(params)
    # Compression layer: per-row (machine, or unique block on the
    # dedup path) error-feedback residuals ride alongside opt_state,
    # and the comm-bytes accounting compares the codec's wire payload
    # against the float32 baseline the uncompressed combine ships.
    compress = None if args.compress == "none" else args.compress
    n_blocks0 = runtime.assignment.n
    comp_rows = n_blocks0 if dedup else m_workers
    comp_state = (compress_mod.init_state(params, comp_rows)
                  if compress else None)
    codec = compress_mod.get_codec(compress) if compress else None
    comm_bytes = compress_mod.comm_bytes_per_step(codec, comp_rows,
                                                  params)
    comm_bytes_f32 = compress_mod.comm_bytes_per_step(None, comp_rows,
                                                      params)
    # Resume: checkpoints carry the full {params, opt_state} training
    # state plus their step number. Restoring and fast-forwarding the
    # host-side streams (data batches are a pure function of the step;
    # runtime.skip replays the straggler RNG) makes the resumed
    # loss/metric stream bit-identical to an uninterrupted run --
    # pinned by tests/test_checkpoint_resume.py.
    start = 0
    if args.ckpt_dir:
        # Resume from the newest checkpoint at or before --steps (a
        # later-step checkpoint must not masquerade as an earlier one).
        usable = [s for s in ckpt.saved_steps(args.ckpt_dir)
                  if s <= args.steps]
        if usable:
            # Ordered templates, newest layout first: compressed runs
            # save {params, opt_state, compress}; uncompressed the
            # composite pair; the original PR saved params only. A
            # mismatched template fails restore's validation and the
            # next is tried; a torn file (crash mid-write, truncated
            # copy) fails np.load and restore_fallback walks back to
            # the previous intact step instead of wedging the resume.
            templates = []
            if compress:
                templates.append(("compressed",
                                  {"params": params,
                                   "opt_state": opt_state,
                                   "compress": comp_state}))
            templates += [("composite", {"params": params,
                                         "opt_state": opt_state}),
                          ("params", params)]
            step0, label, state = ckpt.restore_fallback(
                args.ckpt_dir, templates, max_step=args.steps)
            if step0 != usable[-1]:
                print(f"checkpoint(s) past step {step0} in "
                      f"{args.ckpt_dir} are unreadable; fell back to "
                      f"the newest intact step")
            if label == "params":
                # Pre-composite (params-only) checkpoint layout: keep
                # the historical behavior -- warm-start the params and
                # train from step 0.
                params = state
                print(f"restored params-only checkpoint from "
                      f"{args.ckpt_dir}; training from step 0")
            else:
                params = state["params"]
                opt_state = state["opt_state"]
                if label == "compressed":
                    comp_state = state["compress"]
                elif compress:
                    # Composite checkpoint from an uncompressed run:
                    # resume training state, start compression with a
                    # fresh (zero) residual.
                    print("checkpoint has no compression state; "
                          "resuming with zero error-feedback residual")
                start = step0
                runtime.skip(start)
                print(f"restored step-{step0} {label} checkpoint from "
                      f"{args.ckpt_dir}")
        elif ckpt.saved_steps(args.ckpt_dir):
            raise SystemExit(
                f"--ckpt-dir {args.ckpt_dir} only has checkpoints past "
                f"--steps {args.steps}; refusing to relabel a "
                "later-step state")

    da = rules.data_axes(mesh)
    da1 = da if len(da) > 1 else da[0]
    M.set_residual_sharding(batch_axes=da1, model_axis="model")
    pspec = (rules.fsdp_specs if args.fsdp
             else rules.safe_param_specs)(params, mesh)
    pshard = rules.named(mesh, pspec)
    repl = rules.replicated(mesh)
    oshard = {"step": repl, "m": pshard, "v": pshard}

    # Fault-injection hook for the pipeline-hardening regression test:
    # the batch builder raises at this step (on the worker thread when
    # it is the double-buffered step), and the driver must die with
    # that traceback instead of training on or hanging.
    fail_at = int(os.environ.get("REPRO_FAIL_BATCH_AT", "-1"))

    pool = ThreadPoolExecutor(max_workers=1)
    with jax.set_mesh(mesh):
        params = jax.device_put(params, pshard)
        opt_state = jax.device_put(opt_state, oshard)

        losses = []
        metrics_hist = []          # device scalars, flushed at logs
        all_events = []            # chaos: serialized FailureEvents
        reassignments = []         # chaos: elastic re-draw records
        generation = 0
        step = start
        # An elastic re-assignment's span runs from the re-draw of the
        # code to the rebuilt generation's jitted step.
        rebuild = contextlib.ExitStack()
        t0 = time.time()

        def flush_metrics():
            # One bulk fetch of the buffered per-step scalars. The raw
            # coded loss is scaled by each step's straggler draw
            # (sum_i alpha_i varies); report the debiased estimate
            # loss / alpha_bar so steps are comparable across draws.
            for h in jax.device_get(metrics_hist):
                losses.append(float(h["loss"])
                              / max(float(h["alpha_bar"]), 1e-3))
            metrics_hist.clear()

        def save_ckpt(step: int):
            # A sync point by design (device_get), only hit at
            # checkpoint boundaries.
            state = {"params": jax.device_get(params),
                     "opt_state": jax.device_get(opt_state)}
            if compress:
                # Error-feedback residual rides along so a resumed
                # compressed run replays bit-identically.
                state["compress"] = jax.device_get(comp_state)
            ckpt.save(args.ckpt_dir, state, step=step)
            print(f"saved step-{step} checkpoint to {args.ckpt_dir}")

        try:
            # Generation loop: one iteration per coding geometry. The
            # per-generation machinery (batcher, shardings, jitted
            # step) is rebuilt whenever an elastic re-assignment
            # changes the assignment; without --chaos there is exactly
            # one generation and this reduces to the classic
            # build-once-then-loop driver.
            while step < args.steps:
                assignment = runtime.assignment
                n_blocks = assignment.n
                global_batch = n_blocks * args.block_size
                batcher = CodedBatcher(assignment,
                                       shuffle_seed=args.seed)
                emit = (batcher.unique_blocks if dedup
                        else batcher.code_batch)

                def host_batch(s, _emit=emit, _gb=global_batch):
                    if s == fail_at:
                        raise RuntimeError(
                            f"injected batch failure at step {s} "
                            "(REPRO_FAIL_BATCH_AT)")
                    return _emit(source.batch(_gb, s))

                alpha_w = coded_train.alpha_bar_weights(assignment)
                if args.collective == "manual":
                    train_step = \
                        coded_train.make_manual_collective_train_step(
                            cfg, optimizer, mesh, alpha_weights=alpha_w,
                            compress=compress,
                            streaming_chunk=args.stream_chunk or None)
                else:
                    train_step = coded_train.make_train_step(
                        cfg, optimizer,
                        n_microbatches=args.microbatches,
                        dedup=dedup,
                        norm_scale=coded_train.dedup_norm_scale(
                            assignment),
                        alpha_weights=alpha_w, compress=compress)

                # Shapes are static within a generation: build
                # shardings and the jitted step once, from the first
                # batch this generation will actually consume.
                batch_np = host_batch(step)
                bshard = (rules.block_shardings if dedup
                          else rules.batch_shardings)(mesh, batch_np)
                if compress:
                    if generation > 0:
                        # The residual rows track the block axis, which
                        # the re-assignment re-drew: restart error
                        # feedback from a zero residual.
                        comp_state = compress_mod.init_state(
                            params, n_blocks if dedup else runtime.m)
                    # Replicated is fine at smoke scale, and the
                    # compressed step's signature carries the state as
                    # a donated third argument.
                    comp_state = jax.device_put(comp_state, repl)
                    step_fn = jax.jit(
                        train_step,
                        in_shardings=(pshard, oshard, repl, bshard,
                                      repl),
                        out_shardings=(pshard, oshard, repl, None),
                        donate_argnums=(0, 1, 2))
                else:
                    step_fn = jax.jit(
                        train_step,
                        in_shardings=(pshard, oshard, bshard, repl),
                        out_shardings=(pshard, oshard, None),
                        donate_argnums=(0, 1))
                if generation:
                    rebuild.close()
                    reassignments[-1]["rebuild_s"] = round(
                        reassign_span.ms / 1e3, 3)

                # Straggler sampling + batched decode run on the same
                # worker thread as batch building, one chunk ahead of
                # the device (bit-identical to inline calls -- see
                # LookaheadPrefetcher). Observed masks (chaos) decode
                # per step instead: the mask is not knowable ahead of
                # the heartbeats.
                lookahead_w = None
                if not args.chaos:
                    lookahead_w = coded_train.LookaheadPrefetcher(
                        runtime, pool, lookahead, args.steps - step)
                pending = None
                reassign_dead = None

                while step < args.steps:
                    with jax.profiler.StepTraceAnnotation(
                            "train", step_num=step):
                        if pending is not None:
                            # Re-raises any worker-thread exception
                            # here, on the main loop, with its
                            # traceback.
                            with spans.span("train.batch_wait",
                                            step=step):
                                batch_np = pending.result()
                        if step + 1 < args.steps:
                            # Double buffer: the worker thread builds
                            # step+1's batch while the device runs
                            # step's compute.
                            pending = pool.submit(host_batch, step + 1)
                        if args.chaos:
                            times = injector.completion_times(step)
                            observed = monitor.observe(step, times)
                            runtime.mask_source.push(
                                surv.localize(observed))
                            w, alive = runtime.step_weights()
                        else:
                            w, alive = lookahead_w.next()
                        with spans.span("train.dispatch", step=step):
                            batch = {k: jax.device_put(jnp.asarray(v),
                                                       bshard[k])
                                     for k, v in batch_np.items()}
                            wv = runtime.block_weights(w) if dedup else w
                            wv = jax.device_put(
                                jnp.asarray(wv, jnp.float32), repl)
                            if compress:
                                params, opt_state, comp_state, \
                                    metrics = step_fn(
                                        params, opt_state, comp_state,
                                        batch, wv)
                            else:
                                params, opt_state, metrics = step_fn(
                                    params, opt_state, batch, wv)
                        metrics_hist.append(metrics)
                        if step % log_every == 0 or \
                                step == args.steps - 1:
                            # The only host<->device syncs in the loop:
                            # one bulk fetch per log interval keeps the
                            # metrics buffer bounded by log_every on
                            # arbitrarily long runs.
                            with spans.span("train.sync", step=step):
                                flush_metrics()
                            print(f"step {step:4d} loss "
                                  f"{losses[-1]:.4f} stragglers "
                                  f"{int((~alive).sum())}/{runtime.m} "
                                  f"({time.time() - t0:.1f}s)")
                        if args.ckpt_dir and args.ckpt_every and \
                                (step + 1) % args.ckpt_every == 0 and \
                                step + 1 < args.steps:
                            with spans.span("train.checkpoint",
                                            step=step):
                                save_ckpt(step + 1)
                    step += 1
                    if args.chaos:
                        new_events = monitor.drain_events()
                        for ev in new_events:
                            all_events.append(ev.to_json())
                            print(f"step {ev.step}: machine "
                                  f"{ev.machine} {ev.kind} "
                                  f"{ev.detail}")
                        dead_new = [ev.machine for ev in new_events
                                    if ev.kind == "dead"]
                        if dead_new:
                            reassign_dead = dead_new
                            break

                if reassign_dead:
                    # Elastic re-assignment: re-draw the code over the
                    # survivors and rebuild the generation machinery;
                    # {params, opt_state} stay live on device. The
                    # step where death was declared already decoded
                    # around the dead machine (a miss zeroes its
                    # weight), so no step is recomputed.
                    if surv.alive_count - len(reassign_dead) < 1:
                        raise SystemExit(
                            f"step {step}: all machines dead, cannot "
                            "re-assign")
                    flush_metrics()
                    reassign_span = rebuild.enter_context(
                        spans.span("train.reassign", step=step))
                    local = [int(np.where(surv.survivors == d)[0][0])
                             for d in reassign_dead]
                    surv.remove(reassign_dead)
                    generation += 1
                    runtime = coded_train.elastic_reassign(
                        runtime, local, generation=generation,
                        mask_source=sw.ObservedMaskSource(
                            surv.alive_count))
                    pending = None  # old-geometry batch: discard
                    info = {"step": int(step),
                            "generation": int(generation),
                            "dead": [int(d) for d in reassign_dead],
                            "survivors": surv.survivors.tolist(),
                            "m": surv.alive_count,
                            "scheme": runtime.coding.scheme,
                            "replication":
                                int(runtime.coding.replication),
                            "n_blocks": int(runtime.assignment.n),
                            "rebuild_s": None}
                    reassignments.append(info)
                    all_events.append(
                        {"step": int(step), "kind": "reassign",
                         "machine": -1,
                         "detail": {k: v for k, v in info.items()
                                    if k != "step"}})
                    print(f"step {step}: elastic re-assignment over "
                          f"m={surv.alive_count} survivors "
                          f"(generation {generation}, d="
                          f"{runtime.coding.replication})")

            with spans.span("train.sync", step=step):
                flush_metrics()
            if args.ckpt_dir:
                with spans.span("train.checkpoint", step=step):
                    save_ckpt(args.steps)
        finally:
            rebuild.close()
            # Pipeline hardening: whatever killed the loop (injected
            # batch failure, jit error, KeyboardInterrupt), cancel the
            # queued worker tasks and join the in-flight one so the
            # driver exits promptly with the original traceback
            # instead of idling behind orphaned host work.
            pool.shutdown(wait=True, cancel_futures=True)
    # The per-step coded loss is scaled by the straggler draw (w* varies
    # step to step), so compare window means, not endpoints. A resumed
    # run only sees its own (possibly short) tail of the stream, so the
    # decrease assertion stays with uninterrupted runs.
    if losses and start == 0:
        k = max(1, len(losses) // 4)
        first, last = np.mean(losses[:k]), np.mean(losses[-k:])
        assert last < first, \
            f"loss did not decrease ({first:.3f}->{last:.3f})"
    chaos_summary = None
    if args.chaos:
        detect = monitor.steps_to_detect()
        chaos_summary = {
            "spec": args.chaos,
            "events": all_events,
            "reassignments": reassignments,
            "dead_machines": monitor.dead_machines.tolist(),
            "steps_to_detect": {str(k): int(v)
                                for k, v in detect.items()},
            "degraded_steps": int(sum(detect.values())),
            "m_final": surv.alive_count,
            "generations": generation + 1,
        }
        if args.event_log:
            with open(args.event_log, "w") as f:
                json.dump(chaos_summary, f, indent=1)
            print(f"wrote failure-event log to {args.event_log}")
    summary = {"first_loss": losses[0] if losses else None,
               "last_loss": losses[-1] if losses else None,
               "losses": losses, "start_step": start,
               "steps": args.steps, "m_workers": m_workers,
               "scheme": args.scheme, "decoding": args.decoding,
               "path": "dedup" if dedup else "replicated",
               "collective": args.collective,
               "compress": args.compress,
               "stream_chunk": args.stream_chunk,
               "fsdp": bool(args.fsdp),
               "comm_bytes_per_step": comm_bytes,
               "comm_bytes_per_step_float32": comm_bytes_f32,
               "decode_calls": runtime.decode_calls,
               "chaos": chaos_summary,
               "spans": spans.totals()}
    if runtime.policy is not None:
        est = runtime.estimator.estimate()
        summary["adaptive"] = {
            "policy": args.adaptive,
            "p_hat": est.p_hat,
            "persistence_hat": est.persistence_hat,
            "decision_counts": dict(runtime.decision_counts),
        }
    print(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
