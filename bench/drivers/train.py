"""Coded training for a window of time, checked against the reference.

The window drives the program's own pieces in the order of its train
driver (``repro.launch.train``), which runs a fixed number of steps and
has no entry bounded by time: the jitted dedup coded step of
``dist.coded_train.make_train_step`` with the driver's shardings and
donation, fed by ``CodedBatcher.unique_blocks(SyntheticLM.batch)``
built one step ahead on a worker thread, by the weights
``LookaheadPrefetcher(CodingRuntime)`` decodes on that thread, and by
``runtime.block_weights``. Metrics stay on the device until a
``log_every`` boundary.

Set-up builds the step and its state once, warms it and drives it
through its first ``check_steps`` steps with the window's own call and
feed; the window then continues the same object. After the window the
reference (``reference.py``) redoes those first steps from the seed
and the same inputs, and the two are compared.
"""

from __future__ import annotations

import gc
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import harness
import reference as ref
import weights

GRAD_FLOOR = 1e-3   # leaves below this share of the median gradient are
                    # nought to rounding in the reference (a key bias)


def _optimizer(hp):
    from repro.optim import optimizers as opt_mod

    return opt_mod.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"],
                         eps=hp["eps"], weight_decay=hp["weight_decay"])


def _coding(tr, seed):
    from repro.configs import CodingConfig

    c = tr["coding"]
    return CodingConfig(scheme=c["scheme"], replication=c["replication"],
                        decoding=c["decoding"],
                        straggler_model=c["straggler_model"],
                        straggler_p=c["straggler_p"], seed=seed)


class Feed:
    """The host side of the driver's loop: the batch for step k+1 is
    built on the worker thread while step k runs, and the straggler
    weights come from the lookahead prefetcher on the same thread."""

    def __init__(self, cfg, tr, seed, spans):
        from repro.data.pipeline import CodedBatcher, SyntheticLM
        from repro.dist import coded_train

        self.runtime = coded_train.CodingRuntime(_coding(tr, seed),
                                                 tr["coding"]["machines"])
        self.A = self.runtime.assignment
        self.source = SyntheticLM(cfg.vocab_size, tr["seq_len"], seed=seed)
        self.batcher = CodedBatcher(self.A, shuffle_seed=seed)
        self.global_batch = self.A.n * tr["block_size"]
        self.pool = ThreadPoolExecutor(max_workers=1)
        self.prefetch = coded_train.LookaheadPrefetcher(
            self.runtime, self.pool, tr["lookahead"], 1 << 40)
        self.spans = spans
        self.pending = None

    def host_batch(self, k):
        return self.batcher.unique_blocks(
            self.source.batch(self.global_batch, k))

    def next(self, k):
        """(block batch, machine weights, alive mask) of step k."""
        with self.spans("host_wait"):
            batch = (self.pending.result() if self.pending is not None
                     else self.host_batch(k))
        self.pending = self.pool.submit(self.host_batch, k + 1)
        with self.spans("host_wait"):
            w, alive = self.prefetch.next()
        return batch, w, alive

    def close(self):
        self.pool.shutdown(wait=True, cancel_futures=True)


def run(spec, seed, seconds, trace, devices, t_start, fault=None):
    """One run of a training cell. ``fault`` (tests only) wraps the
    program's step factory to plant a fault under the timed path."""
    import jax
    import jax.numpy as jnp

    from repro.dist import coded_train, sharding as rules
    from repro.launch.mesh import make_test_mesh
    from repro.models import model as M

    import tracing

    cfg, dims = harness.model_config(spec["config"])
    tr = spec["traffic"]
    hp = tr["optimizer"]
    spans = harness.Spans(annotate=bool(trace))
    watch = harness.CompileWatch()
    feed = Feed(cfg, tr, seed, spans)
    A = feed.A
    optimizer = _optimizer(hp)
    make_step = fault or coded_train.make_train_step
    step = make_step(cfg, optimizer, dedup=True,
                     norm_scale=coded_train.dedup_norm_scale(A),
                     alpha_weights=coded_train.alpha_bar_weights(A))
    mesh = make_test_mesh(tuple(tr["mesh"]))
    da = rules.data_axes(mesh)
    M.set_residual_sharding(batch_axes=da if len(da) > 1 else da[0],
                            model_axis="model")
    shapes = weights.param_shapes(cfg)
    pshard = rules.named(mesh, rules.safe_param_specs(shapes, mesh))
    repl = rules.replicated(mesh)
    oshard = {"step": repl, "m": pshard, "v": pshard}
    build = weights.params_fn(cfg, pshard)
    log_every = tr["log_every"]
    n_check = tr["check_steps"]
    tokens_per_step = feed.global_batch * tr["seq_len"]
    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.linalg.norm(x.astype(jnp.float32).ravel()), t))
    diff_norms = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.linalg.norm((x - y).astype(jnp.float32).ravel()),
        a, b))

    def flat(tree):
        return {jax.tree_util.keystr(p): float(v) for p, v in
                jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]}

    with jax.set_mesh(mesh):
        params = build(weights.seed_key(seed))
        opt_state = jax.jit(optimizer.init, out_shardings=oshard)(params)
        first = feed.host_batch(0)
        bshard = rules.block_shardings(mesh, first)
        step_fn = jax.jit(step, in_shardings=(pshard, oshard, bshard, repl),
                          out_shardings=(pshard, oshard, None),
                          donate_argnums=(0, 1))
        state = {"params": params, "opt": opt_state, "hist": [],
                 "losses": []}
        del params, opt_state
        inputs = []

        def flush():
            for h in jax.device_get(state["hist"]):
                state["losses"].append(float(h["loss"]))
            state["hist"].clear()

        def one_step(k):
            batch_np, w, alive = feed.next(k)
            if k < n_check:
                inputs.append({"blocks": batch_np, "alive": alive})
            batch = {n: jax.device_put(v, bshard[n])
                     for n, v in batch_np.items()}
            wv = jax.device_put(feed.runtime.block_weights(w), repl)
            state["params"], state["opt"], metrics = step_fn(
                state["params"], state["opt"], batch, wv)
            state["hist"].append(metrics)
            if k % log_every == 0:
                flush()

        # Set-up: the first steps through the window's own call and
        # feed; the readings the check needs are taken between them.
        grad_norms = grad_tree = None
        for k in range(n_check):
            one_step(k)
            if k == 0:
                grad_norms = {key: v / (1 - hp["b1"]) for key, v in
                              flat(norms(state["opt"]["m"])).items()}
                grad_tree = ref.host_leaves(state["opt"]["m"])
        p0 = build(weights.seed_key(seed))
        change_norms = flat(diff_norms(state["params"], p0))
        del p0
        flush()
        jax.block_until_ready(state["params"])
        spans.reset()
        session = tracing.Session(bool(trace))
        session.start()
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        session.mark("window_start")
        watch.armed = True
        k = n_check
        while time.perf_counter() - t0 < seconds:
            one_step(k)
            k += 1
        jax.block_until_ready((state["params"], state["opt"]))
        t1 = time.perf_counter()
        watch.armed = False
        session.mark("window_end")
        session.stop()
        flush()
        window_s = t1 - t0
        steps = k - n_check
        prog_losses = state["losses"][:n_check]
        finite = bool(np.all(np.isfinite(state["losses"])))
        peak = harness.memory_peak(devices)
        state.clear()
    feed.close()
    reduced = session.reduce()
    gc.collect()

    readings = check_readings(
        cfg, dims, tr, seed, A, inputs, devices,
        program={"losses": prog_losses, "grad": grad_norms,
                 "grad_tree": grad_tree, "grad_scale": 1 / (1 - hp["b1"]),
                 "change": change_norms})
    checks = {k: {"value": v, "limit": tr["limits"][k]}
              for k, v in readings["numbers"].items()}
    correct = finite and all(c["value"] <= c["limit"]
                             for c in checks.values())
    ctx = {"kind": "train", "dims": dims, "traffic": tr,
           "device": harness.device_info(devices),
           "spans": dict(spans.totals), "span_counts": dict(spans.counts),
           "steps": steps, "window_s": window_s,
           "tokens_per_step": tokens_per_step, "trace": reduced}
    return {"correct": correct, "attempted": steps,
            "failed": 0 if finite else steps,
            "e2e": {"train_tokens_per_s": steps * tokens_per_step / window_s,
                    "setup_s": setup_s},
            "ctx": ctx, "memory_peak_bytes": peak, "checks": checks,
            "notes": dict(readings["notes"], compiles_in_window=watch.events,
                          window_steps=steps,
                          kernel_ops={k: tracing.kernel_ops(reduced, rx)
                                      for k, rx in tracing.KERNELS.items()}
                          if reduced else None)}


def reference_steps(cfg, dims, tr, seed, A, inputs, devices,
                    precision=ref.F32, fault=(None, 1.0)):
    """The reference through the checked steps, from the seed and the
    program's inputs (block batches and alive masks). Returns the loss
    of each step, the first gradient's leaf norms and its leaves on the
    host, and the leaf norms of the parameters' change over the steps."""
    import jax

    hp = tr["optimizer"]
    c = tr["coding"]
    build = weights.params_fn(cfg, jax.sharding.SingleDeviceSharding(
        devices[0]))
    to_f32 = jax.jit(lambda t: jax.tree.map(
        lambda x: x.astype(np.float32), t))
    params = to_f32(build(weights.seed_key(seed)))
    ms = jax.tree.map(jax.numpy.zeros_like, params)
    vs = jax.tree.map(jax.numpy.zeros_like, params)
    scale = ref.debias_scale(A.A, c["straggler_p"], tr["debias_trials"],
                             seed + tr["debias_seed_offset"])
    load = int(A.A.sum(axis=0).max())
    rows_fn, norm_factor = fault
    losses, first, first_tree = [], None, None
    for t, inp in enumerate(inputs):
        v = scale * ref.optimal_alpha(A.A, inp["alive"])
        labels = inp["blocks"]["labels"]
        norm = labels.size * (A.m * load / A.n) * norm_factor
        copies = [params] + [jax.device_put(params, d) for d in devices[1:]]
        rows = rows_fn(labels.shape[:2]) if rows_fn else None
        loss, g = ref.coded_loss_and_grad(copies, inp["blocks"], v, norm,
                                          dims, precision, rows=rows)
        del copies
        g = ref.pad_like(g, params)
        if t == 0:
            first = ref.leaf_norms(g)
            first_tree = ref.host_leaves(g)
        params, ms, vs = ref.adamw_step(params, g, ms, vs, t + 1, hp)
        del g
        losses.append(loss)
    del ms, vs
    p0 = to_f32(build(weights.seed_key(seed)))
    change = ref.leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))
    return {"losses": losses, "grad": first, "grad_tree": first_tree,
            "change": change}


def compare(program, reference):
    """The three compared numbers of a training cell: the worst leaf's
    gap between the norms of the first gradient (``grad_gap``), of the
    parameters' change over the checked steps (``update_gap``), and the
    worst leaf's norm of the first gradient's difference
    (``grad_error``), each against the reference's norm of that leaf or
    of the median leaf. The norms of a leaf and the mean loss average
    the rounding of many entries away; the difference does not, and is
    the number that tells a lower precision from the program's (see
    PERF.md). The losses are not compared: their gaps are in the notes
    (PERF.md says why)."""
    med = np.median(list(reference["grad"].values()))
    skip = {k for k, v in reference["grad"].items() if v < GRAD_FLOOR * med}
    loss_gaps = [abs(p - r) / abs(r) for p, r in
                 zip(program["losses"], reference["losses"])]
    grad_gap, grad_leaf = ref.worst_leaf_gap(program["grad"],
                                             reference["grad"], skip)
    change_gap, change_leaf = ref.worst_leaf_gap(program["change"],
                                                 reference["change"], skip)
    grad_error, error_leaf = ref.worst_leaf_error(
        program["grad_tree"], reference["grad_tree"], reference["grad"],
        skip, program.get("grad_scale", 1.0))
    return ({"grad_gap": grad_gap, "update_gap": change_gap,
             "grad_error": grad_error},
            {"grad_leaf": grad_leaf, "update_leaf": change_leaf,
             "error_leaf": error_leaf, "skipped_leaves": sorted(skip),
             "loss_gaps": loss_gaps})


def check_readings(cfg, dims, tr, seed, A, inputs, devices, program):
    reference = reference_steps(cfg, dims, tr, seed, A, inputs, devices)
    numbers, notes = compare(program, reference)
    notes["losses"] = {"program": program["losses"],
                       "reference": reference["losses"]}
    return {"numbers": numbers, "notes": notes}


def planted(name, n_devices):
    """(rows kept, normaliser factor) of a fault planted in the
    reference: ``half_batch`` keeps the first half of the blocks and
    takes the mean over them; ``no_exchange`` keeps the blocks the first
    device holds under the block sharding, normalised as the whole batch
    (its gradient is the first device's partial sum, never all-reduced);
    anything else keeps every row."""
    if name == "half_batch":
        return (lambda shape: {(i, j) for i in range(shape[0] // 2)
                               for j in range(shape[1])}, 0.5)
    if name == "no_exchange":
        return (lambda shape: {(i, j) for i in range(shape[0] // n_devices)
                               for j in range(shape[1])}, 1.0)
    return None, 1.0


def control(spec, seeds, devices):
    """Readings of the control (the reference in float8) and of the
    planted faults, at the cell's size, against the float32 reference;
    no program runs. Returns one dict of numbers per seed and variant."""
    import jax

    cfg, dims = harness.model_config(spec["config"])
    tr = spec["traffic"]
    faults = ["fp8", "half_batch"] + (["no_exchange"]
                                      if len(devices) > 1 else [])
    out = []
    for seed in seeds:
        feed = Feed(cfg, tr, seed, harness.Spans())
        inputs = []
        for k in range(tr["check_steps"]):
            batch, w, alive = feed.next(k)
            inputs.append({"blocks": batch, "alive": alive})
        feed.close()
        base = reference_steps(cfg, dims, tr, seed, feed.A, inputs, devices)
        row = {"seed": seed}
        for f in faults:
            other = reference_steps(
                cfg, dims, tr, seed, feed.A, inputs, devices,
                precision=ref.FP8 if f == "fp8" else ref.F32,
                fault=planted(f, len(devices)))
            numbers, notes = compare(other, base)
            row[f] = dict(numbers, loss_gaps=notes["loss_gaps"])
        out.append(row)
        gc.collect()
    return out
