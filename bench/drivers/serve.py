"""Open-loop serving for a window of time, checked against the reference.

The window drives ``repro.serve.ServeEngine.run()`` with the coded
prefill layer and the engine's default ``log_every``. The engine is
given ``OpenLoop``, a ``ContinuousScheduler`` that submits each seeded
request through ``engine.submit`` when it falls due on the wall clock,
keeps ``has_work()`` true while the measurement lasts, and stamps at
each ``plan()`` the tokens that have newly reached ``engine.records``.

Time to first token runs from when a request was due to when its first
token is seen on the host; time per output token is, for each request,
the time from its first to its last token as the host sees them over
its tokens less one. Arrivals go on after the window closes, until
every request due in the window has all its tokens, so that the tails
count the slowest of them in full.

The traffic is the same set of request sizes and arrival gaps for every
seed, in an order the seed draws: quantiles of the mix's lognormal
lengths and exponential gaps, shuffled, with token ids drawn from the
seed.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

import counts
import harness
import reference as ref
import weights


def _quantiles(n, ppf):
    return np.array([ppf((i + 0.5) / n) for i in range(n)])


def _lognormal_lengths(spec, n, rng):
    from statistics import NormalDist

    z = NormalDist()
    q = _quantiles(n, lambda u: spec["median"] * np.exp(
        spec["sigma"] * z.inv_cdf(u)))
    q = np.clip(np.round(q), spec["min"], spec["max"]).astype(int)
    return q[rng.permutation(n)]


def make_requests(tr, seed, seconds, vocab):
    """Requests due in the window and after it: (due offset s, prompt,
    max new tokens), the window's and the tail's each a fixed set of
    sizes and gaps in the seed's order."""
    rng = np.random.default_rng(seed)
    rate = tr["arrivals"]["rate_per_s"]
    out = []
    t = 0.0
    for span in (seconds, tr["drain_cap_s"]):
        n = max(1, int(round(rate * span)))
        gaps = _quantiles(n, lambda u: -np.log(1 - u) / rate)
        gaps = gaps[rng.permutation(n)]
        prompts = _lognormal_lengths(tr["prompt_len"], n, rng)
        outputs = _lognormal_lengths(tr["output_len"], n, rng)
        for g, p, o in zip(gaps, prompts, outputs):
            t += g
            out.append((t, rng.integers(0, vocab, p).astype(np.int32),
                        int(o)))
    return out


def open_loop_classes():
    from repro.serve import ServeEngine
    from repro.serve.scheduler import ContinuousScheduler, Request

    class OpenLoop(ContinuousScheduler):
        def __init__(self, n_slots, requests, seconds, drain_cap_s, spans):
            super().__init__(n_slots)
            self.requests = requests
            self.seconds = seconds
            self.drain_cap_s = drain_cap_s
            self.spans = spans
            self.engine = None
            self.t0 = None
            self.next = 0
            self.due = {}
            self.live = {}             # uid -> tokens seen so far
            self.first_seen = {}
            self.done_seen = {}
            self.lateness = []
            self.admit_iter = {}       # slot -> iteration admitted
            self.window_iters = 0
            self.rows = {"prefill": 0, "busy": 0}
            self.flops = 0.0
            self.attn_calls = []       # per window iteration: lengths
            self.queue_lengths = []
            self.closed_at = None
            self.on_close = lambda: None

        def start(self, engine, t0):
            self.engine, self.t0 = engine, t0

        def in_window(self, now):
            return now - self.t0 < self.seconds

        def window_uids(self):
            return [u for u, d in self.due.items()
                    if d - self.t0 < self.seconds]

        def has_work(self):
            now = time.perf_counter()
            if self.in_window(now):
                return True
            if self.closed_at is None:
                self.closed_at = now
                self.on_close()
            if now - self.t0 > self.seconds + self.drain_cap_s:
                return False
            return any(u not in self.done_seen for u in self.window_uids())

        def _stamp(self, now):
            recs = self.engine.records
            for uid in list(self.live):
                n = len(recs[uid]["tokens"])
                seen = self.live[uid]
                if n == seen:
                    continue
                if seen == 0:
                    self.first_seen[uid] = now
                self.live[uid] = n
                if n >= self.requests[uid][2]:
                    self.done_seen[uid] = now
                    del self.live[uid]

        def _release(self, now):
            while self.next < len(self.requests) and \
                    self.t0 + self.requests[self.next][0] <= now:
                offset, prompt, max_new = self.requests[self.next]
                uid = self.next
                self.engine.submit(Request(uid, prompt, max_new))
                self.due[uid] = self.t0 + offset
                self.lateness.append(now - self.due[uid])
                self.live[uid] = 0
                self.next += 1

        def plan(self):
            now = time.perf_counter()
            with self.spans("plan"):
                self._stamp(now)
                self._release(now)
                it = self.iterations
                plan = super().plan()
            for b, _ in plan.admitted:
                self.admit_iter[b] = it
            if self.in_window(now):
                self._count(plan, it)
            return plan

        def _count(self, plan, it):
            forced = set(np.flatnonzero(plan.use_forced).tolist())
            decode = {b for b, _, first in plan.emits if not first}
            active = forced | decode
            ctx = [it - self.admit_iter[b] + 1 for b in sorted(active)]
            dims = self.dims
            self.window_iters += 1
            self.queue_lengths.append(len(self.queue))
            self.rows["prefill"] += len(forced)
            self.rows["busy"] += len(active)
            self.flops += sum(counts.decode_flops(dims, c) for c in ctx)
            self.attn_calls.append(ctx)

    class TimedEngine(ServeEngine):
        def _admit(self, admitted):
            with self.scheduler.spans("admit"):
                super()._admit(admitted)

    return OpenLoop, TimedEngine


def p95(values):
    return statistics.quantiles(values, n=20, method="inclusive")[-1]


def run(spec, seed, seconds, trace, devices, t_start, fault=None,
        control=False, check=True):
    """One run of a serving cell. ``fault`` (tests only) wraps the
    engine's pool step to plant a fault under the timed path;
    ``control`` also reads the float8 control's gap."""
    import jax
    import jax.numpy as jnp

    from repro.configs import CodingConfig

    import tracing

    cfg, dims = harness.model_config(spec["config"])
    tr = spec["traffic"]
    spans = harness.Spans(annotate=bool(trace))
    watch = harness.CompileWatch()
    OpenLoop, TimedEngine = open_loop_classes()
    requests = make_requests(tr, seed, seconds, cfg.vocab_size)
    c = tr["coding"]
    coding = CodingConfig(scheme=c["scheme"], replication=c["replication"],
                          decoding=c["decoding"],
                          straggler_model=c["straggler_model"],
                          straggler_p=c["straggler_p"], seed=seed)
    build = weights.params_fn(cfg, jax.sharding.SingleDeviceSharding(
        devices[0]))
    params = build(weights.seed_key(seed))
    B = tr["slots"]
    sched = OpenLoop(B, requests, seconds, tr["drain_cap_s"], spans)
    sched.dims = dims
    engine = TimedEngine(cfg, params, n_slots=B, max_len=tr["max_len"],
                         coding=coding, m_replicas=c["m_replicas"],
                         scheduler=sched, log_every=tr["log_every"])
    if fault is not None:
        engine.step_fn = fault(engine.step_fn)
    del params
    # Warm up the two programs the window runs, at their one shape, with
    # the arguments placed as the window's calls place them: the cache
    # and the previous tokens on the device, the plan's arrays fresh
    # from the host. (A first step on an engine's own unplaced arrays
    # compiles the pool step anew.)
    dev = devices[0]
    everything = np.ones(B, bool)
    engine.pool.cache = jax.device_put(engine.pool.cache, dev)
    engine.pool.reset_slots(everything)
    tok = jax.device_put(jnp.zeros(B, jnp.int32), dev)
    for _ in range(2):
        tok, engine.pool.cache = engine.step_fn(
            engine.params, engine.pool.cache, tok,
            jnp.asarray(np.zeros(B, np.int32)), jnp.asarray(~everything),
            jnp.asarray(np.ones(B, np.float32)))
    engine.pool.reset_slots(everything)
    engine._tok = jax.device_put(jnp.zeros(B, jnp.int32), dev)
    jax.block_until_ready((engine._tok, engine.pool.cache))
    spans.reset()
    session = tracing.Session(bool(trace))
    session.start()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    sched.start(engine, t0)
    sched.on_close = lambda: session.mark("window_end")
    session.mark("window_start")
    watch.armed = True
    engine.run()
    watch.armed = False
    session.stop()
    t_end = time.perf_counter()
    peak = harness.memory_peak(devices)

    window = sched.window_uids()
    ttft = [sched.first_seen.get(u, t_end) - sched.due[u] for u in window]
    tpot = [(sched.done_seen[u] - sched.first_seen[u])
            / (requests[u][2] - 1) for u in window if u in sched.done_seen]
    failed = sum(1 for u in window if u not in sched.done_seen)
    late = np.asarray(sched.lateness)
    records = {u: dict(engine.records[u]) for u in window}
    sample = pick_sample(records, requests, tr["check"]["requests"], seed)
    served = [(requests[u][1], records[u]["tokens"]) for u in sample]
    notes = {"window_requests": len(window), "finished": sum(
        1 for u in window if records[u]["done_iter"] is not None),
        "drain_s": t_end - t0 - seconds,
        "generator_late_ms": {"p50": float(np.median(late)) * 1e3,
                              "max": float(late.max()) * 1e3},
        "compiles_in_window": watch.events,
        "window_iterations": sched.window_iters,
        "iteration_ms": seconds / max(sched.window_iters, 1) * 1e3,
        "queue_by_tenth": [sched.queue_lengths[int(i * len(
            sched.queue_lengths) / 10)] for i in range(10)]
        if sched.queue_lengths else [],
        "ttft_p50_ms": float(np.median(ttft)) * 1e3,
        "sample_tokens": sum(len(t) for _, t in served)}
    ctx = {"kind": "serve", "dims": dims, "traffic": tr,
           "device": harness.device_info(devices),
           "spans": dict(spans.totals), "span_counts": dict(spans.counts),
           "window_s": seconds, "window_iterations": sched.window_iters,
           "rows": dict(sched.rows), "flops": sched.flops,
           "attn_calls": sched.attn_calls, "trace": None}
    engine.scheduler = None
    sched.engine = None
    del engine
    gc.collect()
    ctx["trace"] = session.reduce()

    gap = (logit_gaps(cfg, dims, seed, served, devices, tr["max_len"],
                      control=control) if check else {"f32": float("nan")})
    notes["logit_gaps"] = gap
    checks = {"logit_gap": {"value": gap["f32"],
                            "limit": tr["limits"]["logit_gap"]}}
    correct = failed == 0 and bool(sample) and \
        checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
    e2e = {"ttft_p95_ms": p95(ttft) * 1e3,
           "tpot_p95_ms": p95(tpot) * 1e3 if len(tpot) > 1
           else float("nan"),
           "setup_s": setup_s}
    return {"correct": correct, "attempted": len(window), "failed": failed,
            "e2e": e2e, "ctx": ctx, "memory_peak_bytes": peak,
            "checks": checks, "notes": notes}


def pick_sample(records, requests, k, seed):
    """Up to k finished requests, drawn from the seed, with the longest
    finished one (prompt and output) among them."""
    done = sorted(u for u, r in records.items() if r["done_iter"] is not None
                  and len(r["tokens"]) == requests[u][2])
    if not done:
        return []
    longest = max(done, key=lambda u: len(requests[u][1]) + requests[u][2])
    rest = [u for u in done if u != longest]
    rng = np.random.default_rng(seed + 1)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def logit_gaps(cfg, dims, seed, served, devices, max_len, control=False):
    """The widest gap by which a served token's reference logit lies
    below the reference's best at its position; with ``control`` also
    the gap of the token the float8 reference puts first."""
    import jax

    layers = reference_layers(cfg, dims, seed, devices[0], max_len)
    worst = {"f32": 0.0, "fp8": 0.0}
    for prompt, toks in served:
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        n_out = len(toks)
        lg = layers(seq, ref.F32)[len(prompt) - 1: len(prompt) - 1 + n_out]
        best = lg.max(axis=-1)
        worst["f32"] = max(worst["f32"], float(np.max(
            best - lg[np.arange(n_out), np.asarray(toks)])))
        if control:
            lo = layers(seq, ref.FP8)[len(prompt) - 1:
                                      len(prompt) - 1 + n_out]
            top = lo.argmax(axis=-1)
            worst["fp8"] = max(worst["fp8"], float(np.max(
                best - lg[np.arange(n_out), top])))
    del layers
    gc.collect()
    return worst


def reference_layers(cfg, dims, seed, device, max_len):
    """seq -> (len, vocab) float32 reference logits, one jitted call per
    layer so that only one layer is upcast at a time. Sequences are
    padded at the end to ``max_len``, which causal attention ignores."""
    import jax

    build = weights.params_fn(cfg, jax.sharding.SingleDeviceSharding(device))
    params = build(weights.seed_key(seed))

    def compiled(precision):
        with jax.default_matmul_precision("highest"):
            emb = jax.jit(lambda p, t: ref.embed(p, t))
            lay = jax.jit(lambda p, x: ref.layer(p, x, dims, precision))
            hd = jax.jit(lambda p, x: ref.head(p, x, dims, precision))
        return emb, lay, hd

    fns = {}

    def run(seq, precision):
        if precision not in fns:
            fns[precision] = compiled(precision)
        emb, lay, hd = fns[precision]
        toks = np.zeros(max_len, np.int32)
        toks[:len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            x = emb(params, jax.device_put(toks, device))
            for i in range(dims["n_layers"]):
                x = lay(ref.layer_params(params, i), x)
            out = hd({"final_norm": params["final_norm"],
                      "lm_head": params["lm_head"]}, x)
        return np.asarray(jax.device_get(out))[:len(seq)]
    return run


def control(spec, seeds, devices, seconds):
    """For each seed: the program's served tokens over a short window at
    the cell's load, and the gaps of the program and of the float8
    control at the same positions, read in one process."""
    out = []
    for seed in seeds:
        res = run(spec, seed, seconds, 0, devices, time.perf_counter(),
                  control=True)
        out.append({"seed": seed, **res["notes"]["logit_gaps"],
                    "sample_tokens": res["notes"]["sample_tokens"]})
    return out
