"""The device trace of a run, reduced to what the per-layer metrics read.

``Session`` wraps the JAX profiler: it traces into a temporary
directory, marks the measured window with two host annotations
(``bench.window_start`` and ``bench.window_end``), and ``reduce()``
turns the trace into plain numbers and deletes it.

``load`` reads the profiler's ``.xplane.pb`` into normalised events:
per device, the operations of its ``XLA Ops`` line as
``[name, start_ns, duration_ns]``, each named by its HLO instruction's
own name (``%rmsnorm.1``; the trace gives the whole instruction text,
whose operands name other instructions); on the host, the benchmark's own
``bench.*`` annotations. ``reduce_events`` works on that form alone, so
it is tested on hand-made events with known answers.
"""

from __future__ import annotations

import glob
import re
import shutil
import tempfile

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all-to-all|psum|send|recv)", re.IGNORECASE)


def op_name(text: str) -> str:
    """The instruction's own name from an HLO instruction's text
    (``%fusion.3 = bf16[..] fusion(%rmsnorm.1, ..)`` -> ``%fusion.3``)."""
    return text.split(" = ", 1)[0]


class Session:
    """The profiler over the window of one traced run (no-op when off)."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None

    def start(self):
        if self.on:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)

    def mark(self, name: str):
        if self.on:
            import jax

            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                pass

    def stop(self):
        if self.on:
            import jax

            jax.profiler.stop_trace()

    def reduce(self):
        """The reduced trace (None when off)."""
        if not self.on:
            return None
        try:
            return reduce_events(load(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def load(logdir: str) -> dict:
    """Normalised events of the one trace under ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[m.group(1)] = [
                    [op_name(e.name), e.start_ns, e.duration_ns]
                    for e in line.events]
            elif not m:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Length of the union ``a`` not covered by the union ``b``."""
    covered, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return _length(a) - covered


def window_bounds(events: dict):
    """[start, end] ns of the measured window from the two marks, or
    the span of all device operations when the marks are missing."""
    marks = {n: s for n, s, _ in events["host"]}
    if "bench.window_start" in marks and "bench.window_end" in marks:
        return marks["bench.window_start"], marks["bench.window_end"]
    ops = [ev for evs in events["devices"].values() for ev in evs]
    return (min(s for _, s, _ in ops), max(s + d for _, s, d in ops))


def reduce_events(events: dict, gap_floor_ns: int = 10_000) -> dict:
    """Busy and idle time per device, time per operation name, collective
    time and the part of it no other operation overlaps, and idle gaps
    by the host span they fall in; all within the measured window and
    averaged over the devices."""
    w0, w1 = window_bounds(events)
    n_dev = max(1, len(events["devices"]))
    busy = 0
    coll_total = coll_exposed = 0
    ops = {}
    gaps = []
    host = sorted((s, s + d, n[len("bench."):]) for n, s, d in events["host"]
                  if d > 0)
    for evs in events["devices"].values():
        clipped = [(n, max(s, w0), min(s + d, w1)) for n, s, d in evs
                   if s + d > w0 and s < w1]
        for n, s, e in clipped:
            c, t = ops.get(n, (0, 0))
            ops[n] = (c + 1, t + e - s)
        union = _union([(s, e) for _, s, e in clipped])
        busy += _length(union)
        coll = _union([(s, e) for n, s, e in clipped if COLLECTIVE.search(n)])
        comp = _union([(s, e) for n, s, e in clipped
                       if not COLLECTIVE.search(n)])
        coll_total += _length(coll)
        coll_exposed += _subtract(coll, comp)
        edges = [w0] + [x for iv in union for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e - s >= gap_floor_ns:
                mid = (s + e) / 2
                what = [n for hs, he, n in host if hs <= mid < he]
                gaps.append((what[-1] if what else "no host span", e - s))
    idle = {}
    for what, d in gaps:
        c, t = idle.get(what, (0, 0))
        idle[what] = (c + 1, t + d)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / n_dev * 1e-9,
        "devices": n_dev,
        "ops": {n: [c / n_dev, t / n_dev * 1e-9] for n, (c, t) in ops.items()},
        "top_ops": [[n, t / n_dev * 1e-9] for n, (c, t) in top_ops[:10]],
        "collective_s": coll_total / n_dev * 1e-9,
        "collective_exposed_s": coll_exposed / n_dev * 1e-9,
        "idle_gaps": [[f"{n} ({c} gaps)", t / n_dev * 1e-9] for n, (c, t) in
                      sorted(idle.items(), key=lambda kv: -kv[1][1])][:10],
    }


def kernel_ops(reduced: dict, pattern: str) -> dict:
    """{name: [calls, seconds]} per device of the operations whose name
    matches ``pattern``."""
    rx = re.compile(pattern)
    return {n: v for n, v in reduced["ops"].items() if rx.search(n)}


def kernel_calls(reduced: dict, pattern: str):
    """(calls per device, seconds per device) of the operations whose
    name matches ``pattern``; None when the trace holds none."""
    hits = list(kernel_ops(reduced, pattern).values())
    if not hits:
        return None
    return sum(c for c, _ in hits), sum(t for _, t in hits)


# Instruction names of the kernels whose roofline shares are read.
KERNELS = {"rmsnorm": r"^%?rmsnorm\b",
           "decode_attention": r"^%?decode_attention\b"}
