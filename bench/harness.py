"""What every driver shares: resolving a cell by name, building its
model configuration, host spans, the device's name and memory, the
persistent compilation cache, and the per-layer metric readers.

Nothing here knows a particular cell. A cell is found by its name in
``BENCHMARK.json``; its configuration in ``bench/configs/<config>.json``;
its traffic in ``bench/traffic/<traffic>.json``; each per-layer metric's
reader in ``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent

# Published config.json keys -> the program's ModelConfig fields.
HF_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, root: Path = REPO) -> dict:
    """The cell named ``workload`` with everything it needs, found by
    name: its entry, its configuration and traffic files, and the
    end-to-end and per-layer metrics it reports."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return {"cell": cell,
            "config": load_json(root / config["file"]),
            "traffic": load_json(root / "bench" / "traffic"
                                 / f"{cell['traffic']}.json"),
            "end_to_end": e2e,
            "per_layer": per_layer,
            "run_seconds": bench["run_seconds"]}


def unlisted(config: str, traffic: str, root: Path = REPO) -> dict:
    """A cell that ``BENCHMARK.json`` does not list yet, from its
    configuration and traffic files alone: what the knee sweep needs to
    fix a serving cell's rate before the cell is added. It reports no
    metrics."""
    tr = load_json(root / "bench" / "traffic" / f"{traffic}.json")
    return {"cell": {"name": f"{config}.{traffic}", "config": config,
                     "traffic": traffic, "chips": tr["chips"]},
            "config": load_json(root / "bench" / "configs"
                                / f"{config}.json"),
            "traffic": tr, "end_to_end": [], "per_layer": [],
            "run_seconds": None}


def model_config(config_file: dict):
    """(ModelConfig, dims) for a configuration file: the program's
    registry entry with the file's published keys and numerics over
    it."""
    from repro.configs import get_config

    kw = {HF_KEYS[k]: v for k, v in config_file.items() if k in HF_KEYS}
    kw.update(config_file["numerics"])
    cfg = get_config(config_file["program_arch"]).with_overrides(**kw)
    dims = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "rope_theta": float(cfg.rope_theta),
            "eps": float(cfg.norm_eps)}
    return cfg, dims


def enable_compile_cache() -> str:
    """JAX's persistent cache in ``$JAX_COMPILATION_CACHE_DIR`` or at the
    fixed ``<checkout>/.jax_cache``; every program is cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Spans:
    """Host spans kept in memory: total seconds and count per name.
    With ``annotate`` each span is also a profiler TraceAnnotation, so
    the device trace can say what the host was doing in an idle gap."""

    def __init__(self, annotate: bool = False):
        self.totals: dict = {}
        self.counts: dict = {}
        self.annotate = annotate

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.totals[name] = self.totals.get(name, 0.0) + (
            time.perf_counter() - t0)
        self.counts[name] = self.counts.get(name, 0) + 1

    def reset(self):
        self.totals.clear()
        self.counts.clear()


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device, where reported."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def read_per_layer(metrics: list, ctx: dict, bench_dir: Path = BENCH) -> dict:
    """Run each metric's reader (``bench/metrics/<name>.py``, function
    ``read(ctx)``); a reader that finds nothing returns None and the
    metric is left out."""
    out = {}
    for m in metrics:
        path = bench_dir / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{m['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check_line(checks: dict) -> str:
    """One stderr line per compared number: name, value, limit."""
    return "\n".join(f"check {k}: {v['value']!r} (limit {v['limit']!r})"
                     for k, v in checks.items())


def result_line(spec: dict, res: dict, trace: int) -> str:
    """The run's result as one JSON line; ``checks`` comes last."""
    import peaks

    ctx = res["ctx"]
    ctx["peaks"] = peaks.peaks_for(ctx["device"]["kind"])
    device = dict(ctx["device"], memory_peak_bytes=res["memory_peak_bytes"])
    out = {"correct": bool(res["correct"]),
           "attempted": int(res["attempted"]),
           "failed": int(res["failed"])}
    if trace:
        out["metrics"] = read_per_layer(spec["per_layer"], ctx)
        tr = ctx["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["device"] = device
        out["breakdown"] = {"device_ops": tr["top_ops"][:10],
                            "idle_gaps": tr["idle_gaps"][:10]}
    else:
        out["metrics"] = {m["name"]: {"value": float(res["e2e"][m["name"]]),
                                      "unit": m["unit"]}
                          for m in spec["end_to_end"]}
        out["device"] = device
    out["checks"] = res["checks"]
    return json.dumps(out)


class CompileWatch:
    """Counts JAX compilations (and persistent-cache loads) while armed,
    so a run can show that nothing compiled inside its window."""

    def __init__(self):
        import jax

        self.armed = False
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in (
                "/jax/core/compile/backend_compile_duration",
                "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.events.append((event.rsplit("/", 1)[-1], duration))
