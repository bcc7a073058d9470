"""BENCHMARK.json resolves, by name alone, to each cell's configuration,
traffic and per-layer metric files; a new cell, mix or metric needs new
files and entries only; and each driver runs one short pass on the CPU
through the functions the chip run uses, to a result line of the
contract's shape."""

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path[:0] = [str(REPO / "src"), str(BENCH), str(BENCH / "drivers")]

import harness  # noqa: E402
import small_cells  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def test_benchmark_file_shape():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCHMARK[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in BENCHMARK["end_to_end"]}
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) <= max(
        1, len(BENCHMARK["workloads"]) // 2)
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
    assert len(json.dumps(BENCHMARK)) < 64 * 1024


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_by_name(workload):
    spec = harness.resolve(workload)
    assert spec["cell"]["name"] == workload
    assert spec["traffic"]["chips"] == spec["cell"]["chips"]
    assert (BENCH / "drivers" / f"{spec['traffic']['driver']}.py").is_file()
    cfg, dims = harness.model_config(spec["config"])
    assert dims["head_dim"] * dims["n_heads"] == dims["d_model"]
    reported = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert spec["per_layer"], "every cell reports a per-layer metric"
    for m in spec["per_layer"]:
        path = BENCH / "metrics" / f"{m['name']}.py"
        assert path.is_file(), path
        assert "def read(ctx)" in path.read_text()


@pytest.mark.parametrize("config", BENCHMARK["configs"],
                         ids=[c["name"] for c in BENCHMARK["configs"]])
def test_config_file_lists_its_cuts(config):
    body = json.loads((REPO / config["file"]).read_text())
    assert body["source"] == config["source"]
    assert sorted(body["reduced"]) == sorted(config["reduced"])
    for key, cut in body["reduced"].items():
        assert body[key] == cut["run"] and cut["published"] != cut["run"]


def test_new_cell_mix_and_metric_are_files_and_entries_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCHMARK))
    traffic = json.loads((BENCH / "traffic" / "train_coded.json").read_text())
    traffic["coding"]["straggler_p"] = 0.0
    (root / "bench" / "traffic" / "train_p0.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "metrics" / "steps_per_s.train.py").write_text(
        "def read(ctx):\n    return ctx['steps'] / ctx['window_s']\n")
    bench["workloads"].append({"name": "qwen15_4b.train_p0",
                               "config": "qwen15_4b", "traffic": "train_p0",
                               "chips": 1, "why": "no stragglers"})
    bench["end_to_end"][0]["workloads"].append("qwen15_4b.train_p0")
    bench["per_layer"].append({"name": "steps_per_s.train", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "model step",
                               "moves": "train_tokens_per_s",
                               "workloads": ["qwen15_4b.train_p0"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.resolve("qwen15_4b.train_p0", root=root)
    assert spec["traffic"]["coding"]["straggler_p"] == 0.0
    assert [m["name"] for m in spec["per_layer"]] == ["steps_per_s.train"]
    out = harness.read_per_layer(spec["per_layer"],
                                 {"steps": 10, "window_s": 2.0},
                                 bench_dir=root / "bench")
    assert out == {"steps_per_s.train": {"value": 5.0, "unit": "1/s"}}


def _line(spec, res, monkeypatch):
    import peaks

    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    line = json.loads(harness.result_line(spec, res, 0))
    assert list(line)[-1] == "checks"
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    assert set(line["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert line["device"]["platform"] == "cpu"
    return line


def test_train_driver_runs_one_short_pass(monkeypatch):
    import jax
    import train

    spec = small_cells.train_spec()
    res = train.run(spec, 2**31 + 3, 1.0, 0, jax.devices(), 0.0)
    line = _line(spec, res, monkeypatch)
    assert line["correct"] is True and line["attempted"] >= 1


def test_serve_driver_runs_one_short_pass(monkeypatch):
    import jax
    import serve

    spec = small_cells.serve_spec()
    spec["end_to_end"] = [{"name": n, "unit": u} for n, u in (
        ("ttft_p95_ms", "ms"), ("tpot_p95_ms", "ms"), ("setup_s", "s"))]
    res = serve.run(spec, 2**31 + 3, 1.0, 0, jax.devices(), 0.0)
    line = _line(spec, res, monkeypatch)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10
    assert all(v["value"] > 0 for v in line["metrics"].values())
