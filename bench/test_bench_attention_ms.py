"""The reader of training's attention time, `attention_ms.train`, on
hand-made device traces with known answers."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import tracing  # noqa: E402

NO_KERNEL = {
    "devices": {"0": [["fusion.1", 100, 50], ["all-reduce.2", 140, 40],
                      ["rmsnorm.3", 200, 30]]},
    "host": [["bench.window_start", 90, 0], ["bench.window_end", 300, 0]],
}


def _attention_ms(trace, steps=2):
    ctx = {"kind": "train", "trace": trace, "steps": steps}
    out = harness.read_per_layer(
        [{"name": "attention_ms.train", "unit": "ms"}], ctx)
    return out.get("attention_ms.train", {}).get("value")


def test_attention_reader_on_hand_made_trace(capsys):
    """Two steps of one layer: the forward kernel, its recomputation
    and the two backward kernels each step, 120 ns a step in all; a
    fusion that reads a kernel's output and an operation outside the
    window do not count."""
    step = [["flash_attention_fwd.13", 0, 30],
            ["flash_attention_fwd.14", 40, 20],
            ["flash_attention_dkv.10", 70, 40], ["fusion.3", 115, 5],
            ["flash_attention_dq.10", 120, 30]]
    events = {
        "devices": {"0": [[f"%{n}", 100 + 200 * k + s, d]
                          for k in range(2) for n, s, d in step]
                    + [["%flash_attention_fwd.13", 10, 30]]},
        "host": [["bench.window_start", 90, 0], ["bench.window_end", 500, 0]],
    }
    trace = tracing.reduce_events(events, gap_floor_ns=0)
    capsys.readouterr()
    assert _attention_ms(trace) == pytest.approx(120e-9 * 1e3)
    notes = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert notes == {"attention_calls_per_step": 4.0}


def test_attention_reader_finds_nothing_without_the_kernel():
    trace = tracing.reduce_events(NO_KERNEL, gap_floor_ns=0)
    assert _attention_ms(trace) is None
    assert _attention_ms(None) is None
