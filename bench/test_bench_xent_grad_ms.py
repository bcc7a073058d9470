"""The reader of the head's softmax-gradient time, `xent_grad_ms.train`,
on hand-made device traces with known answers."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import tracing  # noqa: E402


def _xent_grad_ms(trace, steps=2, kind="train"):
    ctx = {"kind": kind, "trace": trace, "steps": steps}
    out = harness.read_per_layer(
        [{"name": "xent_grad_ms.train", "unit": "ms"}], ctx)
    return out.get("xent_grad_ms.train", {}).get("value")


def _trace(step, extra=()):
    return tracing.reduce_events({
        "devices": {"0": [[f"%{n}", 100 + 200 * k + s, d]
                          for k in range(2) for n, s, d in step]
                    + list(extra)},
        "host": [["bench.window_start", 90, 0], ["bench.window_end", 500, 0]],
    }, gap_floor_ns=0)


def test_xent_grad_reader_on_hand_made_trace(capsys):
    """Two steps, one kernel call of 40 ns each; the matmul fusions that
    read its output, an attention kernel and a call outside the window
    do not count."""
    step = [["xent_grad.1", 10, 40], ["fusion.123", 55, 30],
            ["divide_add_fusion", 90, 30], ["flash_attention_dq.3", 130, 20]]
    trace = _trace(step, extra=[["%xent_grad.1", 20, 40]])
    capsys.readouterr()
    assert _xent_grad_ms(trace) == pytest.approx(40e-9 * 1e3)
    notes = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert notes == {"xent_grad_calls_per_step": 1.0}


def test_xent_grad_reader_finds_nothing_without_the_kernel():
    step = [["fusion.136", 10, 40], ["divide_add_fusion", 60, 50],
            ["flash_attention_fwd.2", 120, 20]]
    assert _xent_grad_ms(_trace(step)) is None
    assert _xent_grad_ms(None) is None


def test_xent_grad_reader_reads_only_training_runs():
    trace = _trace([["xent_grad.1", 10, 40]])
    assert _xent_grad_ms(trace, kind="serve") is None
    assert _xent_grad_ms(trace, steps=0) is None
