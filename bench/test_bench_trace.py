"""The reduction from a device trace to busy and idle time, time per
operation, collective time and exposed collective time, on hand-made
events with known answers."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import tracing  # noqa: E402

HAND = {
    "devices": {"0": [["fusion.1", 100, 50], ["all-reduce.2", 140, 40],
                      ["rmsnorm.3", 200, 30], ["fusion.3", 260, 20],
                      ["fusion.9", 290, 40], ["fusion.0", 50, 20]],
                "1": [["fusion.1", 100, 100]]},
    "host": [["bench.window_start", 90, 0], ["bench.window_end", 300, 0],
             ["bench.host_wait", 230, 25]],
}


def test_hand_made_trace():
    r = tracing.reduce_events(HAND, gap_floor_ns=0)
    assert r["window_s"] == pytest.approx(210e-9)
    # device 0 busy: [100,180] + [200,230] + [260,280] + [290,300] = 140;
    # device 1: [100,200] = 100; the mean over the two devices
    assert r["busy_s"] == pytest.approx(120e-9)
    # the collective [140,180] overlaps compute on [140,150] only
    assert r["collective_s"] == pytest.approx(20e-9)
    assert r["collective_exposed_s"] == pytest.approx(15e-9)
    assert r["ops"]["rmsnorm.3"] == [0.5, pytest.approx(15e-9)]
    assert "fusion.0" not in r["ops"]      # wholly before the window
    assert tracing.kernel_calls(r, tracing.KERNELS["rmsnorm"]) == (
        0.5, pytest.approx(15e-9))
    assert tracing.kernel_calls(r, "decode_attention") is None
    idle = dict(r["idle_gaps"])
    # device 0's gap [230,260] and device 1's [200,300] have their
    # middles in the host's wait span; the rest in no span
    assert idle["host_wait (2 gaps)"] == pytest.approx((30 + 100) / 2 * 1e-9)
    assert idle["no host span (5 gaps)"] == pytest.approx(50 / 2 * 1e-9)


def test_window_falls_back_to_the_operations():
    events = {"devices": HAND["devices"], "host": []}
    assert tracing.window_bounds(events) == (50, 330)


def test_operations_are_named_by_their_own_instruction():
    text = ("%fusion.7 = bf16[12,1024,2560]{2,1,0} fusion(bf16[12,1024,2560]"
            "{2,1,0} %rmsnorm.1, f32[2560]{0} %all-reduce.2), kind=kLoop")
    assert tracing.op_name(text) == "%fusion.7"
    assert tracing.op_name("rmsnorm.3") == "rmsnorm.3"
    rx = tracing.re.compile(tracing.KERNELS["rmsnorm"])
    assert rx.search(tracing.op_name(text)) is None
    assert tracing.COLLECTIVE.search(tracing.op_name(text)) is None
    assert rx.search(tracing.op_name("%rmsnorm.1.clone = bf16[8]{0} "
                                     "custom-call(%x)"))
    assert tracing.COLLECTIVE.search("%all-reduce-start.4")
