"""The readers of the program's spans (``bench/metrics/batch_build_ms,
decode_ms, decode_wait_ms, decode_memo_hit_share .train.py``) give
exact answers on hand-made records: only the window's steps count, a
decode chunk counts by its first round, and records of an earlier run
in the same process are left out. A program without the span recorder
gives no reading, and one short pass of the training driver on the CPU
gives all four."""

import math
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path[:0] = [str(REPO / "src"), str(BENCH), str(BENCH / "drivers")]

import harness  # noqa: E402
import small_cells  # noqa: E402
from repro import spans  # noqa: E402

READERS = ["batch_build_ms.train", "decode_ms.train",
           "decode_wait_ms.train", "decode_memo_hit_share.train"]


def _metrics(names=READERS):
    return [{"name": n, "unit": "x"} for n in names]


def _read(ctx):
    return {k: v["value"] for k, v in
            harness.read_per_layer(_metrics(), ctx).items()}


class _Hand:
    """Hand-made records, numbered in the order they are added."""

    def __init__(self):
        self.recs = []

    def add(self, name, step, ms, **attrs):
        self.recs.append(types.SimpleNamespace(
            name=name, step=step, ms=ms, attrs=attrs,
            id=len(self.recs) + 1))

    def run(self, steps, chunks, ms=1.0):
        """One run's records: a batch (``ms``) and its blocks (1 ms)
        and a wait (0.5 ms) each step, and the decode chunks given as
        (first round, rounds, novel, ms)."""
        self.add("data.batch", 0, ms)     # set-up's first batch
        for k in range(steps):
            self.add("data.batch", k, ms)
            self.add("data.blocks", k, 1.0, blocks=12)
            self.add("coding.wait", k, 0.5)
        for first, rounds, novel, t in chunks:
            self.add("coding.lookahead", first, t, rounds=rounds,
                     novel=novel)


def _ctx(steps, check_steps=3):
    return {"kind": "train", "steps": steps,
            "traffic": {"check_steps": check_steps}}


@pytest.fixture
def hand(monkeypatch):
    h = _Hand()
    monkeypatch.setattr(spans, "records", lambda name=None: [
        r for r in h.recs if name is None or r.name == name])
    return h


def test_window_starts_after_the_checked_steps(hand):
    # Steps 0..2 are set-up's (batches of 100 ms); the window is 3..8.
    hand.add("data.batch", 0, 100.0)
    for k in range(9):
        hand.add("data.batch", k, 100.0 if k < 3 else 2.0)
        hand.add("data.blocks", k, 100.0 if k < 3 else 1.0)
        hand.add("coding.wait", k, 100.0 if k < 3 else 0.25)
    hand.add("data.batch", 9, 100.0)   # built for the step after
    hand.add("coding.lookahead", 0, 100.0, rounds=4, novel=4)
    hand.add("coding.lookahead", 4, 2.0, rounds=4, novel=3)
    got = _read(_ctx(6))
    assert got["batch_build_ms.train"] == pytest.approx(3.0)
    assert got["decode_wait_ms.train"] == pytest.approx(0.25)
    assert got["decode_ms.train"] == pytest.approx(0.5)
    assert got["decode_memo_hit_share.train"] == pytest.approx(25.0)


def test_chunk_straddling_the_window_end_counts_whole(hand):
    # Window 3..8: the chunk at 0 began before it and is left out; the
    # chunk at 8 covers 8..11 and counts with all its rounds.
    hand.run(10, [(0, 4, 4, 40.0), (4, 4, 2, 4.0), (8, 4, 1, 2.0),
                  (12, 4, 4, 40.0)])
    got = _read(_ctx(6))
    assert got["decode_ms.train"] == pytest.approx(6.0 / 8)
    assert got["decode_memo_hit_share.train"] == pytest.approx(
        100.0 * (1 - 3 / 8))


def test_records_of_an_earlier_run_are_left_out(hand):
    hand.run(12, [(4, 4, 4, 80.0), (8, 4, 4, 80.0)], ms=50.0)
    hand.run(10, [(4, 4, 0, 1.0), (8, 4, 2, 3.0)], ms=2.0)
    got = _read(_ctx(6))
    assert got["batch_build_ms.train"] == pytest.approx(3.0)
    assert got["decode_wait_ms.train"] == pytest.approx(0.5)
    assert got["decode_ms.train"] == pytest.approx(0.5)
    assert got["decode_memo_hit_share.train"] == pytest.approx(75.0)


def test_no_records_no_reading(hand):
    assert _read(_ctx(6)) == {}
    hand.run(9, [])   # a run whose window holds no decode chunk
    got = _read(_ctx(6))
    assert set(got) == {"batch_build_ms.train", "decode_wait_ms.train"}


def test_program_without_the_recorder_gives_no_reading(monkeypatch):
    import repro

    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert _read(_ctx(6)) == {}


def test_short_pass_of_the_driver_gives_all_four():
    import jax
    import train

    spec = small_cells.train_spec()
    spec["traffic"]["lookahead"] = 2   # chunks begin inside a short window
    res = train.run(spec, 2**31 + 11, 1.0, 0, jax.devices(), 0.0)
    assert res["correct"] is True
    got = _read(res["ctx"])
    assert set(got) == set(READERS)
    assert all(math.isfinite(v) and v >= 0 for v in got.values())
    assert got["decode_memo_hit_share.train"] <= 100.0
