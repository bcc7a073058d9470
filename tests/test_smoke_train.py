"""Fast end-to-end smoke: the real ``repro.launch.train`` driver on
the 8-virtual-device mesh.

Runs as a subprocess because the virtual-device count must enter
XLA_FLAGS before jax initialises (conftest keeps the test process on
the real 1-CPU device by design). The driver itself asserts the
decreasing window-mean loss and prints a JSON summary line; this test
checks the exit status and the summary. Two runs keep both execution
paths in tier-1: the async dedup pipeline (lookahead decoding,
buffered metrics) and the replicated path through the manual
``coded_allreduce`` collective. The longer variants stay behind
--runslow in test_system.py.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _driver_proc(*extra, env_extra=None, check=True):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.update(env_extra or {})
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--arch", "qwen1.5-4b", "--steps", "12", "--seq-len", "32",
         "--block-size", "2", "--straggler-p", "0.2", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420)
    if check:
        assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc


def _run_driver(*extra, env_extra=None):
    proc = _driver_proc(*extra, env_extra=env_extra)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_train_driver_smoke_async_dedup_pipeline():
    summary = _run_driver("--dedup", "--lookahead", "6",
                          "--log-every", "4")
    assert summary["steps"] == 12
    assert summary["m_workers"] == 4  # (4, 2) mesh over 8 virtual devices
    assert summary["path"] == "dedup"
    assert summary["collective"] == "gspmd"
    # decode memoisation sanity: at most one decode per sampled mask
    # (the lookahead-vs-per-step batching itself is pinned in
    # tests/test_coding_runtime.py)
    assert summary["decode_calls"] <= 12
    assert np.isfinite(summary["first_loss"])
    assert np.isfinite(summary["last_loss"])
    # the window-mean decrease is asserted inside train.main; reaching
    # the summary line means the full coded path (batcher -> decode ->
    # sharded step) ran and learned
    assert summary["last_loss"] < summary["first_loss"] + 1.0


ONE_DEVICE = {"XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


def test_train_driver_machines_independent_of_devices():
    """--machines decouples the coded machine count from the chips:
    m = 12 machines (n = 12 blocks) on a one-device mesh, the one-chip
    regime of the paper's m >> chips."""
    summary = _run_driver("--machines", "12", "--steps", "3",
                          "--block-size", "1", "--log-every", "1",
                          env_extra=ONE_DEVICE)
    assert summary["steps"] == 3
    assert summary["m_workers"] == 12
    assert summary["path"] == "dedup"
    assert len(summary["losses"]) == 3
    assert np.isfinite(summary["losses"]).all()


@pytest.mark.parametrize("extra", [("--machines", "1"), ()],
                         ids=["explicit", "from_one_device_mesh"])
def test_train_driver_invalid_code_names_machines(extra):
    """A code the scheme cannot build (expander d=2 over m=1: a graph
    with no edges) fails before training with an error that names the
    flag setting m -- whether m was given or came from the mesh."""
    proc = _driver_proc(*extra, env_extra=ONE_DEVICE, check=False)
    assert proc.returncode != 0
    assert "--machines" in proc.stderr
    assert "m=1" in proc.stderr


def test_train_driver_smoke_manual_collective():
    summary = _run_driver("--collective", "manual", "--lookahead", "4",
                          "--log-every", "6")
    assert summary["steps"] == 12
    assert summary["path"] == "replicated"  # manual implies replicated
    assert summary["collective"] == "manual"
    assert np.isfinite(summary["last_loss"])
    assert summary["last_loss"] < summary["first_loss"] + 1.0


def test_train_driver_smoke_streaming_manual():
    """--stream-chunk routes the manual collective through the
    lax.scan streaming accumulator end to end (on the driver's m = 4
    workers over 4 data shards the scan is a single chunk -- the
    multi-chunk differential lives in tests/test_streaming.py)."""
    summary = _run_driver("--collective", "manual", "--stream-chunk",
                          "1", "--lookahead", "4", "--log-every", "6")
    assert summary["steps"] == 12
    assert summary["collective"] == "manual"
    assert summary["stream_chunk"] == 1
    assert np.isfinite(summary["last_loss"])
    assert summary["last_loss"] < summary["first_loss"] + 1.0


def test_train_driver_smoke_fsdp():
    """--fsdp swaps the replicated param placement for the
    worker-sharded fsdp_specs; the training stream itself must be
    unaffected (same algebra, different layout)."""
    summary = _run_driver("--dedup", "--fsdp", "--lookahead", "6",
                          "--log-every", "4")
    assert summary["steps"] == 12
    assert summary["fsdp"] is True
    assert np.isfinite(summary["last_loss"])
    assert summary["last_loss"] < summary["first_loss"] + 1.0


def test_train_driver_smoke_compressed_sign_packed():
    """The packed 1-bit wire codec end to end on the dedup path: the
    8-per-byte payload must clear the 0.05x comm acceptance bar."""
    summary = _run_driver("--dedup", "--compress", "sign_packed",
                          "--lookahead", "6", "--log-every", "4")
    assert summary["steps"] == 12
    assert summary["compress"] == "sign_packed"
    assert np.isfinite(summary["last_loss"])
    assert summary["last_loss"] < summary["first_loss"] + 1.0
    ratio = (summary["comm_bytes_per_step"]
             / summary["comm_bytes_per_step_float32"])
    assert ratio <= 0.05, \
        f"sign_packed comm ratio {ratio:.4f} exceeds 0.05"


def test_stream_chunk_requires_manual_collective():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--steps", "2",
         "--stream-chunk", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "--collective manual" in proc.stderr


def test_train_driver_chaos_kill_reassigns_and_converges(tmp_path):
    """The CI chaos smoke: kill one of the 4 coded machines at step 3
    of a 12-step run. The heartbeat monitor must declare it dead after
    --dead-after consecutive misses, the driver must elastically
    re-assign over the 3 survivors, and the final loss must land
    within tolerance of the clean (no-failure) run -- straggler
    sampling off on both sides so chaos is the only difference."""
    log = str(tmp_path / "events.json")
    clean = _run_driver("--straggler-p", "0", "--log-every", "4")
    summary = _run_driver("--straggler-p", "0", "--log-every", "4",
                          "--chaos", "kill:1@3", "--event-log", log)
    chaos = summary["chaos"]
    assert chaos["dead_machines"] == [1]
    assert chaos["steps_to_detect"] == {"1": 3}
    assert chaos["m_final"] == 3 and chaos["generations"] == 2
    assert len(chaos["reassignments"]) == 1
    re = chaos["reassignments"][0]
    assert re["dead"] == [1] and re["survivors"] == [0, 2, 3]
    # The re-assignment's rebuild time is its train.reassign span.
    assert summary["spans"]["train.reassign"]["count"] == 1
    assert re["rebuild_s"] == round(
        summary["spans"]["train.reassign"]["total_s"], 3)
    kinds = [e["kind"] for e in chaos["events"]]
    assert kinds == ["straggle", "dead", "reassign"]
    # Pre-kill steps see identical inputs (same seed, no stragglers):
    # the streams must match bitwise until the first missed heartbeat.
    assert summary["losses"][:3] == clean["losses"][:3]
    # Post-reassignment convergence: same noise floor as the clean run.
    assert np.isfinite(summary["last_loss"])
    assert abs(summary["last_loss"] - clean["last_loss"]) < 0.6, (
        f"chaos run ended at {summary['last_loss']:.3f}, clean at "
        f"{clean['last_loss']:.3f}")
    # The structured event log is a JSON artifact mirroring the
    # summary's chaos object.
    with open(log) as f:
        assert json.load(f) == chaos


def test_train_driver_chaos_transient_delay_no_reassign():
    """A bounded delay window straggles a machine (misses, backoff,
    recovery) without ever declaring it dead: no re-assignment, all
    machines alive at the end."""
    summary = _run_driver("--straggler-p", "0", "--log-every", "4",
                          "--chaos", "delay:2@4-6:10")
    chaos = summary["chaos"]
    assert chaos["dead_machines"] == []
    assert chaos["reassignments"] == []
    assert chaos["m_final"] == 4 and chaos["generations"] == 1
    kinds = {e["kind"] for e in chaos["events"]}
    assert "dead" not in kinds
    assert np.isfinite(summary["last_loss"])


def test_batch_thread_failure_kills_driver_with_traceback():
    """Pipeline-hardening regression: an exception on the batch-builder
    worker thread (injected at a double-buffered step) must propagate
    to the main loop and exit the driver with the original error, not
    hang or train on with stale data."""
    proc = _driver_proc("--steps", "6", "--log-every", "2",
                        env_extra={"REPRO_FAIL_BATCH_AT": "3"},
                        check=False)
    assert proc.returncode != 0
    assert "injected batch failure at step 3" in proc.stderr
    assert "RuntimeError" in proc.stderr


def test_chaos_flag_cross_checks():
    proc = _driver_proc("--chaos", "kill:1@3", "--ckpt-dir", "/tmp/x",
                        check=False)
    assert proc.returncode != 0
    assert "--ckpt-dir" in proc.stderr
    proc = _driver_proc("--event-log", "/tmp/x.json", check=False)
    assert proc.returncode != 0
    assert "--chaos" in proc.stderr
    proc = _driver_proc("--chaos", "kill:1@3", "--no-dedup",
                        check=False)
    assert proc.returncode != 0
    assert "dedup" in proc.stderr


def test_train_driver_smoke_compressed_int8():
    """The compression-composed execution model end to end: int8
    quantization + error feedback + the fused quantized combine on the
    dedup path, with the comm-bytes accounting in the summary. The
    driver's own decreasing-loss assertion runs inside the subprocess;
    the 4x wire shrink (int8 payload + scale sideband vs float32
    gradients) must beat the 0.3x acceptance bar."""
    summary = _run_driver("--dedup", "--compress", "int8",
                          "--lookahead", "6", "--log-every", "4")
    assert summary["steps"] == 12
    assert summary["path"] == "dedup"
    assert summary["compress"] == "int8"
    assert np.isfinite(summary["last_loss"])
    assert summary["last_loss"] < summary["first_loss"] + 1.0
    ratio = (summary["comm_bytes_per_step"]
             / summary["comm_bytes_per_step_float32"])
    assert ratio <= 0.3, f"int8 comm ratio {ratio:.3f} exceeds 0.3"
