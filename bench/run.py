#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Resolves the cell by its name in BENCHMARK.json, loads, warms up and
drives its first steps (set-up), measures for ``--seconds``, checks what
the timed path produced against the plain reference, and prints one
JSON object as the last line of standard output:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "checks"}

``metrics`` holds the cell's end-to-end metrics with ``--trace 0`` and
its per-layer metrics with ``--trace 1``; ``checks`` holds each number
compared with its limit, which are also the last lines of standard
error. Without a TPU, with fewer chips than the cell asks for, or
without the program's sources beside this directory, the run exits
non-zero and prints no result.

    python3 bench/run.py --workload <name> --control --seeds 1,2,3

reads, instead of a run, the control's and the planted faults' numbers
on those seeds at the cell's size, and

    python3 bench/run.py --workload <name> --seeds 1,2,3 --seconds 2

the program's compared numbers on those seeds, one short run each in
one process (see PERF.md; the benchmark's own runs never do either).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
SRC = BENCH.parent / "src"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--seeds", default="")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"bench: no program sources at {SRC}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(SRC), str(BENCH), str(BENCH / "drivers")]

    import harness

    spec = harness.resolve(args.workload)
    seconds = args.seconds or spec["run_seconds"]
    harness.enable_compile_cache()
    import jax

    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 3
    if len(devices) < chips:
        print(f"bench: the cell asks for {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    devices = devices[:chips]
    driver = __import__(spec["traffic"]["driver"])

    if args.control:
        seeds = [int(s) for s in args.seeds.split(",") if s]
        if spec["traffic"]["driver"] == "serve":
            rows = driver.control(spec, seeds, devices, seconds)
        else:
            rows = driver.control(spec, seeds, devices)
        for row in rows:
            print(json.dumps(row), flush=True)
        return 0

    if args.seeds:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = driver.run(spec, seed, seconds, 0, devices,
                             time.perf_counter())
            print(json.dumps({"seed": seed, "correct": res["correct"],
                              "checks": res["checks"],
                              "notes": res["notes"]}), flush=True)
        return 0

    res = driver.run(spec, args.seed, seconds, args.trace, devices,
                     T_START)
    line = harness.result_line(spec, res, args.trace)
    print(json.dumps(res["notes"]), file=sys.stderr)
    print(harness.check_line(res["checks"]), file=sys.stderr, flush=True)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
