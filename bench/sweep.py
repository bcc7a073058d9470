#!/usr/bin/env python3
"""Find the knee of a serving cell once, by a sweep of arrival rates on
the chip: for each rate, one window at the cell's traffic with the rate
replaced, without the reference check; prints, per rate, the queue of
waiting requests over the window, iterations per second and the tails.
The highest rate whose queue does not grow is the knee; the cell's
traffic file then states a fixed rate below it.

    python3 bench/sweep.py --config deepseek_coder_33b \\
        --traffic serve_chat --rates 16,20,24 --seconds 20
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH),
                    str(BENCH / "drivers")]
    import harness

    spec = harness.unlisted(args.config, args.traffic)
    harness.enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    driver = __import__(spec["traffic"]["driver"])
    for rate in (float(r) for r in args.rates.split(",")):
        s = copy.deepcopy(spec)
        s["traffic"]["arrivals"]["rate_per_s"] = rate
        # A growing queue shows inside the window; past it, a short
        # drain is enough.
        s["traffic"]["drain_cap_s"] = min(s["traffic"]["drain_cap_s"], 15)
        res = driver.run(s, args.seed, args.seconds, 0,
                         devices[:spec["cell"]["chips"]],
                         time.perf_counter(), check=False)
        print(json.dumps({"rate_per_s": rate, **res["e2e"],
                          "failed": res["failed"],
                          "attempted": res["attempted"], **res["notes"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
