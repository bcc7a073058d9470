"""Coded-serving benchmark: tokens/s + synthetic TTFT tails.

Two halves, both against the real ``repro.serve`` engine (smoke
config) on ``mesh.make_device_mesh`` over the devices of this process
-- on the CPU the caller sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` -- or on no
mesh when there is one device:

* **Engine runs** -- drain the same request set through the
  continuous-batching engine three ways: coded prefill (expander d=2)
  under Bernoulli stragglers, coded at p=0, and the uncoded d=1
  baseline. Reports measured tokens/s and per-request synthetic TTFT,
  and runs the differential pins inline: the coded p=0 token streams
  must be bit-identical to the uncoded single-replica streams AND to
  the sequential-batching reference loop.
* **Latency quantiles** -- ``serve.latency.simulate_shard_ttft`` over
  thousands of pre-decoded rounds (``CodingRuntime.weights_lookahead``)
  at m=32 replicas: paired coded/uncoded TTFT samples per straggler
  model, reduced to p50/p99 rows.

Inline acceptance (the paper's claim, in serving clothes): coded p99 <
uncoded p99 under the Bernoulli model at d=2 -- one deadline + rare
retries instead of waiting out the slowest device -- with p50 within
the jitter of the single-replica latency. Everything runs in the
calling process; ``main`` (the ``benchmarks.run`` entry) returns the
report run.py writes to BENCH_serve.json, naming the device it ran on.
"""

from __future__ import annotations

import json

M_REPLICAS = 32


def _engine_run(cfg, params, mesh, requests, *, scheme: str, p: float,
                slots: int, max_len: int) -> dict:
    from repro.configs import CodingConfig
    from repro import serve as S

    coding = CodingConfig(scheme=scheme, replication=2,
                          straggler_model="bernoulli", straggler_p=p,
                          seed=0)
    eng = S.ServeEngine(cfg, params, n_slots=slots, max_len=max_len,
                        mesh=mesh, coding=coding, m_replicas=8,
                        log_every=8)
    for r in requests:
        eng.submit(r)
    summary = eng.run()
    summary.update(scheme=scheme, straggler_p=p)
    return {"summary": summary, "results": eng.results()}


def measure(full: bool) -> dict:
    import numpy as np

    from benchmarks.train_step import device_info
    from repro.configs import CodingConfig, get_config
    from repro.dist import coded_train
    from repro.launch.mesh import make_device_mesh
    from repro.models import model as M
    from repro import serve as S

    import jax

    # --- engine half: real device runs -------------------------------
    cfg = get_config("qwen1.5-4b").smoke_variant()
    mesh = make_device_mesh() if len(jax.devices()) > 1 else None
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    n_req = 24 if full else 12
    slots, max_len, new_tokens = 8, 48, 8
    rng = np.random.default_rng(0)
    requests = [S.Request(uid=i,
                          prompt=rng.integers(0, cfg.vocab_size,
                                              12 - (i % 4)),
                          max_new_tokens=new_tokens)
                for i in range(n_req)]

    runs = {
        "coded": _engine_run(cfg, params, mesh, requests,
                             scheme="expander", p=0.2,
                             slots=slots, max_len=max_len),
        "coded_p0": _engine_run(cfg, params, mesh, requests,
                                scheme="expander", p=0.0,
                                slots=slots, max_len=max_len),
        "uncoded": _engine_run(cfg, params, mesh, requests,
                               scheme="uncoded", p=0.0,
                               slots=slots, max_len=max_len),
    }
    ref = S.sequential_serve(params, cfg, requests, n_slots=slots,
                             max_len=max_len)
    stream_ok = all(
        np.array_equal(runs["coded_p0"]["results"][r.uid],
                       runs["uncoded"]["results"][r.uid])
        and np.array_equal(runs["coded_p0"]["results"][r.uid],
                           ref[r.uid])
        for r in requests)

    # --- latency half: paired TTFT quantiles over many rounds --------
    rounds = 20000 if full else 6000
    lat_model = S.ReplicaLatencyModel(m=M_REPLICAS)
    lat_rows = []
    coded_p99 = uncoded_p99 = None
    for model, p in (("bernoulli", 0.2), ("markov", 0.2)):
        coding = CodingConfig(scheme="expander", replication=2,
                              straggler_model=model, straggler_p=p,
                              seed=1)
        rt = coded_train.CodingRuntime(coding, M_REPLICAS, debias=False)
        W, alive = rt.weights_lookahead(rounds)
        lat_rng = np.random.default_rng(2)
        lat = np.stack([lat_model.latencies(a, lat_rng) for a in alive])
        coded, uncoded = S.simulate_shard_ttft(
            rt.assignment, W, alive, lat,
            deadline_ms=lat_model.deadline_ms,
            straggle_ms=lat_model.straggle_ms)
        c_row = S.percentile_row("expander_d2", model, p, coded)
        u_row = S.percentile_row("uncoded", model, p, uncoded)
        lat_rows += [c_row, u_row]
        if model == "bernoulli":
            coded_p99, uncoded_p99 = c_row["p99_ms"], u_row["p99_ms"]

    return {
        "device": device_info(),
        "m_replicas_sim": M_REPLICAS,
        "rounds_sim": rounds,
        "requests": n_req,
        "engine": {k: v["summary"] for k, v in runs.items()},
        "latency_rows": lat_rows,
        "acceptance": {
            "token_stream_bit_identical_at_p0": bool(stream_ok),
            "coded_p99_ms": coded_p99,
            "uncoded_p99_ms": uncoded_p99,
            "coded_p99_lt_uncoded": bool(coded_p99 < uncoded_p99),
        },
    }


def main(fast: bool = True) -> dict:
    report = measure(full=not fast)
    for name, s in report["engine"].items():
        ttft = (f", TTFT p50 {s['ttft_p50_ms']:.1f} ms "
                f"p99 {s['ttft_p99_ms']:.1f} ms"
                if "ttft_p50_ms" in s else "")
        print(f"  engine[{name}]: {s['tokens_per_s']:.1f} tok/s over "
              f"{s['requests']} reqs, {s['retries']} retries{ttft}")
    for row in report["latency_rows"]:
        print(f"  sim[{row['scheme']}/{row['straggler_model']} "
              f"p={row['p']}]: p50 {row['p50_ms']:.2f} ms, "
              f"p99 {row['p99_ms']:.2f} ms")
    acc = report["acceptance"]
    # Acceptance: scheduling/coding must never change the tokens, and
    # d=2 replication must bound the tail below the slowest device.
    assert acc["token_stream_bit_identical_at_p0"], \
        "coded p=0 streams diverged from the single-replica oracle"
    assert acc["coded_p99_lt_uncoded"], \
        (f"coded p99 {acc['coded_p99_ms']} ms must beat uncoded "
         f"{acc['uncoded_p99_ms']} ms under bernoulli stragglers")
    print(f"  acceptance: bit-identical streams at p=0; coded p99 "
          f"{acc['coded_p99_ms']:.2f} ms < uncoded "
          f"{acc['uncoded_p99_ms']:.2f} ms")
    return report


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    print(json.dumps(main(fast=not args.full), indent=2))
