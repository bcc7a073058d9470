"""Serving driver: the CLI over the continuous-batching coded engine.

Builds a ``repro.serve.ServeEngine`` -- admission queue, fixed-slot
cache pool, iteration-level prefill/decode interleave, and (with
``--scheme expander``) d-replicated coded prefill with optimal-decode
combine weights and a synthetic per-replica latency model -- then
drains ``--requests`` synthetic prompts through it and prints a JSON
summary line (tokens/s, synthetic TTFT p50/p99, retries).

  python -m repro.launch.serve --arch qwen1.5-4b --requests 12 \
      --scheme expander --straggler-p 0.2

The mesh is built over the devices the process sees (none on one
device); on the CPU a caller that wants several sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in the
environment of the process it starts.

``--check`` re-serves the same requests through the sequential-
batching reference loop and asserts bit-identical token streams (and,
at ``--straggler-p 0``, that the coded stream equals the uncoded
single-replica stream). The vlm/audio families need per-request
prefix/src side channels the pool does not carry; they take the
legacy static-batch path automatically.
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import CodingConfig, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_device_mesh, make_production_mesh
from repro.models import model as M
from repro import serve as S


def _build_requests(args, cfg):
    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.requests):
        # vary lengths (bounded by --prompt-spread) so the interleave
        # actually schedules prefill against decode
        plen = args.prompt_len - (i % (args.prompt_spread + 1))
        plen = max(1, plen)
        reqs.append(S.Request(
            uid=i, prompt=rng.integers(0, cfg.vocab_size, plen),
            max_new_tokens=args.max_new_tokens))
    return reqs


def _static_main(args, cfg):
    """Legacy one-shot batched path (vlm/audio: per-request prefix/src
    side channels)."""
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key)
    B = args.slots
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, args.prompt_len)), jnp.int32)

    kw = {}
    if cfg.arch_type == "vlm":
        kw["prefix"] = jnp.asarray(
            rng.normal(size=(B, cfg.prefix_len, cfg.d_model)) * 0.02,
            jnp.dtype(cfg.dtype))
    src = None
    if cfg.arch_type == "audio":
        src = jnp.asarray(
            rng.normal(size=(B, cfg.prefix_len, cfg.d_model)) * 0.02,
            jnp.dtype(cfg.dtype))
        kw["src"] = src

    M.prefill(params, prompts, cfg, **kw)
    cache = M.init_decode_cache(
        cfg, B, args.max_len,
        src_len=cfg.prefix_len if cfg.arch_type == "audio" else 0)
    if cfg.arch_type == "audio":
        cache["enc"] = M.encode(params, src, cfg)

    step = jax.jit(lambda p, t, c: M.decode_step(p, t, c, cfg))
    logits = None
    for i in range(args.prompt_len):
        logits, cache = step(params, prompts[:, i], cache)
    out_tokens = []
    t0 = time.perf_counter()
    tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1).astype(jnp.int32)
    for _ in range(args.max_new_tokens):
        out_tokens.append(np.asarray(tok))
        logits, cache = step(params, tok, cache)
        tok = jnp.argmax(logits[:, :cfg.vocab_size],
                         axis=-1).astype(jnp.int32)
    dt = time.perf_counter() - t0
    gen = np.stack(out_tokens, axis=1)
    assert not np.isnan(np.asarray(logits)).any()
    summary = {"path": "static", "arch": cfg.name,
               "requests": B, "new_tokens": int(gen.size),
               "tokens_per_s": gen.size / max(dt, 1e-9),
               "sample": gen[0][:12].tolist()}
    print(json.dumps(summary))
    return {"tokens": gen, "summary": summary}


def main(argv=None, *, cfg=None) -> dict:
    """Run the driver on ``argv``. ``cfg`` (a ModelConfig) replaces
    the registry config that ``--arch`` / ``--full-config`` select."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4,
                    help="pool width: requests decoded concurrently")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--prompt-spread", type=int, default=3,
                    help="prompt lengths vary in [len-spread, len]")
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64,
                    help="decode-cache capacity per slot")
    ap.add_argument("--scheme", default="expander",
                    choices=("expander", "uncoded"),
                    help="expander: d-replicated coded prefill; "
                         "uncoded: single replica per shard")
    ap.add_argument("--replicas", type=int, default=8,
                    help="replica slices m for the latency model")
    ap.add_argument("--replication", type=int, default=2)
    ap.add_argument("--decoding", default="optimal",
                    choices=("optimal", "fixed"))
    ap.add_argument("--straggler-model", default="bernoulli",
                    choices=("bernoulli", "markov", "adversarial"))
    ap.add_argument("--straggler-p", type=float, default=0.1)
    ap.add_argument("--base-ms", type=float, default=2.0)
    ap.add_argument("--deadline-ms", type=float, default=6.0)
    ap.add_argument("--straggle-ms", type=float, default=60.0)
    ap.add_argument("--log-every", type=int, default=16,
                    help="iterations between host token fetches")
    ap.add_argument("--check", action="store_true",
                    help="pin the engine streams against the "
                         "sequential-batching reference loop")
    ap.add_argument("--no-mesh", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if cfg is None:
        cfg = get_config(args.arch)
        if not args.full_config:
            cfg = cfg.smoke_variant()

    # Validate the generation budget against the cache capacity (and
    # any config max_seq_len) BEFORE touching the device -- the old
    # driver discovered overflow mid-generation.
    try:
        S.validate_budget(cfg, args.prompt_len, args.max_new_tokens,
                          args.max_len)
    except ValueError as e:
        ap.error(str(e))
    enable_compile_cache()

    if cfg.arch_type in ("vlm", "audio"):
        return _static_main(args, cfg)

    if args.production_mesh:
        mesh = make_production_mesh()
    elif args.no_mesh or len(jax.devices()) == 1:
        mesh = None
    else:
        mesh = make_device_mesh()

    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    reqs = _build_requests(args, cfg)
    coding = CodingConfig(
        scheme=args.scheme, replication=args.replication,
        decoding=args.decoding, straggler_model=args.straggler_model,
        straggler_p=args.straggler_p, seed=args.seed)
    latency = S.ReplicaLatencyModel(
        m=args.replicas, base_ms=args.base_ms,
        deadline_ms=args.deadline_ms, straggle_ms=args.straggle_ms)

    engine = S.ServeEngine(
        cfg, params, n_slots=args.slots, max_len=args.max_len,
        mesh=mesh, coding=coding, m_replicas=args.replicas,
        latency=latency, log_every=args.log_every)
    for r in reqs:
        engine.submit(r)
    summary = engine.run()
    results = engine.results()

    check_passed = None
    if args.check:
        ref = S.sequential_serve(params, cfg, reqs,
                                 n_slots=args.slots,
                                 max_len=args.max_len)
        check_passed = all(np.array_equal(results[r.uid], ref[r.uid])
                           for r in reqs)
        assert check_passed, \
            "engine streams diverged from the sequential reference"

    summary.update(path="engine", arch=cfg.name, scheme=args.scheme,
                   m_replicas=args.replicas,
                   replication=args.replication,
                   straggler_model=args.straggler_model,
                   straggler_p=args.straggler_p,
                   mesh=(list(mesh.shape.values())
                         if mesh is not None else None),
                   check_passed=check_passed,
                   sample=results[0][:12].tolist())
    print(json.dumps(summary))
    return {"results": results, "summary": summary}


if __name__ == "__main__":
    main()
