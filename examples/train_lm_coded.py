"""End-to-end driver: coded training of a (reduced) assigned
architecture over m = 4 coded machines on the devices the process sees
(``--machines``; set ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
for a (4, 2) mesh of virtual CPU devices), with live straggler sampling
and O(m) optimal decoding. Wraps repro.launch.train with its async
pipeline defaults: deduplicated block execution (each unique block
once, weighted by v = A @ w), lookahead-batched decoding, and
metrics buffered on device between log intervals. Pass --no-dedup /
--collective manual to see the replicated-cluster simulation instead.

The default run composes gradient compression with the coded combine
(``--compress int8``): each block's gradient is quantized to a
per-tensor int8 payload + one float32 scale, an error-feedback
residual carries the quantization error into the next step, and the
fused quantized combine dequantizes and applies the decoded weights
in one pass -- the wire payload drops to ~0.25x of the float32 bytes
(audited in the summary's ``comm_bytes_per_step`` fields). Use
``--compress sign`` for the 1-bit signSGD-style codec or
``--compress none`` to recover the float32 combine bit-for-bit.

Chaos mode (``--chaos <spec>``) switches straggler masks from sampled
to *observed*: a seeded injector simulates per-machine completion
timestamps, a heartbeat monitor derives each round's alive mask by
deadline (exponential backoff per consecutive miss), and
``--dead-after`` consecutive misses declare a machine dead -- which
triggers an elastic re-assignment: the code is re-drawn over the
survivors and training continues from the live state. The spec is
semicolon-separated events over the *original* machine ids::

    kill:J@S          machine J dies permanently at step S
    rack:J,K,...@S    correlated failure: all listed machines die at S
    delay:J@S-E[:X]   J's completion time x X (default 10) for [S, E)
    flap:J@S-E[:K]    J alternates K steps dark / K healthy on [S, E)

e.g. ``--chaos "kill:1@3;delay:2@5-8:20"``. The structured failure
log lands in the summary's ``chaos`` object and, with
``--event-log FILE``, as a JSON artifact:

    {"spec": ..., "events": [{"step", "kind": straggle|recover|dead|
     reassign, "machine", "detail"}, ...], "reassignments": [{"step",
     "generation", "dead", "survivors", "m", "scheme", "replication",
     "n_blocks", "rebuild_s"}, ...], "dead_machines": [...],
     "steps_to_detect": {machine: steps}, "degraded_steps": N,
     "m_final": M, "generations": G}

Try: ``PYTHONPATH=src python examples/train_lm_coded.py --steps 20 \
--machines 4 --straggler-p 0 --chaos "kill:1@5" --compress none``

    PYTHONPATH=src python examples/train_lm_coded.py [--arch ...]
"""

import sys

from repro.launch import train


def main():
    argv = sys.argv[1:] or [
        "--arch", "deepseek-moe-16b", "--steps", "40", "--machines", "4",
        "--seq-len", "48", "--block-size", "2", "--lr", "1e-3",
        "--straggler-p", "0.2", "--scheme", "expander",
        "--decoding", "optimal", "--replication", "2",
        "--dedup", "--lookahead", "10", "--log-every", "5",
        "--compress", "int8",
    ]
    train.main(argv)


if __name__ == "__main__":
    main()
