"""Device time of the head's softmax gradient in training: milliseconds
a window step in the instructions of the ``xent_grad`` Pallas call,
averaged over the chips. The calls a step go to standard error beside
the run's notes: 1 where the head's cross-entropy backward takes the
kernel. None where the trace holds no such call, as on a program that
derives the softmax gradient inside the head's matmuls."""

import json
import sys

import tracing

PATTERN = r"^%?xent_grad"


def read(ctx):
    if ctx["kind"] != "train" or ctx["trace"] is None or not ctx["steps"]:
        return None
    hit = tracing.kernel_calls(ctx["trace"], PATTERN)
    if hit is None:
        return None
    calls, seconds = hit
    print(json.dumps({"xent_grad_calls_per_step": calls / ctx["steps"]}),
          file=sys.stderr)
    return 1e3 * seconds / ctx["steps"]
