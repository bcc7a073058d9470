"""Device time of training's attention: milliseconds a window step in
the instructions of the flash-attention Pallas calls
(``flash_attention_fwd``, run again by the layer's rematerialisation,
``flash_attention_dkv`` and ``flash_attention_dq``), averaged over the
chips. The calls a step go to standard error beside the run's notes:
4 a layer where the kernel is engaged. None where the trace holds no
such call, as on a program whose attention is the blockwise scan."""

import json
import sys

import tracing

PATTERN = r"^%?flash_attention"


def read(ctx):
    if ctx["kind"] != "train" or ctx["trace"] is None or not ctx["steps"]:
        return None
    hit = tracing.kernel_calls(ctx["trace"], PATTERN)
    if hit is None:
        return None
    calls, seconds = hit
    print(json.dumps({"attention_calls_per_step": calls / ctx["steps"]}),
          file=sys.stderr)
    return 1e3 * seconds / ctx["steps"]
