"""The benchmark's cells cut to a size the CPU tests can hold: the same
files, drivers and checks, with every width and length made small."""

from __future__ import annotations

import copy

import harness

SMALL_MODEL = {"hidden_size": 64, "num_attention_heads": 4,
               "num_key_value_heads": 4, "intermediate_size": 128,
               "num_hidden_layers": 2, "vocab_size": 256}

# The training checks' limits at this size, set from readings at this
# size (PERF.md, section 4): the program's largest over 6 seeds and the
# smallest that the float8 control and the half-batch fault give. The
# cells' own limits, in their traffic files, are set from readings at
# the cells' size.
SMALL_TRAIN_LIMITS = {"grad_gap": 0.012, "update_gap": 0.04,
                      "grad_error": 0.08}


def train_spec(workload="qwen15_4b.train_coded", traffic=None):
    """A training cell at CPU size; ``traffic`` swaps in another mix
    from ``bench/traffic/`` (such as one that no cell runs yet)."""
    spec = copy.deepcopy(harness.resolve(workload))
    if traffic is not None:
        spec["traffic"] = harness.load_json(
            harness.BENCH / "traffic" / f"{traffic}.json")
    spec["config"].update(SMALL_MODEL)
    spec["traffic"].update(seq_len=16, log_every=2,
                           limits=dict(SMALL_TRAIN_LIMITS))
    return spec


def serve_spec():
    """The serving cell that waits for its knee sweep (not yet in
    ``BENCHMARK.json``), at CPU size."""
    spec = harness.unlisted("deepseek_coder_33b", "serve_chat")
    spec["config"].update(SMALL_MODEL, num_attention_heads=8,
                          num_key_value_heads=2)
    tr = spec["traffic"]
    tr.update(slots=8, max_len=64, drain_cap_s=20)
    tr["arrivals"]["rate_per_s"] = 20.0
    tr["prompt_len"].update(median=8, min=4, max=24)
    tr["output_len"].update(median=4, min=2, max=8)
    return spec
