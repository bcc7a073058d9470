"""Operations and bytes worked out from shapes.

These are the benchmark's yardstick for model FLOP utilization and
kernel roofline shares. Each count is what the algorithm needs, not
what an implementation happens to do: recomputed activations, padded
vocabulary rows, and cache blocks past a row's length are not counted.

``dims`` is a plain dict with the model's sizes: ``n_layers``,
``d_model``, ``n_heads``, ``n_kv_heads``, ``head_dim``, ``d_ff`` and
``vocab_size``.
"""

from __future__ import annotations

BF16 = 2
F32 = 4


def matmul_params(dims: dict) -> int:
    """Weights that take part in a matmul per token: attention
    projections and the SwiGLU MLP in every layer, plus the LM head.
    The embedding is a lookup and counts no operations."""
    d, h, kvh, dh = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                     dims["head_dim"])
    attn = d * h * dh * 2 + d * kvh * dh * 2          # wq, wo; wk, wv
    mlp = 3 * d * dims["d_ff"]                         # gate, up, down
    return dims["n_layers"] * (attn + mlp) + d * dims["vocab_size"]


def attention_flops_fwd(dims: dict, context: int) -> int:
    """Forward attention matmuls (QK^T and PV) of one query token that
    attends to ``context`` positions, over all layers."""
    return 4 * dims["n_layers"] * dims["n_heads"] * dims["head_dim"] * context


def train_flops_per_token(dims: dict, seq_len: int) -> float:
    """Forward and backward model FLOPs per trained token of a causal
    sequence of ``seq_len``: 6 per matmul weight, and three times the
    forward attention over the mean causal context (seq_len + 1) / 2."""
    mean_context = (seq_len + 1) / 2
    return (6 * matmul_params(dims)
            + 3 * attention_flops_fwd(dims, 1) * mean_context)


def decode_flops(dims: dict, context: int) -> int:
    """Forward FLOPs of one served token whose row attends to
    ``context`` cached positions (itself included)."""
    return 2 * matmul_params(dims) + attention_flops_fwd(dims, context)


def rmsnorm_call(rows: int, d: int, act_bytes: int = BF16,
                 scale_bytes: int = F32) -> dict:
    """One RMSNorm call over ``rows`` rows of width ``d``: read x and
    the scale, write y; square, sum, normalise and scale each entry."""
    return {"flops": 4 * rows * d,
            "bytes": 2 * rows * d * act_bytes + d * scale_bytes}


def decode_attention_call(dims: dict, lengths, act_bytes: int = BF16,
                          cache_bytes: int = BF16) -> dict:
    """One decode-attention call of one layer over the rows whose valid
    cache lengths are ``lengths`` (rows serving no request left out):
    read each row's query, its valid K and V entries, write its output;
    QK^T and PV over each valid position."""
    h, kvh, dh = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    rows = len(lengths)
    positions = int(sum(lengths))
    return {"flops": 4 * h * dh * positions,
            "bytes": (2 * rows * h * dh * act_bytes
                      + 2 * positions * kvh * dh * cache_bytes)}


def roofline_seconds(call: dict, peaks: dict) -> tuple:
    """(least seconds, bound) for a call: the larger of operations over
    peak bf16 FLOP/s and bytes over peak HBM bytes/s."""
    t_flops = call["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = call["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops,
                                                          "compute")
