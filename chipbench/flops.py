"""Operations and bytes from shapes — the numerators of MFU and of a
kernel's roofline share. Nothing here reads the program or XLA's cost
analysis: a count that moved with the program (rematerialisation, a
fusion, a custom call that hides its FLOPs) could not be a yardstick.

Convention: one multiply-add is 2 operations; a training step is the
forward pass plus twice as much for the backward pass (3x forward);
recomputed operations are not counted; causal attention is counted at
half of the full score matrix.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip. A device that is not in
    ``peaks.json`` is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       "add it to chipbench/peaks.json with its source")
    return table[device_kind]


def transformer_forward_flops(*, rows: int, seq: int, hidden: int,
                              heads: int, ffn: int, vocab: int, layers: int,
                              causal: bool) -> dict:
    """Forward matmul operations of ``rows`` sequences of ``seq`` tokens,
    by class. The vocabulary projection is counted at every position,
    which is what both model families compute."""
    tokens = rows * seq
    head_dim = hidden // heads
    attn = 2 * rows * heads * seq * seq * head_dim * layers
    if causal:
        attn //= 2
    return {
        "qkv_proj": 2 * tokens * hidden * 3 * hidden * layers,
        "attn_scores": attn,
        "attn_values": attn,
        "out_proj": 2 * tokens * hidden * hidden * layers,
        "ffn": 2 * tokens * hidden * ffn * 2 * layers,
        "vocab_proj": 2 * tokens * hidden * vocab,
    }


def transformer_train_flops(**shape) -> int:
    return 3 * sum(transformer_forward_flops(**shape).values())


def attention_kernel_cost(*, rows: int, seq: int, heads: int, head_dim: int,
                          layers: int, causal: bool, dtype_bytes: int) -> dict:
    """What the attention kernels of one training step must do at the
    least, over all layers: forward (QK^T, PV) plus backward (dV, dP, dQ,
    dK, and the recomputed QK^T every flash backward needs, which IS
    counted: without the O(L^2) matrix in memory the algorithm cannot
    avoid it) = 2 + 5 matmuls of 2*L*L*d each per head; and the bytes of
    q, k, v, o read or written once forward, and q, k, v, o, do read and
    dq, dk, dv written once backward."""
    one = 2 * rows * heads * seq * seq * head_dim * layers
    if causal:
        one //= 2
    tensor = rows * heads * seq * head_dim * dtype_bytes * layers
    return {"flops": 7 * one, "bytes": (4 + 8) * tensor}


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = cost["flops"] / peaks["flops_bf16"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
