"""Operations and bytes of the ``lfm2`` family from shapes — the
numerators of ``model.conv_moe_mfu_pct``, ``conv.mix_roofline_pct``,
``attn.gqa_roofline_pct`` and ``moe.rows_gmm_roofline_pct``. As
``flops.py``: nothing here reads the program or XLA's cost analysis; one
multiply-add is 2 operations; a training step is 3x the forward pass;
recomputed operations (the configuration's ``remat``) are not counted.

A shape names the layers held by kind, twice: by operator
(``conv_layers`` + ``attn_layers``) and by feed-forward (``dense_layers`` +
``expert_layers``); the two sums are equal. By class, for ``rows`` rows of
``seq`` positions:

- a ``conv`` operator's two projections (``hidden x 3 hidden`` and
  ``hidden x hidden``) at every position, and the convolution itself as
  vector operations: ``2 x taps x hidden`` a position (the gates' two
  multiplications are not counted);
- grouped-query attention's four projections at every position, its
  scores and values over the ALLOWED causal pairs only, ``seq (seq + 1) /
  2`` a head and row;
- the dense SwiGLU and the router at every position of their layers;
- routed experts over the pairs HELD here, at the uniform-routing
  expectation (``flops_sdar.py``'s convention);
- the tied head at every position, once.
"""

from __future__ import annotations

from chipbench.flops_sdar import grouped_matmul_cost
# the causal pairs a layer and the expected pairs held, as for ``xing``
from chipbench.flops_xing import allowed_pairs, pairs_held  # noqa: F401


def forward_flops(*, rows: int, seq: int, hidden: int, heads: int,
                  kv_heads: int, head_dim: int, ffn: int, expert_width: int,
                  experts: int, experts_held: int, top_k: int, taps: int,
                  vocab: int, conv_layers: int, attn_layers: int,
                  dense_layers: int, expert_layers: int) -> dict:
    if conv_layers + attn_layers != dense_layers + expert_layers:
        raise ValueError("the layers by operator and by feed-forward differ")
    tokens = rows * seq
    pairs = allowed_pairs(rows=rows, seq=seq, heads=heads)
    held = pairs_held(rows=rows, seq=seq, top_k=top_k, experts=experts,
                      experts_held=experts_held)
    return {
        "conv_proj": 2 * tokens * hidden * 4 * hidden * conv_layers,
        "conv_mix": 2 * tokens * taps * hidden * conv_layers,
        "qkv_proj": 2 * tokens * hidden * (heads + 2 * kv_heads) * head_dim
        * attn_layers,
        "out_proj": 2 * tokens * heads * head_dim * hidden * attn_layers,
        "attn_scores": 2 * pairs * head_dim * attn_layers,
        "attn_values": 2 * pairs * head_dim * attn_layers,
        "dense_ffn": 2 * tokens * hidden * ffn * 3 * dense_layers,
        "router": 2 * tokens * hidden * experts * expert_layers,
        "experts": 2 * held * 3 * hidden * expert_width * expert_layers,
        "vocab_proj": 2 * tokens * hidden * vocab,
    }


def train_flops(**shape) -> float:
    return 3 * sum(forward_flops(**shape).values())


def short_conv_cost(*, rows: int, seq: int, hidden: int, taps: int,
                    conv_layers: int, dtype_bytes: int, **_) -> dict:
    """The least the gated short convolutions of one training step must
    move, whatever implements them, over all ``conv`` layers. Forward:
    ``B``, ``C``, ``u`` read and the result written, 4 values of
    ``hidden`` a position. Backward: those three and the result's gradient
    read, the three gradients written: 7. The operations are the tap sums
    and the two gates, forward and twice that backward (vector work: the
    bound is the memory's)."""
    tokens = rows * seq
    return {"flops": 3 * tokens * hidden * (2 * taps + 2) * conv_layers,
            "bytes": (4 + 7) * tokens * hidden * dtype_bytes * conv_layers}


def gqa_attention_kernel_cost(*, rows: int, seq: int, heads: int,
                              kv_heads: int, head_dim: int, attn_layers: int,
                              dtype_bytes: int, **_) -> dict:
    """The least the three causal flash kernels of one training step must
    do, over all attention layers: 2 + 5 products of 2 * head_dim
    operations over the allowed pairs (as ``flops.attention_kernel_cost``),
    and q, o, do, dq once each way over ``heads`` (6 tensors) and k, v, dk,
    dv over ``kv_heads`` (6 tensors), each of ``seq`` positions."""
    one = 2 * allowed_pairs(rows=rows, seq=seq, heads=heads) * head_dim
    per_head = rows * seq * head_dim * dtype_bytes
    return {"flops": 7 * one * attn_layers,
            "bytes": 6 * (heads + kv_heads) * per_head * attn_layers}


def rows_grouped_matmul_cost(*, rows: int, seq: int, hidden: int,
                             expert_width: int, experts: int,
                             experts_held: int, top_k: int,
                             expert_layers: int, dtype_bytes: int,
                             positions_per_row: int | None = None,
                             **_) -> dict:
    """The least the grouped products of one training step must do,
    through the SHAPE alone, for any family whose shape carries these
    keys: 3 matrices (gate, up, down) x 3 passes of 2 * hidden *
    expert_width operations a held pair; each of the 9 products reads or
    writes its rows once on either side and the held experts' matrix once
    (``flops_sdar.grouped_matmul_cost``'s count). A row runs as
    ``positions_per_row`` positions (``seq`` unless the family doubles its
    rows)."""
    return grouped_matmul_cost(
        hidden=hidden, expert_width=expert_width, experts_held=experts_held,
        layers=expert_layers, dtype_bytes=dtype_bytes,
        pairs=pairs_held(rows=rows, seq=positions_per_row or seq, top_k=top_k,
                         experts=experts, experts_held=experts_held))
