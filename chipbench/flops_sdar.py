"""Operations and bytes of the ``sdar_moe`` family from shapes — the
numerators of ``model.moe_mfu_pct``, ``attn.bd_roofline_pct`` and
``moe.gmm_roofline_pct``. As ``flops.py``: nothing here reads the program
or XLA's cost analysis; one multiply-add is 2 operations; a training step
is 3x the forward pass; recomputed operations (the configuration's
``remat``) are not counted.

A row of ``seq`` data tokens runs as ``2 * seq`` positions (noised copy
and clean copy). By class, for ``rows`` rows:

- projections (q, k, v, o) and the router over all ``2 * seq`` positions;
- attention over the ALLOWED pairs only: ``seq^2 + seq * block`` a row and
  head (noised-noised ``seq * block``, noised-clean ``seq * (seq - block)
  / 2``, clean-clean ``seq * (seq + block) / 2``), a quarter of the
  ``(2 * seq)^2`` score matrix;
- experts over the pairs HELD here, at the uniform-routing expectation
  ``positions * top_k * experts_held / experts`` (a stated convention: the
  measured pairs a step are in the run's counters, and a router that
  concentrates on the held experts would do more work than is counted);
- the head over the ``seq`` noised positions of a row only.
"""

from __future__ import annotations


def pairs_held(*, rows: int, seq: int, top_k: int, experts: int,
               experts_held: int, **_) -> float:
    """Expected (position, expert) pairs routed to the experts held here,
    a layer, under uniform routing."""
    return rows * 2 * seq * top_k * experts_held / experts


def allowed_pairs(*, rows: int, seq: int, block: int, heads: int, **_) -> int:
    """(query, key) pairs the block-diffusion mask allows, all heads."""
    return rows * heads * (seq * seq + seq * block)


def forward_flops(*, rows: int, seq: int, block: int, hidden: int, heads: int,
                  kv_heads: int, head_dim: int, expert_width: int,
                  experts: int, experts_held: int, top_k: int, vocab: int,
                  layers: int) -> dict:
    positions = rows * 2 * seq
    attn = 2 * allowed_pairs(rows=rows, seq=seq, block=block,
                             heads=heads) * head_dim * layers
    held = pairs_held(rows=rows, seq=seq, top_k=top_k, experts=experts,
                      experts_held=experts_held)
    return {
        "qkv_proj": 2 * positions * hidden * (heads + 2 * kv_heads)
        * head_dim * layers,
        "out_proj": 2 * positions * heads * head_dim * hidden * layers,
        "router": 2 * positions * hidden * experts * layers,
        "attn_scores": attn,
        "attn_values": attn,
        "experts": 2 * held * 3 * hidden * expert_width * layers,
        "vocab_proj": 2 * rows * seq * hidden * vocab,
    }


def train_flops(**shape) -> float:
    return 3 * sum(forward_flops(**shape).values())


def bd_attention_kernel_cost(*, rows: int, seq: int, block: int, heads: int,
                             kv_heads: int, head_dim: int, layers: int,
                             dtype_bytes: int, **_) -> dict:
    """The least the three masked flash kernels of one training step must
    do, over all layers: 2 + 5 products of 2 * head_dim operations over
    the allowed pairs (as ``flops.attention_kernel_cost``), and q, o, do,
    dq once each way over ``heads`` (6 tensors) and k, v, dk, dv over
    ``kv_heads`` (6 tensors), each of 2 * seq positions."""
    one = 2 * allowed_pairs(rows=rows, seq=seq, block=block,
                            heads=heads) * head_dim * layers
    per_head = rows * 2 * seq * head_dim * dtype_bytes * layers
    return {"flops": 7 * one, "bytes": 6 * (heads + kv_heads) * per_head}


def grouped_matmul_cost(*, hidden: int, expert_width: int, experts_held: int,
                        layers: int, dtype_bytes: int, pairs: float | None = None,
                        **shape) -> dict:
    """The least the grouped products of one training step must do,
    whatever implements them: 3 matrices (gate, up, down) x 3 passes
    (forward, the gradient of the rows, the gradient of the matrices) of
    2 * hidden * expert_width operations a pair; each of the 9 products
    reads or writes its rows once on either side (hidden + expert_width
    values a pair) and the held experts' matrix once."""
    if pairs is None:
        pairs = pairs_held(experts_held=experts_held, **shape)
    weights = experts_held * hidden * expert_width
    return {"flops": 9 * 2 * pairs * hidden * expert_width * layers,
            "bytes": 9 * (pairs * (hidden + expert_width) + weights)
            * dtype_bytes * layers}
