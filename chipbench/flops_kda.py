"""Operations and bytes of the ``solar`` family from shapes — the
numerators of ``model.kda_moe_mfu_pct`` and ``kda.scan_roofline_pct``. As
``flops.py``: nothing here reads the program or XLA's cost analysis; one
multiply-add is 2 operations; a training step is 3x the forward pass;
recomputed operations (the configuration's ``remat``) are not counted.

A shape names the layers held by operator, ``kda_layers`` (the gated delta
rule over ``kda_heads`` heads of ``dk`` keys and ``dv`` values) and
``attn_layers`` (grouped-query attention of ``heads`` over ``kv_heads``
heads of ``head_dim``, with an output gate), and ``expert_layers`` (every
layer: router, ``shared_experts`` shared SwiGLUs and the routed experts
held, all of ``expert_width``). By class, for ``rows`` rows of ``seq``
positions:

- the delta rule's projections at every position: q, k and v, the output,
  beta (``hidden x heads``), and the low-rank decay and gate (``hidden x
  dk`` then ``dk x heads dk`` each); its convolutions as vector work,
  ``2 x taps`` a channel of q, k and v;
- the delta rule itself in its chunked form at ``chunk`` C
  (``kda_scan_cost``): a chunk and head computes A (``C (C - 1) / 2`` key
  pairs of ``dk``), P (``C (C + 1) / 2`` pairs), one triangular solve
  against ``dk + dv`` columns, and four products with the ``dk x dv``
  state (``W S``, ``K^T U``, ``Q S``) and ``P U``;
- attention's five projections at every position, its scores and values
  over the ALLOWED causal pairs only, ``seq (seq + 1) / 2`` a head and row;
- the router and the shared experts at every position, the routed experts
  over the pairs HELD here at the uniform-routing expectation
  (``flops_sdar.py``'s convention);
- the untied head at every position, once.
"""

from __future__ import annotations

from chipbench.flops_xing import allowed_pairs, pairs_held


def chunks(*, seq: int, chunk: int, **_) -> int:
    return -(-seq // chunk)


def scan_forward_flops(*, rows: int, seq: int, kda_heads: int, dk: int,
                       dv: int, chunk: int, kda_layers: int, **_) -> int:
    """The chunked delta rule's forward operations, every ``kda`` layer."""
    c = chunk
    per_chunk = 2 * (c * (c - 1) // 2 * dk + c * (c + 1) // 2 * dk
                     + c * (c - 1) // 2 * (dk + dv) + 3 * c * dk * dv
                     + c * (c + 1) // 2 * dv)
    return (per_chunk * chunks(seq=seq, chunk=chunk) * rows * kda_heads
            * kda_layers)


def forward_flops(*, rows: int, seq: int, hidden: int, heads: int,
                  kv_heads: int, head_dim: int, kda_heads: int, dk: int,
                  dv: int, chunk: int, taps: int, expert_width: int,
                  experts: int, experts_held: int, top_k: int,
                  shared_experts: int, vocab: int, kda_layers: int,
                  attn_layers: int, expert_layers: int,
                  conv_layers: int = 0) -> dict:
    if kda_layers + attn_layers != expert_layers or conv_layers:
        raise ValueError("every layer is a delta-rule or an attention layer "
                         "with an expert layer")
    tokens = rows * seq
    wide_k, wide_v = kda_heads * dk, kda_heads * dv
    pairs = allowed_pairs(rows=rows, seq=seq, heads=heads)
    held = pairs_held(rows=rows, seq=seq, top_k=top_k, experts=experts,
                      experts_held=experts_held)
    return {
        "kda_proj": 2 * tokens * (hidden * (2 * wide_k + wide_v)
                                  + wide_v * hidden + hidden * kda_heads
                                  + 2 * (hidden * dk + dk * wide_k))
        * kda_layers,
        "kda_conv": 2 * tokens * taps * (2 * wide_k + wide_v) * kda_layers,
        "kda_scan": scan_forward_flops(rows=rows, seq=seq, kda_heads=kda_heads,
                                       dk=dk, dv=dv, chunk=chunk,
                                       kda_layers=kda_layers),
        "qkv_proj": 2 * tokens * hidden * (2 * heads + 2 * kv_heads)
        * head_dim * attn_layers,
        "out_proj": 2 * tokens * heads * head_dim * hidden * attn_layers,
        "attn_scores": 2 * pairs * head_dim * attn_layers,
        "attn_values": 2 * pairs * head_dim * attn_layers,
        "router": 2 * tokens * hidden * experts * expert_layers,
        "shared": 2 * tokens * 3 * hidden * expert_width * shared_experts
        * expert_layers,
        "experts": 2 * held * 3 * hidden * expert_width * expert_layers,
        "vocab_proj": 2 * tokens * hidden * vocab,
    }


def train_flops(**shape) -> float:
    return 3 * sum(forward_flops(**shape).values())


def kda_scan_cost(*, rows: int, seq: int, kda_heads: int, dk: int, dv: int,
                  chunk: int, kda_layers: int, dtype_bytes: int, **_) -> dict:
    """The least the chunked delta rule of one training step must do and
    move at chunk C, whatever implements it, over all ``kda`` layers: 3x
    its forward operations; forward, q, k, v (``dtype_bytes``), the
    float32 log-decays and betas read and the float32 output written, and
    the state at each chunk's start written (float32, ``dk x dv``);
    backward, those inputs and the output's gradient and the states read,
    the inputs' gradients written."""
    inputs = (2 * dk + dv) * dtype_bytes + 4 * dk + 4
    per_position = inputs + 4 * dv + (inputs + 4 * dv) + inputs
    states = chunks(seq=seq, chunk=chunk) * dk * dv * 4
    n = rows * kda_heads * kda_layers
    return {"flops": 3 * scan_forward_flops(
                rows=rows, seq=seq, kda_heads=kda_heads, dk=dk, dv=dv,
                chunk=chunk, kda_layers=kda_layers),
            "bytes": n * (seq * per_position + 2 * states)}
