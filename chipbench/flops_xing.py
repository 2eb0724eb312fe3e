"""Operations and bytes of the ``xing`` family from shapes — the
numerators of ``model.mla_moe_mfu_pct``, ``attn.mla_roofline_pct`` and
``hc.mix_roofline_pct``. As ``flops.py``: nothing here reads the program
or XLA's cost analysis; one multiply-add is 2 operations; a training step
is 3x the forward pass; recomputed operations (the configuration's
``remat``) are not counted.

A shape names the layers held by kind: ``dense_layers`` (latent attention +
a SwiGLU of ``ffn``), ``expert_layers`` (latent attention + router + shared
expert + the routed experts held) and ``mtp_modules`` (each one expert
layer more, a joining product of ``2 hidden x hidden`` and the head once
more). By class, for ``rows`` rows of ``seq`` positions:

- latent attention's five projections at every position; its scores over
  the ALLOWED causal pairs only, ``seq (seq + 1) / 2`` a head and row, at
  ``nope_dim + rope_dim`` wide keys and a ``v_dim`` wide value;
- the hyper-connections, two a layer: the mixing weights' product of the
  ``streams x hidden`` wide normalised stream with ``2 streams +
  streams^2`` columns, and the two mixes (``streams + streams^2 +
  streams`` multiply-adds a value of ``hidden``): vector work, a
  hundredth of the step's operations;
- routed experts over the pairs HELD here, at the uniform-routing
  expectation (``flops_sdar.py``'s convention);
- the head at every position, once a loss.
"""

from __future__ import annotations


def attention_layers(*, dense_layers: int, expert_layers: int,
                     mtp_modules: int, **_) -> int:
    return dense_layers + expert_layers + mtp_modules


def allowed_pairs(*, rows: int, seq: int, heads: int, **_) -> int:
    """(query, key) pairs the causal mask allows, all heads, a layer."""
    return rows * heads * seq * (seq + 1) // 2


def pairs_held(*, rows: int, seq: int, top_k: int, experts: int,
               experts_held: int, **_) -> float:
    """Expected (position, expert) pairs routed to the experts held here,
    a layer, under uniform routing."""
    return rows * seq * top_k * experts_held / experts


def forward_flops(*, rows: int, seq: int, hidden: int, heads: int,
                  q_rank: int, kv_rank: int, nope_dim: int, rope_dim: int,
                  v_dim: int, ffn: int, expert_width: int, experts: int,
                  experts_held: int, top_k: int, shared_experts: int,
                  streams: int, vocab: int, dense_layers: int,
                  expert_layers: int, mtp_modules: int) -> dict:
    tokens = rows * seq
    attn = dense_layers + expert_layers + mtp_modules
    moe = expert_layers + mtp_modules
    pairs = allowed_pairs(rows=rows, seq=seq, heads=heads)
    held = pairs_held(rows=rows, seq=seq, top_k=top_k, experts=experts,
                      experts_held=experts_held)
    n = streams
    return {
        "q_proj": 2 * tokens * (hidden * q_rank + q_rank * heads * (
            nope_dim + rope_dim)) * attn,
        "kv_proj": 2 * tokens * (hidden * (kv_rank + rope_dim) + kv_rank
                                 * heads * (nope_dim + v_dim)) * attn,
        "out_proj": 2 * tokens * heads * v_dim * hidden * attn,
        "attn_scores": 2 * pairs * (nope_dim + rope_dim) * attn,
        "attn_values": 2 * pairs * v_dim * attn,
        "hc_weights": 2 * tokens * n * hidden * (2 * n + n * n) * 2 * attn,
        "hc_mixes": 2 * tokens * hidden * (n + n * n + n) * 2 * attn,
        "dense_ffn": 2 * tokens * hidden * ffn * 3 * dense_layers,
        "router": 2 * tokens * hidden * experts * moe,
        "shared_experts": 2 * tokens * hidden * expert_width * shared_experts
        * 3 * moe,
        "experts": 2 * held * 3 * hidden * expert_width * moe,
        "mtp_join": 2 * tokens * 2 * hidden * hidden * mtp_modules,
        "vocab_proj": 2 * tokens * hidden * vocab * (1 + mtp_modules),
    }


def train_flops(**shape) -> float:
    return 3 * sum(forward_flops(**shape).values())


def mla_attention_kernel_cost(*, rows: int, seq: int, heads: int,
                              nope_dim: int, rope_dim: int, v_dim: int,
                              dtype_bytes: int, **shape) -> dict:
    """The least the three flash kernels of one training step must do,
    over all latent-attention layers: 2 + 5 products over the allowed
    pairs (as ``flops.attention_kernel_cost``) — the scores, their one
    recomputation, dq and dk at ``nope_dim + rope_dim``; the values, dv
    and dp at ``v_dim`` — and, of ``seq`` positions a head each way, q, k
    (forward, backward) and dq, dk at the keys' width, v (twice), dv, o
    (written, read) and do at the value's: 6 tensors of each width."""
    layers = attention_layers(**shape)
    qk = nope_dim + rope_dim
    pairs = allowed_pairs(rows=rows, seq=seq, heads=heads)
    return {"flops": 2 * pairs * (4 * qk + 3 * v_dim) * layers,
            "bytes": 6 * rows * seq * heads * (qk + v_dim) * dtype_bytes
            * layers}


def hc_mix_cost(*, rows: int, seq: int, hidden: int, streams: int,
                dtype_bytes: int, **shape) -> dict:
    """The least the hyper-connections of one training step must move,
    whatever implements them, two a layer. Forward: the streams read once
    for the weights and ``u`` together, ``u`` written; the streams and the
    sub-layer's output read, the new streams written: ``3 streams + 2``
    values of ``hidden`` a position. Backward: the new streams' gradient,
    the streams and the output read, the output's gradient written; then
    ``u``'s gradient, the streams and the new streams' gradient read and
    the streams' gradient written: ``5 streams + 3``. The operations are
    the mixes' and the weights' product (vector work: the bound is the
    memory's)."""
    sub_layers = 2 * attention_layers(**shape)
    n, tokens = streams, rows * seq
    values = (3 * n + 2) + (5 * n + 3)
    flops = 3 * 2 * tokens * hidden * (n * (2 * n + n * n) + 2 * n + n * n)
    return {"flops": flops * sub_layers,
            "bytes": tokens * hidden * values * dtype_bytes * sub_layers}
