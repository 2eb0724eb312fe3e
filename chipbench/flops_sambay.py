"""Operations and bytes of the ``sambay`` family from shapes — the
numerators of ``model.ssm_mfu_pct``, ``attn.diff_roofline_pct`` and
``ssm.scan_roofline_pct``. As ``flops.py``: nothing here reads the program
or XLA's cost analysis; one multiply-add is 2 operations; a training step
is 3x the forward pass; recomputed operations (the configuration's
``remat``) are not counted.

A shape names how many layers of each kind the configuration holds
(``mamba_layers`` counts ``mamba`` and ``mamba_memory``; ``full_layers``
and ``cross_layers`` attend over all earlier positions, ``window_layers``
over the last ``window``; only ``cross_layers`` have no key-value
projection); every layer has the MLP. By class, for ``rows`` rows of
``seq`` positions:

- differential attention over the ALLOWED pairs only: ``seq (seq + 1) /
  2`` a softmax map under the causal mask, ``window (window + 1) / 2 +
  (seq - window) window`` under the window; ``heads`` maps a layer, each
  a product of ``head_dim`` (scores) and one of ``2 head_dim`` (the
  joined values);
- the recurrence at 6 operations a step, channel and state (``Dt A``,
  the decay times the state, ``Dt x B``, the sum, ``s C`` and its sum):
  vector work, a thousandth of the step's operations;
- the head at every position.
"""

from __future__ import annotations


def attention_pairs(*, seq: int, window: int | None = None) -> int:
    """(query, key) pairs a softmax map allows."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def forward_flops(*, rows: int, seq: int, hidden: int, ffn: int, heads: int,
                  kv_heads: int, head_dim: int, window: int, d_inner: int,
                  d_state: int, dt_rank: int, d_conv: int, vocab: int,
                  mamba_layers: int, gmu_layers: int, window_layers: int,
                  full_layers: int, cross_layers: int) -> dict:
    tokens = rows * seq
    attention_layers = window_layers + full_layers + cross_layers
    layers = mamba_layers + gmu_layers + attention_layers
    pairs = rows * heads * (
        (full_layers + cross_layers) * attention_pairs(seq=seq)
        + window_layers * attention_pairs(seq=seq, window=window))
    return {
        "mlp": 2 * tokens * hidden * ffn * 3 * layers,
        "ssm_proj": 2 * tokens * mamba_layers * (
            hidden * 2 * d_inner + d_inner * (dt_rank + 2 * d_state)
            + dt_rank * d_inner + d_inner * hidden + d_inner * d_conv),
        "ssm_scan": 6 * tokens * d_inner * d_state * mamba_layers,
        "gmu": 2 * tokens * 2 * hidden * d_inner * gmu_layers,
        "q_o_proj": 2 * tokens * 2 * hidden * heads * head_dim
        * attention_layers,
        "kv_proj": 2 * tokens * hidden * 2 * kv_heads * head_dim
        * (window_layers + full_layers),
        "attn_scores": 2 * pairs * head_dim,
        "attn_values": 2 * pairs * 2 * head_dim,
        "vocab_proj": 2 * tokens * hidden * vocab,
    }


def train_flops(**shape) -> int:
    return 3 * sum(forward_flops(**shape).values())


def diff_attention_kernel_cost(*, rows: int, seq: int, heads: int,
                               kv_heads: int, head_dim: int, window: int,
                               window_layers: int, full_layers: int,
                               cross_layers: int, dtype_bytes: int,
                               **_) -> dict:
    """The least the flash kernels of one training step must do, over the
    three kinds of attention layer: 2 + 5 products over the allowed
    pairs of ``heads`` softmax maps — the scores, their recomputation,
    dq and dk at ``head_dim``; the values, dv and dp at ``2 head_dim``:
    10 x ``2 pairs head_dim`` — and, of ``seq`` positions each way, q, o,
    do, dq once each over the ``heads / 2`` query pairs (forward 2,
    backward 4: 6 tensors ``2 head_dim`` wide a pair; the output counted
    AFTER the subtraction, one a pair) and k, v, dk, dv over the
    ``kv_heads / 2`` key-value pairs (6 tensors)."""
    pairs = rows * heads * (
        (full_layers + cross_layers) * attention_pairs(seq=seq)
        + window_layers * attention_pairs(seq=seq, window=window))
    layers = window_layers + full_layers + cross_layers
    per_pair = rows * seq * 2 * head_dim * dtype_bytes * layers
    return {"flops": 10 * 2 * pairs * head_dim,
            "bytes": 6 * (heads // 2 + kv_heads // 2) * per_pair}


def scan_cost(*, rows: int, seq: int, d_inner: int, d_state: int,
              mamba_layers: int, dtype_bytes: int, **_) -> dict:
    """The least the selective scans of one training step must move,
    whatever implements them: forward, xh (the compute dtype), Dt, B, C,
    A, D read and y written once (float32); backward, those and dy read
    and the gradients of xh, Dt, B, C (and of A and D) written once. The
    operations are the recurrence's 6 a step, channel and state, three
    times (vector work: the bound is the memory's)."""
    wide = rows * seq * d_inner
    narrow = 2 * rows * seq * d_state * 4 + (d_inner * d_state + d_inner) * 4
    forward = wide * (dtype_bytes + 4 + 4) + narrow
    backward = wide * (dtype_bytes + 4 + 4 + 4 + 4) + 2 * narrow
    return {"flops": 3 * 6 * wide * d_state * mamba_layers,
            "bytes": (forward + backward) * mamba_layers}
