"""Expert parallelism: GShard-style top-k MoE over a mesh axis
(top-1 Switch gate by default; ``top_k=2`` is the classic GShard gate
with the chosen experts' probs renormalized per token).

No reference analog (SURVEY §2.5: EP absent — out of reference scope) —
added to complete the parallelism matrix (DP × SP × TP × PP × EP). The
design is the canonical TPU one (Lepikhin et al. 2020, GShard,
arXiv:2006.16668 — public technique): static-shape capacity-limited
dispatch so XLA sees fixed tensors, and ``lax.all_to_all`` over the
expert axis as the only collective — the exact op class the reference's
MPI stack explored but never shipped (``test_mpi.py:20`` Ialltoallv).

Shapes (inside ``shard_map`` with ``expert_axis`` of size D bound):

- tokens ``x [n_loc, d]`` — this device's slice of the batch.
- every device holds ``e_loc = E // D`` experts' FFN weights, stacked on
  a leading local axis (host-side ``[E, ...]`` sharded ``P(expert_axis)``).
- router weights ``wr [d, E]`` replicated.

Per device: route → build per-expert capacity buffers ``[E, C, d]`` →
``all_to_all`` (each device sends every other device the buffer slots of
THAT device's experts, receives its own experts' tokens from everyone)
→ run local experts → ``all_to_all`` back → combine with the gate.

Capacity semantics: ``C`` is per **(expert, source device)** — each
device dispatches at most C of ITS tokens to any one expert, so an
expert serves up to ``n_dev * C`` tokens per step and the dispatch/
all_to_all buffers are ``[E, C, d]`` *per device*. Sizing against a
GShard-style global per-expert budget B means ``capacity = B / n_dev``.
Overflowing tokens are dropped (output 0 for them — GShard semantics);
size C generously in tests to compare exactly against the dense oracle.

What a benchmark configuration uses is NOT this path but the dropless
layer of ``parallel/dropless.py`` (a chip is told which experts it holds,
routes over all of them, computes its own experts' part for every pair
routed to them through grouped products, and never drops one); it runs
on one chip without its exchange. This capacity-drop Switch/GShard path
is the starting point of that exchange across four chips (its
``all_to_all`` pair is the only collective either needs), and has never
run on the chip.

Like ``parallel/pp.py``: wrap in a vma-checked ``shard_map`` (the default
``check_vma=True``) when differentiating, so the collective transposes
are exact; shard tokens over the expert axis (or jointly over data ×
expert — the GShard layout) so each device contributes its own slice.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any


def init_moe(key, d: int, f: int, n_experts: int, scale: float = 0.1) -> PyTree:
    """Host-side MoE params: router (replicated) + per-expert FFN weights
    stacked on a leading ``[E]`` axis for ``P(expert_axis)`` sharding."""
    kr, k1, k2 = jax.random.split(key, 3)
    return {
        "wr": scale * jax.random.normal(kr, (d, n_experts), jnp.float32),
        "w1": scale * jax.random.normal(k1, (n_experts, d, f), jnp.float32),
        "w2": scale * jax.random.normal(k2, (n_experts, f, d), jnp.float32),
    }


def moe_spec(params: PyTree, expert_axis: str):
    from jax.sharding import PartitionSpec as P

    return {
        "wr": P(),
        "w1": P(expert_axis),
        "w2": P(expert_axis),
    }


def _route_top1(x, wr) -> Tuple[jax.Array, jax.Array]:
    """(expert index, gate) per token — softmax prob of the argmax."""
    probs = jax.nn.softmax(x @ wr, axis=-1)          # [n, E]
    eidx = jnp.argmax(probs, axis=-1)                # [n]
    gate = jnp.take_along_axis(probs, eidx[:, None], axis=1)[:, 0]
    return eidx, gate


def _route_topk(x, wr, k: int) -> Tuple[jax.Array, jax.Array]:
    """(expert indices [n, k], gates [n, k]) — softmax probs of the
    top-k experts, renormalized to sum to 1 per token (the GShard top-2
    convention: the chosen experts split the token's whole weight)."""
    probs = jax.nn.softmax(x @ wr, axis=-1)          # [n, E]
    gates, eidx = lax.top_k(probs, k)                # [n, k] each
    gates = gates / jnp.maximum(gates.sum(axis=-1, keepdims=True), 1e-9)
    return eidx, gates


def _dispatch_combine(x, eidx_k, gate_k, w1, w2, expert_axis, capacity):
    """Dispatch→expert→combine for a top-k assignment in ONE all_to_all
    round trip: choice rank c writes its tokens into slots
    ``[c*C, (c+1)*C)`` of a single ``[E, k*C, d]`` buffer (each choice
    has its own independent capacity budget, so a token can lose its
    2nd choice to capacity while keeping its 1st), the experts process
    all k*C slots together, and each choice combines from its slice.
    k=1 reduces exactly to the original top-1 machinery; k>1 costs the
    same two all_to_all launches per layer, not 2k.

    ``eidx_k``/``gate_k``: [n, k]."""
    n_loc, d = x.shape
    k = eidx_k.shape[1]
    n_dev = lax.axis_size(expert_axis)
    e_loc = w1.shape[0]
    n_experts = n_dev * e_loc

    buf = jnp.zeros((n_experts, k * capacity, d), x.dtype)
    keeps, slots = [], []
    for c in range(k):
        eidx = eidx_k[:, c]
        # slot of each token within its expert's capacity buffer for THIS
        # choice rank (among this device's tokens): running count of
        # same-expert tokens before it
        onehot = jax.nn.one_hot(eidx, n_experts, dtype=jnp.int32)   # [n, E]
        pos = jnp.cumsum(onehot, axis=0) * onehot                    # 1-based
        slot0 = pos.max(axis=1) - 1                                  # [n]
        keep = (slot0 >= 0) & (slot0 < capacity)
        slot = jnp.clip(slot0, 0, capacity - 1)
        buf = buf.at[eidx, c * capacity + slot].add(
            jnp.where(keep[:, None], x, jnp.zeros_like(x))
        )
        keeps.append(keep)
        slots.append(slot)

    # one all_to_all over the expert axis: send device j its experts'
    # slots (all k choices at once), receive my experts' tokens
    buf = buf.reshape(n_dev, e_loc, k * capacity, d)
    recv = lax.all_to_all(buf, expert_axis, split_axis=0, concat_axis=0)
    # [n_dev, e_loc, k*C, d] — recv[j] = device j's tokens for MY experts

    tok = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_dev * k * capacity, d)
    h = jax.nn.gelu(jnp.einsum("etd,edf->etf", tok, w1))
    y = jnp.einsum("etf,efd->etd", h, w2)
    y = y.reshape(e_loc, n_dev, k * capacity, d).transpose(1, 0, 2, 3)

    # return trip: outputs for device j's tokens go back to device j
    back = lax.all_to_all(y, expert_axis, split_axis=0, concat_axis=0)
    out_buf = back.reshape(n_experts, k * capacity, d)

    # combine: each kept (token, choice) reads its slot, scaled by gate
    out = jnp.zeros_like(x)
    for c in range(k):
        tok_out = out_buf[eidx_k[:, c], c * capacity + slots[c]]
        tok_out = tok_out * gate_k[:, c][:, None]
        out = out + jnp.where(keeps[c][:, None], tok_out,
                              jnp.zeros_like(tok_out))
    return out


def moe_apply(
    x: jax.Array,
    params: Dict[str, jax.Array],
    expert_axis: str,
    *,
    capacity: int,
    top_k: int = 1,
) -> jax.Array:
    """Top-k MoE forward for this device's tokens (default top-1, the
    Switch/GShard-minimal config; ``top_k=2`` is the classic GShard
    gate with the chosen experts' probs renormalized per token).

    Returns ``[n_loc, d]``: each token's gated expert output (zeros for
    capacity-dropped choices). Differentiable end to end — the dispatch/
    combine are scatter-adds/gathers and the collective is all_to_all
    (whose transpose is the reverse all_to_all). Each choice rank owns
    an independent capacity budget inside ONE shared ``[E, k*C, d]``
    buffer (2x the slots at top-2 — GShard's budget), so a token can
    lose its 2nd choice to capacity while keeping its 1st — and every
    layer pays exactly one all_to_all round trip regardless of k.
    """
    w1, w2 = params["w1"], params["w2"]         # [e_loc, d, f], [e_loc, f, d]
    n_dev = lax.axis_size(expert_axis)
    assert params["wr"].shape[1] == n_dev * w1.shape[0], (
        params["wr"].shape, n_dev, w1.shape)
    if top_k == 1:
        eidx, gate = _route_top1(x, params["wr"])
        eidx_k, gate_k = eidx[:, None], gate[:, None]
    else:
        eidx_k, gate_k = _route_topk(x, params["wr"], top_k)
    return _dispatch_combine(x, eidx_k, gate_k, w1, w2, expert_axis, capacity)


def load_balance_loss(x: jax.Array, wr: jax.Array, top_k: int = 1,
                      expert_axis: str = None) -> jax.Array:
    """Switch/GShard auxiliary load-balancing loss for this device's
    tokens: ``E * sum_e f_e * P_e`` where ``f_e`` is the fraction of
    (token, choice) assignments routed to expert e and ``P_e`` the mean
    router probability of e (Fedus et al. 2021 eq. 4; Lepikhin et al.
    2020 §3.2 — public techniques). Minimized (value 1.0) at a perfectly
    uniform assignment; without it the router collapses onto a few
    experts and the capacity buffers drop everything else.

    Differentiable through ``P_e`` (the f_e counts are stop-gradient
    by construction — argmax/top_k are non-differentiable). With
    ``expert_axis`` bound, f/P are psum-averaged so every device
    penalizes the GLOBAL balance, not its local slice. The router
    forward here duplicates the dispatch path's textually, but under
    jit XLA's common-subexpression elimination merges the identical
    ``x @ wr`` / softmax; ``lax.top_k`` breaks ties lowest-index-first
    exactly like ``_route_top1``'s argmax, so the assignment counted is
    the assignment dispatched."""
    probs = jax.nn.softmax(x @ wr, axis=-1)              # [n, E]
    n_experts = wr.shape[1]
    _, eidx = lax.top_k(probs, top_k)                    # [n, k]
    counts = jax.nn.one_hot(eidx, n_experts, dtype=probs.dtype).sum(
        axis=(0, 1))                                     # [E]
    n_assign = jnp.asarray(eidx.size, probs.dtype)
    p_mean = probs.mean(axis=0)                          # [E]
    if expert_axis is not None:
        counts = lax.psum(counts, expert_axis)
        n_assign = lax.psum(n_assign, expert_axis)
        p_mean = lax.pmean(p_mean, expert_axis)
    f = counts / jnp.maximum(n_assign, 1.0)
    return n_experts * jnp.sum(f * p_mean)


def moe_dense_oracle(x: jax.Array, params: Dict[str, jax.Array],
                     top_k: int = 1) -> jax.Array:
    """Single-device reference: every token through its own top-k
    expert(s) (no capacity limit) — the equality oracle for tests AND
    the dense fallback ``models/moe.py`` runs outside ``shard_map``.

    Computes all experts for all tokens and combines with a one-hot
    select (n·E·f work) rather than gathering per-token weight copies: a
    ``w1[eidx]`` gather materializes ``[n, d, f]`` — 4.3 GB per layer at
    8K tokens for BERT-ish sizes — while the all-experts activations are
    ``[n, E, f]``, ~30x smaller there. Gradients are identical: the
    one-hot zeroes non-selected experts' paths exactly like the gather.
    """
    h = jax.nn.gelu(jnp.einsum("td,edf->tef", x, params["w1"]))
    y_all = jnp.einsum("tef,efd->ted", h, params["w2"])
    n_experts = params["wr"].shape[1]
    if top_k == 1:
        eidx, gate = _route_top1(x, params["wr"])
        onehot = jax.nn.one_hot(eidx, n_experts, dtype=x.dtype)
        return jnp.einsum("ted,te->td", y_all, onehot) * gate[:, None]
    eidx, gates = _route_topk(x, params["wr"], top_k)
    out = jnp.zeros_like(x)
    for c in range(top_k):
        onehot = jax.nn.one_hot(eidx[:, c], n_experts, dtype=x.dtype)
        out = out + (jnp.einsum("ted,te->td", y_all, onehot)
                     * gates[:, c][:, None])
    return out
