"""A dropless expert layer for the experts ONE chip holds.

What expert parallelism asks of a layer, without its exchange: the layer
is told which experts live here (``experts_held=(first, count)``), routes
every position over ALL experts (the router keeps its published width
and its experts per token), and computes its own experts' part of the
result for every (position, expert) pair routed to them. What the absent
experts would add is left out; the gate weights stay normalised over all
chosen experts, held or not. Across chips the same function runs on each
share and the parts add up (``tests/test_sdar_moe.py`` ties the shares to
the uncut layer); the all-to-all that would carry tokens between chips is
``parallel/ep.py``'s, and is not here.

The grouped products' and the sum back's work is proportional to the
pairs HELD (positions x top_k x count / n_experts in expectation), never
to positions x top_k; the gather into the buffer's to the buffer's rows
(a stated multiple of that expectation):

1. ``route``: float32 softmax over all experts, top-k, renormalised; or
   sigmoid scores, the choice by score + a frozen bias, the gates from
   the unbiased scores (``scoring='sigmoid'``).
2. ``dispatch_plan``: the held pairs sorted by expert (two argsorts of
   the positions x top_k expert ids; no scatter), the first ``capacity``
   rows of that order being the buffer; one more binary search over the
   sorted keys says, for each block of positions and each held expert,
   which contiguous rows of the buffer are theirs (``Plan.runs``: within
   an expert the buffer is sorted by position). ``capacity`` is a stated
   multiple (``capacity_factor``) of the expected number of held pairs,
   capped at the worst case (every position choosing ``min(top_k,
   count)`` held experts). The buffer is shared by the experts, so it
   overflows only when the TOTAL over the held experts exceeds it; then
   the layer's output is NaN (the step's loss is non-finite and the
   caller counts a failed step) — never a silently smaller sum — and the
   groups handed to the products are cut at the buffer's end. At
   ``capacity_factor >= n_experts * min(top_k, count) / (top_k * count)``
   it cannot overflow. Every leaf of the plan, and the sigmoid router's
   choice, is named for a layer's checkpoint to keep
   (``ops/_common.KEPT``: integers, under 2 MB a layer): under ``remat``
   the backward pass sorts nothing again.
3. Grouped matrix products over the sorted rows: ``jax.lax.ragged_dot``
   with the per-expert group sizes. XLA:TPU lowers it to its own Mosaic
   kernels (forward, and both transposes for the backward pass) whose
   work follows the group sizes, not the buffer. Chosen by measurement
   on a v5e at the SDAR widths (16 experts, 2048 x 768, 16,384 rows;
   PERF.md section 6, PR 27): one forward product 1.10 ms against 0.84 ms
   for a tile-aligned Pallas kernel with no row to skip, the whole SwiGLU
   forward and backward (9 products) 5.75 ms = 41 % of the bf16 peak;
   the hand-written kernel would need its own transposes, tile-padded
   groups and a custom VJP to win a quarter of a tenth of the step.
4. ``combine``: every position sums the rows of its held pairs.

Moving rows: ``gather_rows`` (buffer row <- position) and ``slot_sum``
(position <- its pairs' rows) are each other's transpose, and each one's
VJP is the other; a scatter-add never runs. The gather is ``jnp.take``
out of ``x`` with a zero row appended for "none": every row of the
buffer is written anyway, and XLA's gather costs 6 ns a row (0.41 ms for
the 65,536 rows of ``sdar-30b-a3b.bd4k``'s buffer; the layer alone on a
v5e, PR 34's chip run K3). The sum back is
``ops/moe_rows_pallas.sum_rows`` where the rows are whole lane tiles of
2- or 4-byte floats (the gate weights' width-1 rows and narrower presets
stay ``take``): as ``take`` it is top_k gathers of P rows, one in eight
of them a real row (8 x 16,384 rows, 4.79 ms; K1); the kernel walks each
block of positions' runs of the buffer and reads the tiles they touch,
0.74 ms at the expected load and 1.19 ms at 1.7 x it (K3; a kernel that
walked the slots and copied a tile a held slot read 8 x the bytes and
3.39 ms: K1). It adds a position's rows in float32 in buffer order (by
expert) and rounds once; ``take`` adds in slot order and rounds after
every addition.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.ops import moe_rows_pallas
from pytorch_ps_mpi_tpu.ops._common import keep
from pytorch_ps_mpi_tpu.telemetry.recorder import setup_event


class Plan(NamedTuple):
    src: jax.Array       # [capacity] position of each buffer row (P: none)
    pair: jax.Array      # [capacity] flat (position, slot) of each row
    dest: jax.Array      # [P, top_k] buffer row of each pair (capacity: none)
    sizes: jax.Array     # [count] pairs per held expert, within the buffer
    loads: jax.Array     # [count] pairs per held expert, all of them
    overflow: jax.Array  # [] bool: the held pairs exceed the buffer
    runs: jax.Array      # [blocks + 1, count] first buffer row of each held
    #                      expert at or after each block of positions


def capacity_rows(positions: int, top_k: int, n_experts: int, count: int,
                  capacity_factor: float) -> int:
    """Buffer rows for ``count`` of ``n_experts`` experts held here."""
    expected = positions * top_k * count / n_experts
    worst = positions * min(top_k, count)
    return min(worst, 8 * math.ceil(capacity_factor * expected / 8))


def route(x, w_router, top_k: int, norm_topk_prob: bool = True, *,
          scoring: str = "softmax", bias=None, scaling: float = 1.0):
    """``x [P, d]`` -> gate weights ``[P, top_k]`` float32 and expert ids
    ``[P, top_k]``. The router runs in float32 at full precision: a bf16
    product moves which expert is 8th and which 9th.

    ``scoring='sigmoid'`` is the bias-corrected router: scores ``s =
    sigmoid(x W_r)``, the experts chosen by ``top_k(s + bias)`` (``bias
    [n_experts]`` steers the choice only and takes no gradient), the gate
    weights the UNBIASED scores of the chosen experts, normalised over
    them (``norm_topk_prob``) and times ``scaling``."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            choice = scores if bias is None else (
                scores + jax.lax.stop_gradient(bias.astype(jnp.float32)))
            _, experts = jax.lax.top_k(choice, top_k)
            # the choice also gathers the gates out of the scores: kept
            # with the plan, a layer's checkpoint runs no second top_k
            # (under softmax top_k's VALUES are the gates: it runs again)
            experts = keep(experts, "moe.plan")
            weights = jnp.take_along_axis(scores, experts, axis=-1)
            if norm_topk_prob:
                weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
            return weights * scaling, experts.astype(jnp.int32)
        if scoring != "softmax" or bias is not None:
            raise ValueError(f"scoring={scoring!r} with bias "
                             f"{'given' if bias is not None else 'None'}")
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, top_k)
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights, experts.astype(jnp.int32)


def dispatch_plan(experts, experts_held: Tuple[int, int], capacity: int,
                  block: int) -> Plan:
    """``block``: the positions one grid step of the sum back owns
    (``Plan.runs``: one more binary search over the sorted keys)."""
    first, count = experts_held
    p, k = experts.shape
    local = experts.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)   # held pairs first
    rank = jnp.argsort(order).astype(jnp.int32)               # its inverse
    ordered = key[order]
    bounds = jnp.searchsorted(ordered, jnp.arange(count + 1, dtype=key.dtype))
    total = bounds[count]
    rows = jnp.arange(capacity, dtype=jnp.int32)
    pair = jnp.where(rows < total, order[:capacity], p * k)
    dest = jnp.where(rank < jnp.minimum(total, capacity), rank, capacity)
    # under an overflow the groups are cut at the buffer's end (a grouped
    # product must not read past it); the layer's output is NaN anyway
    return Plan(src=pair // k, pair=pair, dest=dest.reshape(p, k),
                sizes=jnp.diff(jnp.minimum(bounds, capacity)).astype(jnp.int32),
                loads=jnp.diff(bounds).astype(jnp.int32),
                overflow=total > capacity,
                runs=moe_rows_pallas.block_runs(
                    ordered[:capacity], pair // k, count, p, block))


def _mover(y) -> str:
    """``'kernel'`` where the rows of ``y`` are what the Pallas kernel
    sums (whole lane tiles of 2- or 4-byte floats), else ``'take'``: the
    gate weights' width-1 rows, the tests' narrow presets."""
    return "kernel" if moe_rows_pallas.movable(y) else "take"


def _gather(x, idx):
    """``x[idx]``, zero where ``idx == len(x)``: a zero row past the end
    stands for "none", so the gather is all there is (``mode='fill'``
    adds a ``select`` over the whole result: 0.82 ms beside a gather of
    0.41 into ``bd4k``'s buffer; PR 34's chip run K3)."""
    return jnp.take(jnp.pad(x, ((0, 1), (0, 0))), idx, axis=0, mode="clip")


def _slot_sum(y, slots, to, runs):
    """``out[p] = sum_s y[slots[p, s]]`` = the sum of the rows ``r`` with
    ``to[r] == p``: by the kernel over the rows held (``runs`` says where
    each block of positions finds them), or a ``take`` a slot."""
    if moe_rows_pallas.movable(y):
        return moe_rows_pallas.sum_rows(y, to, runs, slots.shape[0])
    out = _gather(y, slots[:, 0])
    for s in range(1, slots.shape[1]):
        out = out + _gather(y, slots[:, s])
    return out


@jax.custom_vjp
def gather_rows(x, idx, back, runs):
    """``out[r] = x[idx[r]]`` (zero where ``idx[r] == len(x)``). ``back
    [len(x), S]`` lists for every row of ``x`` the rows of ``out`` that
    read it (``len(out)`` for none), ``runs`` where a block of ``x``'s
    rows finds them (``Plan.runs``): the transpose is ``slot_sum``."""
    return _gather(x, idx)


gather_rows.defvjp(
    lambda x, idx, back, runs: (_gather(x, idx), (idx, back, runs)),
    lambda res, g: (_slot_sum(g, res[1], res[0], res[2]), None, None, None))


@jax.custom_vjp
def slot_sum(y, slots, back, runs):
    """``out[p] = sum_s y[slots[p, s]]`` (zero where ``slots[p, s] ==
    len(y)``); ``back [len(y)]`` is the row of ``out`` each row of ``y``
    is summed into, ``runs`` as ``gather_rows``'s."""
    return _slot_sum(y, slots, back, runs)


slot_sum.defvjp(
    lambda y, slots, back, runs: (_slot_sum(y, slots, back, runs), back),
    lambda back, g: (_gather(g, back), None, None, None))


def swiglu_experts(xs, sizes, gate_proj, up_proj, down_proj):
    """``down(silu(gate(x)) * up(x))`` of each sorted row under its own
    expert's matrices ``[count, d, f]``, ``[count, d, f]``, ``[count, f,
    d]``: three grouped products forward, six backward."""
    with jax.named_scope("moe.experts"):
        gate = jax.lax.ragged_dot(xs, gate_proj, sizes)
        up = jax.lax.ragged_dot(xs, up_proj, sizes)
        return jax.lax.ragged_dot(jax.nn.silu(gate) * up, down_proj, sizes)


def dropless_moe(x, w_router, gate_proj, up_proj, down_proj, *,
                 top_k: int, experts_held: Tuple[int, int],
                 capacity_factor: float, norm_topk_prob: bool = True,
                 scoring: str = "softmax", router_bias=None,
                 routed_scaling_factor: float = 1.0):
    """``x [P, d]`` -> (this share's part of the layer ``[P, d]``, pairs
    per held expert ``[count]``). The expert matrices are the held ones,
    in the compute dtype; ``w_router [d, n_experts]`` is whole;
    ``scoring``, ``router_bias`` and ``routed_scaling_factor`` are
    ``route``'s."""
    p, _ = x.shape
    n_experts = w_router.shape[1]
    first, count = experts_held
    if gate_proj.shape[0] != count or first + count > n_experts:
        raise ValueError(f"experts_held={experts_held} against "
                         f"{gate_proj.shape[0]} expert matrices and a router "
                         f"over {n_experts}")
    weights, experts = route(x, w_router, top_k, norm_topk_prob,
                             scoring=scoring, bias=router_bias,
                             scaling=routed_scaling_factor)
    capacity = capacity_rows(p, top_k, n_experts, count, capacity_factor)
    # what the moves move and who moves it, a trace (the set-up log's row)
    setup_event("moe.row_moves", rows=p, slots=top_k, width=x.shape[1],
                dtype=str(x.dtype), buffer_rows=capacity,
                expected_held=p * top_k * count // n_experts,
                gather_rows="take", sum_rows=_mover(x),
                gather_gates="take",
                sum_gates=_mover(weights.reshape(-1, 1)))
    with jax.named_scope("moe.dispatch"):
        # integer-only and under 2 MB: a layer's checkpoint keeps the plan
        # (`_common.KEPT`) and the backward pass does not sort again
        plan = jax.tree.map(
            lambda leaf: keep(leaf, "moe.plan"),
            dispatch_plan(experts, experts_held, capacity,
                          moe_rows_pallas.block_rows(x.shape[1])))
        xs = gather_rows(x, plan.src, plan.dest, plan.runs)
        ws = gather_rows(weights.reshape(-1, 1), plan.pair,
                         plan.dest.reshape(-1, 1), plan.runs)
    ys = swiglu_experts(xs, plan.sizes, gate_proj, up_proj, down_proj)
    with jax.named_scope("moe.combine"):
        y = slot_sum(ys * ws.astype(ys.dtype), plan.dest, plan.src,
                     plan.runs)
        y = jnp.where(plan.overflow, jnp.asarray(jnp.nan, y.dtype), y)
    return y, plan.loads
