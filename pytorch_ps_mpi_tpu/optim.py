"""Fused optimizer update rules: SGD (momentum/nesterov/dampening/weight
decay) and Adam (amsgrad/bias correction/weight decay).

The math mirrors the reference's PS-fused reimplementations —
``SGD.optim_step`` (``ps.py:195-214``) and ``Adam.optim_step``
(``ps.py:217-261``) — which themselves mirror ``torch.optim``. Here each
rule is a pure per-leaf function tree-mapped over the parameter pytree and
fused by XLA into the jitted train step, instead of an eager per-parameter
Python loop run redundantly on every rank (``ps.py:190``).

Semantics checked against optax in ``tests/test_optim.py``. Notable
reference quirk preserved: the momentum buffer is *initialized to the first
d_p* (``ps.py:203-205``, torch semantics), not to zero.

Learning-rate schedules: ``lr`` may be a float (the reference's only
option, constant ``ps.py:197``) or a callable ``step -> scalar`` from
:data:`SCHEDULES` (or any user function built from jnp ops). A schedule is
evaluated on the optimizer state's traced step counter INSIDE the compiled
program, so the lr varies per step with zero recompiles — the TPU-native
shape of torch's host-side ``lr_scheduler.step()`` mutation.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

PyTree = Any
LR = Union[float, Callable[[jax.Array], jax.Array]]


def _lr_at(lr: LR, step: jax.Array):
    """Resolve a constant-or-schedule lr at a (traced) 0-based step."""
    return lr(step) if callable(lr) else lr


# -- schedules (each returns step -> scalar; all jnp, trace-safe) ------------

def constant_lr(base: float) -> Callable:
    return lambda step: jnp.float32(base)


def warmup_cosine(base: float, total_steps: int, warmup_steps: int = 0,
                  final_scale: float = 0.0) -> Callable:
    """Linear warmup 0 -> base over ``warmup_steps``, then cosine decay to
    ``final_scale * base`` at ``total_steps`` (flat afterwards). The
    de-facto standard schedule of the BERT/ResNet training recipes the
    BASELINE configs name."""
    if total_steps <= warmup_steps:
        raise ValueError("total_steps must exceed warmup_steps")

    def f(step):
        s = step.astype(jnp.float32)
        warm = s / max(warmup_steps, 1)
        t = jnp.clip((s - warmup_steps) / (total_steps - warmup_steps), 0.0, 1.0)
        cos = final_scale + (1.0 - final_scale) * 0.5 * (1.0 + jnp.cos(jnp.pi * t))
        return jnp.float32(base) * jnp.where(s < warmup_steps, warm, cos)

    return f


def step_decay(base: float, boundaries: Tuple[int, ...],
               scale: float = 0.1) -> Callable:
    """Multiply by ``scale`` at each boundary step (torch MultiStepLR, the
    classic ResNet recipe)."""
    bounds = jnp.asarray(boundaries, jnp.int32)

    def f(step):
        k = jnp.sum(step >= bounds).astype(jnp.float32)
        return jnp.float32(base) * jnp.float32(scale) ** k

    return f


SCHEDULES: Dict[str, Callable[..., Callable]] = {
    "constant": constant_lr,
    "warmup_cosine": warmup_cosine,
    "step_decay": step_decay,
}


class SGDHyper(NamedTuple):
    lr: LR = 0.01
    momentum: float = 0.0
    dampening: float = 0.0
    weight_decay: float = 0.0
    nesterov: bool = False


class AdamHyper(NamedTuple):
    lr: LR = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    amsgrad: bool = False
    # False: torch.optim.Adam's coupled L2 (wd added to the gradient,
    # the reference's semantics); True: AdamW (Loshchilov & Hutter
    # 2019) — decay applied directly to params, outside the adaptive
    # rescaling, the modern default for transformer training
    decoupled_weight_decay: bool = False


class SGDState(NamedTuple):
    step: jax.Array          # scalar int32
    momentum_buf: PyTree     # per-leaf buffers (zeros when momentum == 0)


class AdamState(NamedTuple):
    step: jax.Array
    exp_avg: PyTree
    exp_avg_sq: PyTree
    max_exp_avg_sq: PyTree   # params-shaped with amsgrad, else () (empty)


def init_sgd_state(params: PyTree) -> SGDState:
    return SGDState(
        step=jnp.zeros((), jnp.int32),
        momentum_buf=jax.tree.map(jnp.zeros_like, params),
    )


def init_adam_state(params: PyTree, amsgrad: bool = True) -> AdamState:
    """``amsgrad=False`` leaves the running maximum empty: a fourth
    float32 copy of the parameters that nothing would read (2.8 GB of a
    697 M-parameter state)."""
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    return AdamState(jnp.zeros((), jnp.int32), zeros(), zeros(),
                     zeros() if amsgrad else ())


def sgd_update(
    params: PyTree, grads: PyTree, state: SGDState, h: SGDHyper
) -> Tuple[PyTree, SGDState]:
    """One fused SGD step on the aggregated gradient (reference
    ``ps.py:197-214``)."""
    first = state.step == 0
    lr = _lr_at(h.lr, state.step)

    def leaf(p, g, buf):
        d_p = g + h.weight_decay * p if h.weight_decay else g
        if h.momentum:
            # torch/reference init: buf <- d_p on first step (ps.py:203-205)
            new_buf = jnp.where(
                first, d_p, h.momentum * buf + (1.0 - h.dampening) * d_p
            )
            d_p = d_p + h.momentum * new_buf if h.nesterov else new_buf
        else:
            new_buf = buf
        return p - lr * d_p, new_buf

    out = jax.tree.map(leaf, params, grads, state.momentum_buf)
    new_params = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_bufs = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return new_params, SGDState(state.step + 1, new_bufs)


def adam_update(
    params: PyTree, grads: PyTree, state: AdamState, h: AdamHyper
) -> Tuple[PyTree, AdamState]:
    """One fused Adam step (reference ``ps.py:218-261``): moment updates,
    optional amsgrad max-denominator, bias-corrected parameter update."""
    step = state.step + 1
    lr = _lr_at(h.lr, state.step)
    bias1 = 1.0 - h.b1 ** step.astype(jnp.float32)
    bias2 = 1.0 - h.b2 ** step.astype(jnp.float32)

    def leaf(p, g, m, v, vmax=None):
        if h.weight_decay and not h.decoupled_weight_decay:
            g = g + h.weight_decay * p  # coupled L2 (torch Adam)
        m_new = h.b1 * m + (1.0 - h.b1) * g
        v_new = h.b2 * v + (1.0 - h.b2) * (g * g)
        if h.amsgrad:
            vmax_new = jnp.maximum(vmax, v_new)
            denom = jnp.sqrt(vmax_new) + h.eps
        else:
            vmax_new = None
            denom = jnp.sqrt(v_new) + h.eps
        step_size = lr * jnp.sqrt(bias2) / bias1
        p_new = p - step_size * m_new / denom
        if h.weight_decay and h.decoupled_weight_decay:
            p_new = p_new - lr * h.weight_decay * p  # AdamW
        return p_new, m_new, v_new, vmax_new

    # without amsgrad the maximum is passed on as it came: empty, or the
    # dead tree of a checkpoint written before it became optional
    out = jax.tree.map(
        leaf, params, grads, state.exp_avg, state.exp_avg_sq,
        *((state.max_exp_avg_sq,) if h.amsgrad else ())
    )
    pick = lambda i: jax.tree.map(
        lambda o: o[i], out, is_leaf=lambda x: isinstance(x, tuple)
    )
    return pick(0), AdamState(
        step, pick(1), pick(2),
        pick(3) if h.amsgrad else state.max_exp_avg_sq)


# ---------------------------------------------------------------------------
# Adafactor (Shazeer & Stern 2018) — the TPU-native memory-efficient
# optimizer: second moments of [n, m] leaves are stored FACTORED as a
# row vector + column vector (sublinear optimizer state; the rank-1
# reconstruction is exact at the optimum of the I-divergence, paper
# §3). Beyond the reference's SGD/Adam family — at BERT/GPT scale the
# optimizer state drops from 2x params (Adam) to ~1/128th of one copy,
# which is HBM that goes back to batch size. No-momentum form (the
# paper's memory-efficient default; Adam covers the momentum niche).
# Semantics mirror optax.adafactor leaf-for-leaf (factoring over the
# two LARGEST dims, clip-by-block-rms, optional parameter-scale
# multiply) and are pinned to it in tests/test_optim.py — with ONE
# deliberate divergence: ``lr=None`` here applies the paper's relative
# step size rho_t = min(1e-2, 1/sqrt(t)) (Shazeer & Stern Alg. 4),
# whereas ``optax.adafactor(learning_rate=None)`` simply OMITS the lr
# scaling stage (the update magnitude then comes only from the
# parameter scale). The paper default is the right zero-config
# behavior for a drop-in optimizer; the two are reconciled in
# tests/test_optim.py::
# test_adafactor_relative_step_matches_optax_explicit_schedule, which
# pins our lr=None path against optax given rho_t as an EXPLICIT
# schedule.

_FACTOR_MIN = 128  # fixed at init (registry inits see params only)


def _factored_dims(shape) -> Optional[Tuple[int, int]]:
    """The two largest axes (d1, d0), or None when the second-largest
    is below the factoring threshold — optax's rule exactly."""
    if len(shape) < 2:
        return None
    order = sorted(range(len(shape)), key=lambda i: shape[i])
    if shape[order[-2]] < _FACTOR_MIN:
        return None
    return order[-2], order[-1]


class AdafactorHyper(NamedTuple):
    lr: LR = None                 # None -> relative step min(1e-2, t^-0.5)
    decay_rate: float = 0.8       # beta2_t = 1 - t^-decay_rate
    eps1: float = 1e-30           # squared-gradient regularizer
    eps2: float = 1e-3            # parameter-scale floor (paper alg. 4)
    clip_threshold: float = 1.0   # update block-RMS clip
    weight_decay: float = 0.0     # added to the update un-lr-scaled
    # (optax add_decayed_weights semantics)
    multiply_by_parameter_scale: bool = True


class AdafactorState(NamedTuple):
    step: jax.Array
    v_row: PyTree   # factored leaves: [shape minus largest dim];
    v_col: PyTree   # [shape minus second-largest]; zeros((1,)) sentinel
    v_full: PyTree  # unfactored leaves: full shape; sentinel otherwise


def init_adafactor_state(params: PyTree) -> AdafactorState:
    def vr(p):
        d = _factored_dims(p.shape)
        if d is None:
            return jnp.zeros((1,), p.dtype)
        return jnp.zeros(tuple(np.delete(p.shape, d[1])), p.dtype)

    def vc(p):
        d = _factored_dims(p.shape)
        if d is None:
            return jnp.zeros((1,), p.dtype)
        return jnp.zeros(tuple(np.delete(p.shape, d[0])), p.dtype)

    def vf(p):
        return (jnp.zeros_like(p) if _factored_dims(p.shape) is None
                else jnp.zeros((1,), p.dtype))

    return AdafactorState(
        step=jnp.zeros((), jnp.int32),
        v_row=jax.tree.map(vr, params),
        v_col=jax.tree.map(vc, params),
        v_full=jax.tree.map(vf, params),
    )


def adafactor_update(
    params: PyTree, grads: PyTree, state: AdafactorState, h: AdafactorHyper,
    scalar_mean: Optional[Callable] = None,
) -> Tuple[PyTree, AdafactorState]:
    """One fused Adafactor step on the aggregated gradient.

    ``scalar_mean`` turns the two per-leaf SCALAR reductions (the
    update-clip RMS and the parameter-scale RMS) into global means
    under sharded execution: pass ``lambda s: lax.pmean(s, model_axes)``
    inside shard_map and — because uniform shards have equal sizes —
    the pmean of per-shard means IS the global mean, while replicated
    leaves pmean to themselves. The factored row/col means never need
    it: :func:`adafactor_check_sharding` guarantees the factored dims
    are unsharded, so those reductions are shard-local by construction.
    """
    step = state.step + 1
    t = step.astype(jnp.float32)
    beta2t = 1.0 - t ** (-h.decay_rate)
    if h.lr is None:
        lr = jnp.minimum(1e-2, 1.0 / jnp.sqrt(t))
    else:
        lr = _lr_at(h.lr, state.step)

    mean_sq = scalar_mean if scalar_mean is not None else (lambda x: x)

    def leaf(p, g, vr, vc, vf):
        dims = _factored_dims(p.shape)
        g2 = g * g + h.eps1
        if dims is not None:
            d1, d0 = dims
            vr_new = beta2t * vr + (1.0 - beta2t) * jnp.mean(g2, axis=d0)
            vc_new = beta2t * vc + (1.0 - beta2t) * jnp.mean(g2, axis=d1)
            # the per-row mean normalizer lives in the row factor
            reduced_d1 = d1 - 1 if d1 > d0 else d1
            row_mean = jnp.mean(vr_new, axis=reduced_d1, keepdims=True)
            u = (g * jnp.expand_dims((vr_new / row_mean) ** -0.5, d0)
                 * jnp.expand_dims(vc_new ** -0.5, d1))
            vf_new = vf
        else:
            vf_new = beta2t * vf + (1.0 - beta2t) * g2
            u = g * vf_new ** -0.5
            vr_new, vc_new = vr, vc
        rms_u = jnp.sqrt(mean_sq(jnp.mean(u * u)))
        u = u / jnp.maximum(1.0, rms_u / h.clip_threshold)
        scale = lr
        if h.multiply_by_parameter_scale:
            scale = scale * jnp.maximum(
                h.eps2,
                jnp.sqrt(mean_sq(jnp.mean(p.astype(jnp.float32) ** 2))),
            )
        p_new = p - scale * u
        if h.weight_decay:
            p_new = p_new - h.weight_decay * p
        return p_new, vr_new, vc_new, vf_new

    out = jax.tree.map(
        leaf, params, grads, state.v_row, state.v_col, state.v_full
    )
    pick = lambda i: jax.tree.map(
        lambda o: o[i], out, is_leaf=lambda x: isinstance(x, tuple)
    )
    return pick(0), AdafactorState(step, pick(1), pick(2), pick(3))


def adafactor_check_sharding(params: PyTree, param_specs: PyTree) -> None:
    """Reject leaves whose GLOBAL factored dims are sharded: the
    row/col means would then span devices, and a shard-local mean
    silently computes a different (and shape-corrupting, once the
    replicated-state broadcast joins in) update. Sharding any OTHER
    axis is exactly decomposable — the factored means stay shard-local
    and the scalar reductions go through ``scalar_mean``."""
    spec_leaves = jax.tree.structure(params).flatten_up_to(param_specs)
    for p, sp in zip(jax.tree.leaves(params), spec_leaves):
        dims = _factored_dims(p.shape)
        if dims is None:
            continue  # v_full mirrors the leaf: elementwise, any sharding
        entries = tuple(sp) if sp is not None else ()
        sharded = {i for i, e in enumerate(entries) if e is not None}
        if sharded & set(dims):
            raise NotImplementedError(
                "optim='adafactor': leaf with global shape "
                f"{p.shape} factors over dims {dims}, but spec {sp} "
                "shards one of them — the row/col second-moment means "
                "would span devices. Shard a non-factored axis (e.g. a "
                "leading stack axis) or use optim='adam'/'sgd'"
            )


def _delete_spec_dim(sp, ndim: int, d: int):
    entries = (tuple(sp) if sp is not None else ()) + (None,) * ndim
    entries = entries[:ndim]
    kept = entries[:d] + entries[d + 1:]
    return PartitionSpec(*kept)


def adafactor_state_specs(params: PyTree, param_specs: PyTree):
    """Per-leaf shard_map specs for :class:`AdafactorState` under
    model-parallel ``param_specs``: v_row/v_col inherit the leaf's spec
    minus the deleted (factored, guaranteed-unsharded) dim; v_full
    mirrors the leaf for unfactored leaves; sentinels replicate."""
    P_ = PartitionSpec
    treedef = jax.tree.structure(params)
    spec_leaves = treedef.flatten_up_to(param_specs)
    p_leaves = jax.tree.leaves(params)

    def per_leaf(which):
        out = []
        for p, sp in zip(p_leaves, spec_leaves):
            dims = _factored_dims(p.shape)
            if which == "v_full":
                out.append(P_() if dims is not None
                           else (sp if sp is not None else P_()))
            elif dims is None:
                out.append(P_())
            else:
                d1, d0 = dims
                d = d0 if which == "v_row" else d1
                out.append(_delete_spec_dim(sp, len(p.shape), d))
        return jax.tree.unflatten(treedef, out)

    return AdafactorState(
        step=P_(),
        v_row=per_leaf("v_row"),
        v_col=per_leaf("v_col"),
        v_full=per_leaf("v_full"),
    )


OPTIMIZERS: Dict[str, Any] = {
    "sgd": (SGDHyper, init_sgd_state, sgd_update),
    "adam": (AdamHyper, init_adam_state, adam_update),
    "adafactor": (AdafactorHyper, init_adafactor_state, adafactor_update),
}
