"""AsySG-InCon: asynchronous SGD with inconsistent reads.

The algorithm the reference implements (Lian et al. 2015,
arXiv:1506.08272, cited reference ``README.md:56-59``): workers compute
gradients against *stale* parameter snapshots — each worker may hold a
different version ("inconsistent reads") — and the server applies their
updates sequentially as they arrive.

The reference got asynchrony from OS threads + nonblocking MPI requests
(``ps.py:65-66,85``). Neither exists inside an XLA program, so the
TPU-native design makes staleness *explicit data*: a ring buffer of recent
parameter versions lives on device; each round every worker grad is taken
at ``history[now - staleness_i]`` (vmapped — all workers' backward passes
run as one batched XLA program), then the server applies the updates one
at a time with ``lax.scan`` (update *i* sees the params produced by update
*i-1*, exactly the arrival-order semantics of the MPI PS). Bounded
staleness is the buffer depth. Across pod slices the same construct runs
over DCN with per-slice histories; within a slice sync aggregation is
cheaper (ICI) and preferred — SURVEY §2.5's disposition.

Codec compression applies on the simulated wire: each worker's gradient
goes encode → decode before the server sees it, matching the reference's
encode-before-send/decode-on-receive placement (``ps.py:94,166``).

Scope note: this module is the *algorithm-semantics* vehicle — bounded
staleness as explicit data inside one XLA program, with per-round lags
SAMPLED from a distribution (optionally the measured arrival histogram
of a real multi-process run, via :func:`staleness_probs_from_histogram`;
a fixed schedule remains available for deterministic tests). The
*wall-clock* benefit asynchrony exists for — fast workers streaming
past a straggler — is demonstrated by the multi-process stack with real
jitted compute in ``parallel/async_train.py`` (the chip's number for it
is the ``resnet18-cifar.async1`` cell of ``BENCHMARK.json``); the two
are tied together by
``tests/test_async_train.py::
test_inxla_sampled_staleness_matches_shm_arrival_histogram``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pytorch_ps_mpi_tpu.codecs import Codec, IdentityCodec
from pytorch_ps_mpi_tpu.optim import OPTIMIZERS
from pytorch_ps_mpi_tpu.telemetry import get_recorder

PyTree = Any


def staleness_probs_from_histogram(
    hist: Dict[int, int], max_staleness: int
) -> np.ndarray:
    """Measured arrival histogram → sampling distribution for
    :class:`AsyncPS`.

    ``hist`` is a ``{staleness: count}`` dict as produced by the
    multi-process servers (``ShmPSServer.staleness_seen``,
    ``TcpPSServer.staleness_seen``) — measured wall-clock arrival
    behavior. Lags beyond ``max_staleness`` were *dropped* by those
    servers (never applied), so they are excluded here too: the returned
    distribution is over the lags that actually reached the optimizer.
    """
    probs = np.zeros(max_staleness + 1, np.float64)
    for lag, count in hist.items():
        if 0 <= int(lag) <= max_staleness:
            probs[int(lag)] = float(count)
    if probs.sum() <= 0:
        raise ValueError(
            f"histogram has no mass in 0..{max_staleness}: {hist}"
        )
    return probs / probs.sum()


class AsyncPS:
    """Bounded-staleness asynchronous parameter server.

    Args:
      params: initial parameter pytree.
      loss_fn: ``loss_fn(params, batch) -> scalar``.
      num_workers: worker count (the reference's MPI world size).
      optim: ``'sgd'`` or ``'adam'``.
      code: gradient codec applied on the simulated wire.
      max_staleness: ring-buffer depth; worker *i*'s read lag is
        ``staleness[i] <= max_staleness``.
      staleness: optional FIXED per-worker lags (a deterministic
        schedule, for tests/repro). When omitted, lags are SAMPLED fresh
        each round inside the jitted program — AsySG-InCon's
        inconsistent reads are stochastic arrival effects, not a
        round-robin.
      staleness_probs: distribution over lags ``0..max_staleness`` the
        per-round sampling draws from; default uniform. Feed it a
        *measured* arrival histogram (e.g. a ShmPSServer/TcpPSServer
        run's ``staleness_seen`` via
        :func:`staleness_probs_from_histogram`) to replay real cluster
        arrival behavior inside the XLA program.
      seed: PRNG seed for stochastic codecs AND the staleness sampling.
      **hyper: optimizer hyperparameters.

    ``self.staleness_hist`` accumulates the lags actually used (a
    ``{lag: count}`` dict), directly comparable to the multi-process
    servers' ``staleness_seen``.
    """

    def __init__(
        self,
        params: PyTree,
        loss_fn: Callable,
        *,
        num_workers: int,
        optim: str = "sgd",
        code: Optional[Codec] = None,
        max_staleness: int = 2,
        staleness: Optional[Sequence[int]] = None,
        staleness_probs: Optional[Sequence[float]] = None,
        seed: int = 0,
        **hyper,
    ):
        hyper_cls, init_state, update_fn = OPTIMIZERS[optim]
        self.hyper = hyper_cls(**hyper)
        self._update_fn = update_fn
        self.loss_fn = loss_fn
        self.num_workers = int(num_workers)
        self.code = code if code is not None else IdentityCodec()
        self.max_staleness = int(max_staleness)
        if staleness is not None and staleness_probs is not None:
            raise ValueError("give staleness (fixed) OR staleness_probs, not both")
        if staleness is not None:
            if (len(staleness) != num_workers
                    or max(staleness) > self.max_staleness
                    or min(staleness) < 0):
                raise ValueError(
                    "need num_workers staleness values in 0..max_staleness"
                )
            self.staleness = jnp.asarray(staleness, jnp.int32)
            self._staleness_logits = None
        else:
            if staleness_probs is None:
                staleness_probs = [1.0] * (self.max_staleness + 1)
            probs = np.asarray(staleness_probs, np.float64)
            if probs.shape != (self.max_staleness + 1,) or probs.min() < 0 \
                    or probs.sum() <= 0:
                raise ValueError(
                    "staleness_probs must be max_staleness+1 nonnegative "
                    "weights with positive sum"
                )
            self.staleness = None
            self._staleness_logits = jnp.log(
                jnp.asarray(probs / probs.sum(), jnp.float32) + 1e-30
            )
        self.staleness_hist: Dict[int, int] = {}
        self.params = params
        self.opt_state = init_state(params)
        # history[0] = newest … history[max_staleness] = oldest, stacked.
        self.history = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (self.max_staleness + 1,) + p.shape),
            params,
        )
        self.codec_state = jax.tree.map(
            lambda p: jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (self.num_workers,) + x.shape),
                self.code.init_state(p.shape, p.dtype),
            ),
            params,
        )
        self._rng = jax.random.key(seed)
        self._round = jax.jit(self._make_round())
        self.step_count = 0

    def _wire(self, grads, codec_state, rng):
        """encode → decode round trip for one worker's gradient pytree
        (the simulated network; reference ``ps.py:94,166``)."""
        leaves, treedef = jax.tree.flatten(grads)
        flat_states = treedef.flatten_up_to(codec_state)
        keys = (
            list(jax.random.split(rng, len(leaves)))
            if self.code.needs_rng
            else [None] * len(leaves)
        )
        outs, states = [], []
        for g, st, k in zip(leaves, flat_states, keys):
            payload, new_st = self.code.encode(g, st, k)
            outs.append(self.code.decode(payload, g.shape, g.dtype))
            states.append(new_st)
        return jax.tree.unflatten(treedef, outs), jax.tree.unflatten(treedef, states)

    def _make_round(self):
        grad_fn = jax.grad(self.loss_fn)

        def round_fn(params, opt_state, history, codec_state, batches, rng):
            # 1. Inconsistent reads: worker i reads version history[lag_i].
            #    Sampled mode draws fresh lags every round from the
            #    (possibly measured) arrival distribution — stochastic
            #    inconsistent reads, not a schedule.
            if self._staleness_logits is not None:
                rng, k = jax.random.split(rng)
                lags = jax.random.categorical(
                    k, self._staleness_logits, shape=(self.num_workers,)
                ).astype(jnp.int32)
            else:
                lags = self.staleness
            stale = jax.tree.map(lambda h: h[lags], history)
            # 2. All workers' backward passes as one batched program.
            grads = jax.vmap(grad_fn)(stale, batches)
            # 3. Simulated wire: per-worker encode/decode (+ codec state).
            def per_worker(w_grads, w_state, k):
                return self._wire(w_grads, w_state, k)
            keys = jax.random.split(rng, self.num_workers)
            grads, new_codec_state = jax.vmap(per_worker)(grads, codec_state, keys)
            # 4. Server applies updates in arrival order (scan = sequential
            #    inconsistent updates, AsySG-InCon's core).
            def apply_one(carry, g):
                p, s = carry
                p, s = self._update_fn(p, g, s, self.hyper)
                return (p, s), None
            (params, opt_state), _ = lax.scan(apply_one, (params, opt_state), grads)
            # 5. Push the new version into the history ring.
            history = jax.tree.map(
                lambda h, p: jnp.concatenate([p[None], h[:-1]], axis=0),
                history,
                params,
            )
            return params, opt_state, history, new_codec_state, lags

        return round_fn

    def step(self, batches: PyTree) -> Tuple[None, Dict[str, float]]:
        """One async round: every worker contributes one (stale) gradient.

        ``batches``: pytree whose leaves have a leading ``[num_workers]``
        axis (each worker's local batch). Returns ``(None, data)`` in the
        reference's ``(loss, data)`` shape (``ps.py:193``).
        """
        import time

        t0 = time.perf_counter()
        self._rng, rng = jax.random.split(self._rng)
        (self.params, self.opt_state, self.history, self.codec_state,
         lags) = self._round(
            self.params, self.opt_state, self.history, self.codec_state,
            batches, rng,
        )
        jax.block_until_ready(self.params)
        for lag in np.asarray(lags).tolist():
            self.staleness_hist[lag] = self.staleness_hist.get(lag, 0) + 1
        self.step_count += 1
        dur = time.perf_counter() - t0
        rec = get_recorder()
        if rec is not None:
            rec.event("async_ps.round", kind="span",
                      ts=time.monotonic() - dur, dur=dur,
                      step=self.step_count,
                      updates_applied=self.num_workers,
                      lags=np.asarray(lags).tolist())
        return None, {"step_time": dur,
                      "updates_applied": float(self.num_workers)}
