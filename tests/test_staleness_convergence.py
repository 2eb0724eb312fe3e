"""Staleness→convergence curve semantics: the
in-XLA bounded-staleness sweep must reproduce the committed artifact's
shape — no tax at small bounds, a real tax at large ones — and the
bench's updates-to-target machinery must be correct.

Deterministic by construction: each curve runs a SEEDED pacing schedule
(``staleness_probs`` — the per-round lags are drawn inside the XLA
program from a fixed key, so the whole lag sequence is a pure function
of the seed; no wall clock, no host load). The earlier form pinned
every worker at the worst-case lag every round (``staleness=[bound]*W``)
— a schedule the committed artifact never measured (its lags were
sampled) and whose small-bound leg carries a real tax (measured ~1.6×
sync at bound 2), which made the "nearly free" assertion flaky-by-
margin. The pacing schedules below pin the artifact's actual shape:
a front-loaded small-lag schedule (mean lag ~0.55) is nearly free,
a tail-heavy large-lag schedule (mean lag ~7.8) costs heavily
(measured 42–45× across seeds — asserted with a 10× floor)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.staleness_bench import _problem, updates_to_target
from pytorch_ps_mpi_tpu.parallel.async_ps import AsyncPS

WORKERS = 4

#: seeded pacing schedules (lag distributions over 0..bound): the small
#: bound keeps most reads fresh (the healthy-fleet shape the artifact
#: measured); the large bound concentrates mass at the bound (a fleet
#: pacing far behind the publisher)
PACE_SMALL = [4 / 7, 2 / 7, 1 / 7]                 # bound 2, mean ~0.55
PACE_LARGE = [0.0] * 7 + [0.2, 0.8]                # bound 8, mean ~7.8


def _run_curve(bound: int, probs=None, rounds: int = 60, seed: int = 0):
    # the bench's own problem, not a copy: the test must track what the
    # committed artifact actually measured
    cfg, params0, batch_fn, loss_fn = _problem()
    eval_batch = batch_fn(10**6, 10**6)
    eval_loss = jax.jit(loss_fn)
    kw = (dict(staleness_probs=probs) if probs is not None
          else dict(staleness=[bound] * WORKERS))
    ps = AsyncPS(params0, loss_fn, num_workers=WORKERS, optim="sgd",
                 lr=cfg["hyper"]["lr"], max_staleness=max(bound, 1),
                 seed=seed, **kw)
    losses = [float(eval_loss(ps.params, eval_batch))]
    for step in range(rounds):
        batches = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[batch_fn(step, w) for w in range(WORKERS)],
        )
        ps.step(batches)
        losses.append(float(eval_loss(ps.params, eval_batch)))
    mean_lag = (sum(k * v for k, v in ps.staleness_hist.items())
                / max(1, sum(ps.staleness_hist.values())))
    return losses, mean_lag


def test_small_staleness_is_nearly_free_and_large_costs():
    """The artifact's headline shape, pinned on seeded deterministic
    pacing schedules: a small-lag schedule (mean ~0.55) converges within
    15% of synchronous; a tail-heavy bound-8 schedule (mean ~7.8) is
    strictly worse than both — the convergence cost the AsySG-InCon
    bound predicts grows with the schedule's observed lag, which the
    controller's staleness LR scaling exists to pay down."""
    sync, _ = _run_curve(0)
    s2, lag2 = _run_curve(2, PACE_SMALL)
    s8, lag8 = _run_curve(8, PACE_LARGE)
    # the schedules realized the lags they were derived for
    assert lag2 < 1.0, lag2
    assert lag8 > 6.0, lag8
    assert sync[-1] < 0.1 * sync[0]          # the problem converges
    assert s2[-1] < 1.15 * sync[-1], (sync[-1], s2[-1])
    assert s8[-1] > s2[-1], (s8[-1], s2[-1])
    # measured 42-45x across seeds; 10x is the no-flake floor that still
    # separates "costs heavily" from noise
    assert s8[-1] > 10.0 * sync[-1], (sync[-1], s8[-1])


def test_updates_to_target_interpolation():
    """The bench's threshold-crossing interpolation: exact on a known
    curve, None when the target is never reached."""
    curves = {
        0: ([0, 10, 20], [1.0, 0.5, 0.25]),
        8: ([0, 10, 20], [1.0, 0.9, 0.8]),
    }
    utt = updates_to_target(curves, target_frac=0.5)
    assert utt[0] == 10.0          # hits exactly at the second point
    assert utt[8] is None          # never reaches 0.5
    utt2 = updates_to_target(curves, target_frac=0.375)
    assert np.isclose(utt2[0], 15.0)  # halfway between 0.5 and 0.25
