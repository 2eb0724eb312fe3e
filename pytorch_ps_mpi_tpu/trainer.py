"""Trainer: the training-loop layer the reference never shipped.

The reference was a bare optimizer library — its train scripts lived in a
sibling research repo (SURVEY: "no models, no training loop, no CLI").
This closes that gap: a loop that owns an :class:`MPI_PS` optimizer,
fuses steps in ``lax.scan`` chunks for throughput, accumulates the
per-step metrics dicts, and checkpoints/resumes (params + optimizer state
+ step counter) through :class:`CheckpointManager`.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_ps_mpi_tpu.ps import MPI_PS
from pytorch_ps_mpi_tpu.telemetry import setup_event, span
from pytorch_ps_mpi_tpu.utils.checkpoint import CheckpointManager
from pytorch_ps_mpi_tpu.utils.metrics import MetricsAccumulator

PyTree = Any


class Trainer:
    """Drive an ``MPI_PS`` optimizer over a batch iterator.

    Args:
      optimizer: a constructed :class:`MPI_PS` (or SGD/Adam subclass).
      loss_fn: ``loss_fn(params, batch) -> scalar``.
      checkpoint_dir: optional; enables save/resume.
      checkpoint_every: steps between checkpoints.
      scan_chunk: >1 fuses that many steps into one XLA program via
        ``run_steps`` (requires a steady batch shape).
    """

    def __init__(
        self,
        optimizer: MPI_PS,
        loss_fn: Callable,
        *,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 100,
        scan_chunk: int = 1,
    ):
        self.opt = optimizer
        self.loss_fn = loss_fn
        self.metrics = MetricsAccumulator()
        self.step_count = 0
        self.scan_chunk = max(1, int(scan_chunk))
        self.checkpoint_every = checkpoint_every
        self._last_saved_step = 0
        self._eval_compiled: Dict[Any, Callable] = {}
        # entry of the first fit call, until the first loss is on the host
        self._first_fit_at: Optional[float] = None
        self._first_loss_seen = False
        self.ckpt = (
            CheckpointManager(checkpoint_dir) if checkpoint_dir else None
        )

    # -- checkpoint / resume ------------------------------------------------
    def _state(self, **kw) -> Dict[str, PyTree]:
        # Delegate to the optimizer's own state_dict so checkpoints carry
        # everything it considers state — including the PRNG stream
        # (stochastic codecs replay keys on resume) and aux_state (BN
        # batch_stats), not just params/opt_state.
        sd = dict(self.opt.state_dict(**kw))
        sd["trainer_step"] = jnp.asarray(self.step_count)
        if sd.get("aux_state") is None:
            sd.pop("aux_state")  # pytree restore needs a stable structure
        return sd

    def save(self) -> None:
        if self.ckpt is None:
            raise RuntimeError("no checkpoint_dir configured")
        with span("trainer.checkpoint", step=self.step_count):
            self.ckpt.save(self.step_count, self._state())
        self._last_saved_step = self.step_count

    def maybe_restore(self) -> bool:
        """Resume from the latest checkpoint if one exists. A checkpoint
        whose pytree structure does not match the current schema (e.g.
        written by an older version) is reported and skipped — training
        starts fresh rather than crashing."""
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return False
        try:
            try:
                state = self.ckpt.restore(self._state())
            except Exception:
                # written when Adam's state always held the AMSGrad maximum
                state = self.ckpt.restore(self._state(legacy_adam=True))
        except Exception as e:
            import sys

            print(
                f"checkpoint restore failed (incompatible schema?): {e}; "
                "starting fresh",
                file=sys.stderr,
            )
            return False
        # device placement of restored leaves is load_state_dict's job
        # (MPI_PS._decommit_restored keeps correctly-sharded restores,
        # rehosts the rest)
        self.step_count = int(np.asarray(state.pop("trainer_step")))
        state.setdefault("aux_state", None)
        self.opt.load_state_dict(state)
        return True

    # -- evaluation ---------------------------------------------------------
    def evaluate(
        self,
        batches: Iterator[PyTree],
        num_batches: int,
        eval_fn: Optional[Callable] = None,
    ) -> float:
        """Mean of ``eval_fn(params, batch)`` (default: the training
        ``loss_fn``) over ``num_batches`` batches, without touching
        optimizer state."""
        fn = eval_fn if eval_fn is not None else self.loss_fn
        # key by behavior, not object identity: a bound method or fresh
        # lambda per call must not recompile every evaluate() (same
        # machinery MPI_PS.step uses for loss_fn)
        from pytorch_ps_mpi_tpu.ps import _fn_cache_key

        key = ("eval", _fn_cache_key(fn))
        if key not in self._eval_compiled:
            self._eval_compiled[key] = jax.jit(fn)
        compiled = self._eval_compiled[key]
        total = 0.0
        for _ in range(num_batches):
            total += float(compiled(self.opt.params, next(batches)))
        return total / max(1, num_batches)

    # -- training -----------------------------------------------------------
    @staticmethod
    def _fetch_loss(step: int, loss: jax.Array) -> float:
        """``float(loss)`` of step ``step``: the row of the span that
        waits for the value is the one that carries it."""
        with span("trainer.loss_fetch", step=step) as attrs:
            value = float(loss)
            if attrs is not None:
                attrs["loss"] = value
        return value

    def _first_loss(self, step: int) -> bool:
        """The first loss of this trainer's life is on the host: one row
        ``setup.first_step`` of the set-up log, from the entry of the
        first ``fit`` call to now. Gives False, what the loop's
        ``awaiting`` becomes: no step after this one comes here."""
        self._first_loss_seen = True
        setup_event("setup.first_step", kind="span", ts=self._first_fit_at,
                    dur=time.monotonic() - self._first_fit_at, step=step)
        return False

    def fit(
        self,
        batches: Iterator[PyTree],
        num_steps: int,
        log_every: int = 0,
    ) -> Dict[str, float]:
        """Train for ``num_steps`` batches; returns mean metrics (the
        reference's returned-timings contract, aggregated).

        The per-step loop keeps one step in flight (see
        :meth:`MPI_PS.step`): ``opt.step`` launches step n and waits for
        step n-1, whose loss is then fetched while the device runs step
        n. The loop drains at return: ``final_loss`` is the LAST step's
        loss, a float, so a call is that many completed steps.
        ``log_every`` prints the loss of the step it names, one wait for
        the device every ``log_every`` steps; a checkpoint waits through
        ``state_dict``. The mean of ``host_ahead`` among the returned
        metrics is the share of steps that found the device still busy
        when the next program was already queued."""
        t0 = time.perf_counter()
        if self._first_fit_at is None:
            self._first_fit_at = time.monotonic()
        awaiting = not self._first_loss_seen  # the time to the first step
        last_loss = None
        launched = None  # (step, loss) of the step whose loss is not fetched
        done = 0
        while done < num_steps:
            # telemetry.span: does nothing while the recorder is off
            if self.scan_chunk > 1 and num_steps - done >= self.scan_chunk:
                with span("trainer.step_chunk",
                          step=self.step_count + self.scan_chunk,
                          n_steps=self.scan_chunk) as attrs:
                    chunk = [next(batches) for _ in range(self.scan_chunk)]
                    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *chunk)
                    losses, data = self.opt.run_steps(self.loss_fn, stacked)
                    last_loss = float(losses[-1])
                    self.metrics.add(data)
                    done += self.scan_chunk
                    self.step_count += self.scan_chunk
                    if attrs is not None:
                        attrs["loss"] = last_loss
                if awaiting:
                    awaiting = self._first_loss(self.step_count)
            else:
                with span("trainer.step", step=self.step_count + 1):
                    with span("trainer.data"):
                        batch = next(batches)
                    loss, data = self.opt.step(loss_fn=self.loss_fn,
                                               batch=batch)
                    # step has waited for the step before: its loss is ready
                    if launched is not None:
                        last_loss = self._fetch_loss(*launched)
                        if awaiting:
                            awaiting = self._first_loss(launched[0])
                    self.metrics.add(data)
                    done += 1
                    self.step_count += 1
                    launched = (self.step_count, loss)
            log_now = log_every and done % log_every == 0
            if launched is not None and (log_now or done == num_steps):
                # what the caller asked for now: wait for this step
                last_loss = self._fetch_loss(*launched)
                if awaiting:
                    awaiting = self._first_loss(launched[0])
                launched = None
            if log_now:
                rate = done / (time.perf_counter() - t0)
                print(f"step {self.step_count}: loss={last_loss:.4f} "
                      f"({rate:.1f} steps/s)")
            # interval crossing, not modulo: scan_chunk may not divide
            # checkpoint_every
            if (self.ckpt is not None
                    and self.step_count - self._last_saved_step >= self.checkpoint_every):
                self.save()
        if self.ckpt is not None and self.step_count != self._last_saved_step:
            self.save()
        out = self.metrics.mean()
        out["final_loss"] = last_loss
        out["wall_time"] = time.perf_counter() - t0
        out["steps_per_sec_overall"] = num_steps / out["wall_time"]
        return out
