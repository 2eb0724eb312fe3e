"""tpu-ps: a TPU-native distributed-training framework.

Rebuilt from scratch on JAX/XLA/pjit with the capabilities of the reference
``stsievert/pytorch_ps_mpi`` (a mpi4py parameter-server layer for PyTorch,
see ``/root/reference``):

- a drop-in optimizer-style API (``MPI_PS`` / ``SGD`` / ``Adam``, mirroring
  the reference's public surface, reference ``__init__.py:1``) whose ``step``
  aggregates gradients across workers,
- two aggregation topologies (decentralized allgather-sum — the reference's
  live path, ``ps.py:75,140-161`` — and leader-PS gather+broadcast,
  ``mpi_comms.py:60-133``),
- an asynchronous bounded-staleness mode (AsySG-InCon, reference README),
- a pluggable gradient-codec interface (reference ``codings`` hook,
  ``ps.py:94,166``) with identity / top-k / random-k / int8 / sign codecs,
- fused SGD + Adam update rules (reference ``ps.py:195-261``),
- the per-step timing/bytes metrics schema (reference ``ps.py:116-148``).

Everything on-device runs under ``jax.jit``/``shard_map`` over a
``jax.sharding.Mesh``; collectives ride ICI (``psum``/``all_gather``/
``ppermute``) instead of MPI over Ethernet.
"""

import sys as _sys
import time as _time

# first line to last of this import, as the set-up log's first row; the
# log cannot be imported before the package is
_T0, _JAX_IN = _time.monotonic(), "jax" in _sys.modules

from pytorch_ps_mpi_tpu.ps import MPI_PS, Adafactor, Adam, SGD

__all__ = ["MPI_PS", "Adafactor", "Adam", "SGD"]
__version__ = "0.1.0"

from pytorch_ps_mpi_tpu.telemetry import setup_event as _setup_event

_setup_event("setup.import", kind="span", ts=_T0,
             dur=_time.monotonic() - _T0, jax_already_imported=_JAX_IN)
