"""Shared train-step timing recipe for the model-family benches.

One implementation of the step measurement (bert_bench and gpt_bench
need the same thing): jit the step, pull measured FLOPs from XLA cost
analysis, time it (``utils/devtime.timed``: the host clock around
``block_until_ready``), and return the common emit fields.
"""

from __future__ import annotations

import time

import jax

from pytorch_ps_mpi_tpu.utils.devtime import (
    compiled_flops,
    peak_flops_for,
    timed,
)


def step_timing_fields(train_step, params, state, batch,
                       reps: int = 5) -> dict:
    """Measure ``train_step(params, state, batch) -> (params, state, loss)``
    and return the shared metric fields (steps/sec in ``value``)."""
    fn = jax.jit(train_step)
    t0 = time.perf_counter()
    compiled = fn.lower(params, state, batch).compile()
    compile_s = round(time.perf_counter() - t0, 2)
    flops = compiled_flops(compiled)
    step_s = timed(lambda: fn(params, state, batch), reps=reps)
    dev = jax.devices()[0]
    return {
        "value": round(1.0 / step_s, 3),
        "unit": "steps/sec",
        "step_ms": round(step_s * 1e3, 2),
        "flops_per_step": flops,
        "compile_s": compile_s,
        "mfu": round(flops / (step_s * peak_flops_for()), 4),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
    }
