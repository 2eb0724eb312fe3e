"""``ops/selective_scan.py``: the chunked scan with its hand-written
backward pass against the recurrence taken one step at a time and
differentiated by ``jax.grad``, for chunk lengths under, at and over T,
and T not a multiple of the chunk. Float32 on both sides: 1e-5 of the
largest entry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu import telemetry
from pytorch_ps_mpi_tpu.ops.selective_scan import CHUNK, selective_scan

ROWS, T, E, N = 2, 37, 24, 4


def step_by_step(x, dt, a, b_in, c_in, d):
    def step(s, t):
        x_t, dt_t, b_t, c_t = t
        s = (jnp.exp(dt_t[:, :, None] * a) * s
             + (dt_t * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.einsum("ben,bn->be", s, c_t) + d * x_t

    time_major = lambda v: v.transpose(1, 0, 2)
    _, y = jax.lax.scan(step, jnp.zeros((x.shape[0], x.shape[2], a.shape[1])),
                        tuple(map(time_major, (x, dt, b_in, c_in))))
    return y.transpose(1, 0, 2)


@pytest.fixture(scope="module")
def inputs():
    k = jax.random.split(jax.random.key(0), 7)
    return (jax.random.normal(k[0], (ROWS, T, E)),
            jax.nn.softplus(jax.random.normal(k[1], (ROWS, T, E))),
            -jnp.exp(jax.random.normal(k[2], (E, N))),
            jax.random.normal(k[3], (ROWS, T, N)),
            jax.random.normal(k[4], (ROWS, T, N)),
            jax.random.normal(k[5], (E,))), jax.random.normal(k[6], (ROWS, T, E))


def close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


@pytest.mark.parametrize("chunk", [1, 5, 8, 16, 37, 64])
def test_forward_equals_the_recurrence(inputs, chunk):
    args, _ = inputs
    assert close(selective_scan(*args, chunk=chunk), step_by_step(*args))


@pytest.mark.parametrize("chunk", [1, 5, 8, 37, 64])
def test_gradients_equal_the_recurrences(inputs, chunk):
    args, w = inputs
    ours = jax.grad(lambda *z: jnp.sum(w * selective_scan(*z, chunk=chunk)),
                    range(6))(*args)
    theirs = jax.grad(lambda *z: jnp.sum(w * step_by_step(*z)),
                      range(6))(*args)
    for name, a, b in zip(("x", "dt", "A", "B", "C", "D"), ours, theirs):
        assert a.shape == b.shape and close(a, b), name


def test_bf16_inputs_are_taken_to_float32(inputs):
    args, _ = inputs
    x16 = args[0].astype(jnp.bfloat16)
    y = selective_scan(x16, *args[1:])
    assert y.dtype == jnp.float32
    assert close(y, step_by_step(x16.astype(jnp.float32), *args[1:]))


def test_a_state_carried_in_bf16_is_another_result(inputs):
    """What the benchmark's limits must catch: the same scan with its
    state rounded to bf16 after every step is off by far more than the
    float32 one."""
    args, _ = inputs
    exact = step_by_step(*args)
    off = selective_scan(*args, state_dtype=jnp.bfloat16)
    assert not close(off, exact, tol=1e-3)
    assert close(off, exact, tol=5e-2)


def test_the_plan_is_one_recorder_row_a_trace(inputs):
    args, _ = inputs
    rec = telemetry.configure()
    try:
        jax.jit(lambda *z: selective_scan(*z, chunk=8)).lower(*args)
        rows = [e for e in rec.events() if e["name"] == "ssm.scan_plan"]
    finally:
        telemetry.disable()
    assert len(rows) == 1
    row = rows[0]["attrs"]
    assert (row["T"], row["chunk"], row["chunks"], row["E"], row["N"]) == (
        T, 8, 5, E, N)
    assert row["state_bytes_carried"] == 4 * 5 * ROWS * N * E
    assert CHUNK >= 8
