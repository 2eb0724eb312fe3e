"""Chunked selective scan: the recurrence of a Mamba layer, forward and
backward, without ever holding ``[T, E, N]``.

With ``x, dt [b, T, E]``, ``A [E, N]``, ``B, C [b, T, N]``, ``D [E]``::

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * x_t) B_t^T      (s_0 = 0, [E, N])
    y_t = s_t C_t + D * x_t

The state of T = 8,192 positions at E = 5,120, N = 16 would be 2.7 GB in
float32; here time is cut into chunks of ``CHUNK`` steps:

- **forward**: a ``lax.scan`` over the chunks carries the state
  (``[b, N, E]``: E on the lanes, the N = 16 rows of a state on the
  sublanes); inside a chunk a second scan takes the steps one by one and
  keeps only ``y_t``. What the backward pass keeps of the forward is the
  state at each chunk's START (``T / CHUNK`` states: 42 MB at the sizes
  above) and the inputs.
- **backward** (``custom_vjp`` of one chunk): the chunk's states are
  computed again from its start and kept (``[CHUNK, b, N, E]``: 21 MB),
  the adjoint states ``G_t = C_t dy_t^T + exp(dt_{t+1} A) G_{t+1}`` come
  from one scan in reverse, and every gradient is then a reduction over
  the two stacks in bulk: with ``z = dt A``, ``dz_t = G_t s_{t-1}
  exp(z_t)``; ``d dt = sum_n dz A + du x``, ``dA = sum_t dz dt``,
  ``du = sum_n G B`` (``u = dt x``), ``dx = du dt + D dy``,
  ``dB = sum_e G u``, ``dC = sum_e s dy``. The state handed to the next
  chunk carries its cotangent the other way.

Everything is float32 whatever the inputs' dtype (``state_dtype`` exists
for the one measurement that shows a bf16-carried state failing the
benchmark's comparison). The scan stays XLA's: its scope in a model
(``ssm.scan``) is what a trace times. T need not divide by the chunk:
the tail is padded with ``dt = 0``, which leaves the state as it is.

``CHUNK``: measured on a v5e at T = 8,192, E = 5,120, N = 16, one row,
forward / forward + backward in ms (``PERF.md`` section 6, PR 31, run
K1): 64 steps 6.98 / 23.42; 16 steps 7.10 / 27.45, 128 steps 6.26 /
31.32 and 256 steps 6.90 / 35.12 (the inner loops unrolled 8, 4 and 8
times; at 64 steps unrolling 8 or 16 times reads 6.71 / 25.04 and 7.34
/ 27.38, so the loops stay rolled). A step of the forward loop costs
0.8 us: the compiler keeps the state and the chunk's blocks in fast
memory. Longer chunks lose in the backward pass's two stacks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.telemetry.recorder import setup_event

CHUNK = 64


def _decay(dt_t, a_t):
    """``exp(dt_t * A)`` of one step: ``dt_t [b, E]``, ``a_t [N, E]``."""
    return jnp.exp(dt_t[:, None, :] * a_t)


def _steps(state_dtype, a_t, s0, xs, keep):
    """The chunk's steps one by one from ``s0`` over ``xs = (x, dt, B,
    ...)``: (last state, the stack of what ``keep(s_t, t)`` returns)."""
    def step(s, t):
        x_t, dt_t, b_t = t[:3]
        s = (_decay(dt_t, a_t) * s.astype(jnp.float32)
             + (dt_t * x_t)[:, None, :] * b_t[:, :, None]).astype(state_dtype)
        return s, keep(s.astype(jnp.float32), t)

    return jax.lax.scan(step, s0, xs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunk(state_dtype, a_t, s0, x, dt, b_in, c_in):
    """One chunk, time-major: ``x, dt [L, b, E]``, ``b_in, c_in
    [L, b, N]``, ``s0 [b, N, E]`` -> (``s_L``, ``y [L, b, E]`` without
    the ``D x`` term)."""
    return _steps(state_dtype, a_t, s0, (x, dt, b_in, c_in),
                  lambda s_t, t: jnp.sum(s_t * t[3][:, :, None], axis=1))


def _chunk_fwd(state_dtype, a_t, s0, x, dt, b_in, c_in):
    return (_chunk(state_dtype, a_t, s0, x, dt, b_in, c_in),
            (a_t, s0, x, dt, b_in, c_in))


def _chunk_bwd(state_dtype, res, g):
    a_t, s0, x, dt, b_in, c_in = res
    g_end, dy = g
    _, s = _steps(state_dtype, a_t, s0, (x, dt, b_in),
                  lambda s_t, t: s_t)
    s_prev = jnp.concatenate([s0.astype(jnp.float32)[None], s[:-1]])

    def back(h, t):     # h = exp(dt_{t+1} A) G_{t+1}; for the last step g_end
        dt_t, c_t, dy_t = t
        g_t = c_t[:, :, None] * dy_t[:, None, :] + h
        return _decay(dt_t, a_t) * g_t, g_t

    ds0, g_all = jax.lax.scan(back, g_end.astype(jnp.float32),
                              (dt, c_in, dy), reverse=True)
    dz = g_all * s_prev * jnp.exp(dt[:, :, None, :] * a_t)
    du = jnp.sum(g_all * b_in[..., None], axis=2)
    return (jnp.sum(dz * dt[:, :, None, :], axis=(0, 1)),          # dA^T
            ds0.astype(s0.dtype),
            du * dt,                                                # dx
            jnp.sum(dz * a_t, axis=2) + du * x,                     # d dt
            jnp.sum(g_all * (dt * x)[:, :, None, :], axis=3),       # dB
            jnp.sum(s * dy[:, :, None, :], axis=3))                 # dC


_chunk.defvjp(_chunk_fwd, _chunk_bwd)


def selective_scan(x, dt, a, b_in, c_in, d, *, chunk: int = CHUNK,
                   state_dtype=jnp.float32):
    """``y [b, T, E]`` float32 of the recurrence in the module docstring."""
    rows, steps, width = x.shape
    n = a.shape[1]
    chunk = min(chunk, steps)
    chunks = -(-steps // chunk)
    # once a trace (the set-up log's row)
    setup_event("ssm.scan_plan", T=steps, chunk=chunk, chunks=chunks,
                E=width, N=n, state_bytes_carried=4 * chunks * rows * n * width)

    f32 = lambda v: v.astype(jnp.float32)
    x, dt, b_in, c_in, a_t = f32(x), f32(dt), f32(b_in), f32(c_in), f32(a).T

    def by_chunk(v):    # [b, T, w] -> [chunks, chunk, b, w], dt = 0 in the tail
        v = jnp.pad(v, ((0, 0), (0, chunks * chunk - steps), (0, 0)))
        return v.reshape(rows, chunks, chunk, -1).transpose(1, 2, 0, 3)

    def one(s, xs):
        return _chunk(state_dtype, a_t, s, *xs)

    _, y = jax.lax.scan(one, jnp.zeros((rows, n, width), state_dtype),
                        tuple(map(by_chunk, (x, dt, b_in, c_in))))
    y = y.transpose(2, 0, 1, 3).reshape(rows, chunks * chunk, width)[:, :steps]
    return y + f32(d) * x
