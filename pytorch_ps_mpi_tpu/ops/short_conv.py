"""The gated short convolution of the ``lfm2`` family (LFM2-24B-A2B's
``conv`` layers): two input-dependent gates around a causal depthwise
convolution of a few taps.

For ``[B | C | u] = bcu [b, T, 3 d]`` (the operator's input projection,
three ``d``-wide parts side by side) and ``taps [d, K]`` (one tap tuple a
channel, no bias):

    g_t   = B_t * u_t
    c_t   = sum_{j=0..K-1} taps[:, j] * g_{t-(K-1)+j}     (g = 0 before the
                                                            row's first position)
    out_t = C_t * c_t

Rows never see each other: the ``K - 1`` zero positions are put before
EVERY row. Plain ``jax.numpy`` under the scope ``conv.mix``, differentiable
by JAX as it stands, for any ``T`` (also ``T < K``) and any ``K``; the
gates and the tap sum are float32 and the result is rounded once. No
kernel: by its bytes the operator is ~1.2 ms a layer and step at
``[2, 8192, 2048]`` bf16 (4 arrays forward, 7 backward), and what XLA
makes of it on the chip is read by ``conv.mix_ms`` / ``conv.mix_roofline_pct``
(PERF.md section 3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.telemetry.recorder import setup_event


def record_plan(bcu, taps) -> None:
    """One ``conv.plan`` row in the set-up log a trace: the rows, their
    length, the channels, the taps, what one forward pass must read
    (``bcu``) and write (the result), and who moves it (``jnp``: XLA's
    own fusions)."""
    rows, steps, wide = bcu.shape
    item = bcu.dtype.itemsize
    setup_event("conv.plan", rows=int(rows), T=int(steps),
                channels=int(wide // 3), taps=int(taps.shape[1]),
                bytes_read=int(bcu.size * item),
                bytes_written=int(bcu.size // 3 * item), mover="jnp")


def gated_short_conv(bcu, taps):
    """``bcu [b, T, 3 d]``, ``taps [d, K]`` -> ``C * conv(B * u) [b, T,
    d]`` in ``bcu``'s dtype."""
    if bcu.shape[-1] != 3 * taps.shape[0]:
        raise ValueError(f"bcu {bcu.shape} against taps {taps.shape}: the "
                         "last dimension holds B, C and u side by side")
    record_plan(bcu, taps)
    steps, k = bcu.shape[1], taps.shape[1]
    with jax.named_scope("conv.mix"):
        b_gate, c_gate, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
        g = jnp.pad(b_gate * u, ((0, 0), (k - 1, 0), (0, 0)))
        w = taps.astype(jnp.float32)
        c = sum(w[:, j] * g[:, j:j + steps] for j in range(k))
        return (c_gate * c).astype(bcu.dtype)
