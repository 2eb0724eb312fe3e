"""Device timing: the host clock around ``block_until_ready``.

JAX dispatch is asynchronous; ``jax.block_until_ready`` waits for the
device. ``chip_smoke.py`` phase (a) checks that on every run with a
known-FLOPs control (a chain of 4096³ bf16 matmuls must land between
half of and the whole published peak), which is what licenses every
number taken with :func:`timed`.
"""

from __future__ import annotations

import time
from typing import Any, Callable

import jax

# bf16 peak FLOP/s per JAX device, keyed by device_kind substring
# (lowercased) — the single table every benchmark's MFU is reported
# against (v3 entry is per core; 2 cores/chip). Source: Google Cloud
# TPU documentation, per-generation system architecture pages.
PEAK_FLOPS_BF16 = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 61.25e12),
    ("v2", 22.5e12),
]


def device_kind() -> str:
    return jax.devices()[0].device_kind


def peak_flops_for(kind: str | None = None) -> float:
    """Published bf16 peak for ``kind`` (default: this process's first
    device). A device that is not in the table is an error, not a 0.0."""
    kind = kind if kind is not None else device_kind()
    for sub, peak in PEAK_FLOPS_BF16:
        if sub in kind.lower():
            return peak
    raise ValueError(
        f"no published peak for device_kind {kind!r}; add it to "
        "PEAK_FLOPS_BF16 with its source"
    )


def safe_ratio(num: float, den: float) -> float:
    """num/den, or 0.0 when the denominator is 0 (an empty run in a
    sweep reads as "not measured" instead of crashing it)."""
    return num / den if den > 0 else 0.0


def timed(call: Callable[[], Any], reps: int = 5) -> float:
    """Min-of-``reps`` wall seconds of ``call()`` with its result awaited
    by ``block_until_ready``. One untimed call first compiles and warms."""
    def once() -> float:
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        return time.perf_counter() - t0

    once()
    return min(once() for _ in range(reps))


def compiled_flops(compiled) -> float:
    """FLOPs of a compiled program from XLA's cost analysis — the
    numerator of every measured-FLOPs MFU."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def codec_roundtrip_seconds(code, shape, dtype, k: int = 8,
                            phase: str = "roundtrip") -> float:
    """Device seconds for one ``encode`` + ``decode`` of a codec at
    ``shape`` — a k-iteration fused scan whose iterations carry a
    numerically-negligible data dependence (``+ decoded * 1e-30``) AND
    loop-carry the codec state, so XLA can neither hoist the codec out of
    the loop nor dead-code the stateful half (PowerSGD's warm-started Q,
    error-feedback residuals, adaptive thresholds). A loop-invariant
    state once let the best-compressing codec measure 0.0 ms at 132M —
    and steady-state cost with an evolving Q is what a training step
    actually pays anyway. The one shared implementation of the codec
    timing recipe (bench consumers must not re-roll it).

    ``phase='encode'`` times the encode half alone (decode cost is then
    the roundtrip minus this). The carry dependence switches to a full
    reduction over every payload leaf — a first-element dependence would
    let XLA slice-fuse away most of the encode, while a jnp.sum forces
    full payload materialization at the cost of one extra payload read
    per iteration (negligible: the encode itself writes those bytes)."""
    import jax.numpy as jnp

    if phase not in ("roundtrip", "encode"):
        raise ValueError(f"phase={phase!r}: expected 'roundtrip' or 'encode'")
    g = jax.random.normal(jax.random.key(0), shape, dtype)
    st = code.init_state(shape, dtype)
    rng = jax.random.key(1) if code.needs_rng else None

    @jax.jit
    def loop(g, st):
        def body(carry, _):
            g_c, st_c = carry
            payload, st_new = code.encode(g_c, st_c, rng)
            if phase == "encode":
                dep = sum(
                    jnp.sum(leaf).astype(g_c.dtype)
                    for leaf in jax.tree.leaves(payload)
                )
            else:
                dep = code.decode(payload, shape, dtype).astype(g_c.dtype)
            g_next = g_c + dep * jnp.asarray(1e-30, g_c.dtype)
            return (g_next, st_new), None

        (out, st_out), _ = jax.lax.scan(body, (g, st), None, length=k)
        # return the state too: keeping st_out live in the program
        # output closes the last dead-code-elimination door for
        # state-only compute
        return out, st_out

    return timed(lambda: loop(g, st), reps=3) / k
