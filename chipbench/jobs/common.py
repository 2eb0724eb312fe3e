"""What both job drivers need: the compile counter, the device report,
the clock control."""

from __future__ import annotations

import json
import time


def say(**row) -> None:
    """One JSON line on standard output, before the final one."""
    print(json.dumps(row, default=str), flush=True)


class CompileCounter:
    """Counts the programs the backend was asked for (compiled or loaded
    from the cache), with the wall time of each request — copied from
    ``chip_smoke.py``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.times.append(time.time())

    @property
    def count(self) -> int:
        return len(self.times)

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


def device_report(devices, peak_bytes: int) -> dict:
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": jax.device_count(), "memory_peak_bytes": int(peak_bytes)}


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device; 0 where the backend keeps
    no statistics (XLA:CPU)."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def clock_control(peak_flops: float) -> dict:
    """``chip_smoke.py``'s control: a chain of 64 matmuls of 4096^3 in
    bf16, timed by the host clock around ``block_until_ready`` (best of
    five), must land between half of and the whole published peak. It is
    what licenses every host-clock time this benchmark reports."""
    import jax
    import jax.numpy as jnp

    n, links = 4096, 64
    x = jax.random.normal(jax.random.key(0), (n, n), jnp.bfloat16)
    w = (jax.random.normal(jax.random.key(1), (n, n)) / n ** 0.5
         ).astype(jnp.bfloat16)

    @jax.jit
    def chain(x, w):
        for _ in range(links):
            x = x @ w
        return x

    def once() -> float:
        t0 = time.perf_counter()
        jax.block_until_ready(chain(x, w))
        return time.perf_counter() - t0

    once()
    secs = min(once() for _ in range(5))
    share = links * 2 * n ** 3 / secs / peak_flops
    return {"chain_s": secs, "share_of_peak": share,
            "ok": 0.5 <= share <= 1.0}
