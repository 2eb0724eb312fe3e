"""Read-tier smoke gate (make read-smoke, in the default `make test` path).

Five checks, each a hard assert:

1. **coalescing** — a burst of identical-version delta requests through
   the network read tier is served from ONE encode (coalesce_hits fires,
   the delta codec ran once);
2. **admission shedding + retry** — with a tiny admission depth, a
   concurrent burst trips ``reads_shed``, and every
   :class:`~pytorch_ps_mpi_tpu.serving.ServingReader` still completes by
   honoring the retry-after replies (shed-then-retry);
3. **delta == full bit-exactness** — a reader that tracked versions via
   deltas holds bit-identical bytes to a fresh full read;
4. **ring ageout fallback** — a reader whose base version left the ring
   gets a full snapshot (counted in ``ring_ageouts``), never an error;
5. **publish overhead** — the armed read tier's per-publish cost
   (snapshot ring put) stays ≤5% of the transport publish itself, so
   arming the tier cannot blow the standing telemetry budget.
"""

from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check(name: str, cond: bool, detail: str = "") -> None:
    status = "ok" if cond else "FAIL"
    print(f"  [{status}] {name}" + (f" — {detail}" if detail else ""))
    if not cond:
        raise SystemExit(f"read_smoke: {name} failed ({detail})")


def main() -> int:
    from pytorch_ps_mpi_tpu.parallel.dcn import _flatten, _unflatten
    from pytorch_ps_mpi_tpu.serving import ServingCore, ServingReader
    from pytorch_ps_mpi_tpu.serving.net import ReadClient

    template = {"w0": np.zeros((40_000,), np.float32),
                "w1": np.zeros((9_000,), np.float32)}
    serving_kw = {"ring": 4, "admission_depth": 2, "retry_after_s": 0.01,
                  "delta_bucket_mb": 0.05}
    cfg = {"read_port": 0, "serving_kw": serving_kw}
    core = ServingCore(None, cfg, template=template)
    rng = np.random.RandomState(0)
    flat_v1 = rng.randn(49_000).astype(np.float32)
    core.publish(flat=flat_v1.copy())

    # -- 1. coalescing under a burst of identical-version reads -----------
    n_burst = 12
    readers = [ServingReader("127.0.0.1", core.read_port, template,
                             serving_kw=serving_kw) for _ in range(n_burst)]
    for r in readers:
        r.read_params()  # everyone now holds v1
    flat_v2 = flat_v1.copy()
    flat_v2[rng.choice(49_000, 100, replace=False)] += 0.5
    core.publish(flat=flat_v2.copy())
    barrier = threading.Barrier(n_burst)

    def delta_read(r):
        barrier.wait()
        r.read_params()

    threads = [threading.Thread(target=delta_read, args=(r,))
               for r in readers]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    s = core.serving_snapshot()
    check("coalescing: one encode fans out",
          s["reads_delta"] == n_burst
          and s["coalesce_hits"] == n_burst - 1,
          f"delta_reads={s['reads_delta']} coalesce={s['coalesce_hits']}")
    check("delta saves bytes", s["delta_bytes_saved"] > 0,
          f"saved={s['delta_bytes_saved']}")

    # -- 2. admission shed fires at the configured depth, retry succeeds --
    shed_before = s["reads_shed"]
    n_storm = 24
    errs = []
    barrier2 = threading.Barrier(n_storm)

    def storm_read(r):
        try:
            barrier2.wait()
            r.read_params()
        except Exception as e:
            errs.append(repr(e))

    new_readers = [ServingReader("127.0.0.1", core.read_port, template,
                                 serving_kw=serving_kw)
                   for _ in range(n_storm - n_burst)]
    all_readers = readers + new_readers
    threads = [threading.Thread(target=storm_read, args=(r,))
               for r in all_readers]
    # force every request to do real work (full read): a fresh version
    # nobody holds, too far for some, plus brand-new readers with no base
    flat_v3 = flat_v2.copy()
    flat_v3[:200] -= 0.25
    core.publish(flat=flat_v3.copy())
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    s = core.serving_snapshot()
    check("no reader errored through the storm", not errs, "; ".join(errs))
    check("admission shed fired (depth 2, storm of 24)",
          s["reads_shed"] > shed_before,
          f"shed={s['reads_shed']}")
    shed_retries = sum(r.shed_retries for r in all_readers)
    check("shed readers retried to completion", shed_retries > 0,
          f"shed_retries={shed_retries}")

    # -- 3. delta-tracked state is bit-exact vs a full read ---------------
    tracked = readers[0]
    tracked.read_params()
    fresh = ServingReader("127.0.0.1", core.read_port, template,
                          serving_kw=serving_kw, want_delta=False)
    fresh.read_params()
    check("delta read == full read, bit for bit",
          tracked.version == fresh.version
          and np.array_equal(tracked._flat.view(np.uint32),
                             fresh._flat.view(np.uint32)),
          f"versions {tracked.version}/{fresh.version}")
    check("tracked reader used deltas", tracked.delta_reads >= 1,
          f"delta_reads={tracked.delta_reads}")

    # -- 4. ring ageout -> full-snapshot fallback -------------------------
    stale = ServingReader("127.0.0.1", core.read_port, template,
                          serving_kw=serving_kw)
    stale.read_params()  # holds the current version
    for i in range(serving_kw["ring"] + 2):  # push it out of the ring
        bump = flat_v3.copy()
        bump[0] = float(i)
        core.publish(flat=bump)
        flat_v3 = bump
    age_before = core.serving_snapshot()["ring_ageouts"]
    stale.read_params()
    s = core.serving_snapshot()
    check("aged-out base falls back to a full snapshot",
          s["ring_ageouts"] == age_before + 1
          and stale.full_reads == 2,
          f"ageouts={s['ring_ageouts']} full={stale.full_reads}")
    check("fallback is current",
          np.array_equal(stale._flat.view(np.uint32),
                         flat_v3.view(np.uint32)))
    for r in all_readers:
        r.close()
    fresh.close()
    stale.close()

    core.close()

    # -- 5. armed publish overhead <= 5% of the transport publish ---------
    from pytorch_ps_mpi_tpu.parallel.dcn import ShmPSServer

    big = {"w": np.zeros((2_000_000,), np.float32)}  # 8 MB snapshot
    name = f"/psq_read_smoke_{os.getpid()}"
    srv = ShmPSServer(name, num_workers=1, template=big)
    score = ServingCore(srv, {"serving": True}, monitors=False)
    flat = np.random.RandomState(1).randn(2_000_000).astype(np.float32)
    n_pub = 30
    t0 = time.perf_counter()
    for _ in range(n_pub):
        srv.publish_flat(flat)
    t_pub = time.perf_counter() - t0
    store = score._stores[score.default_tenant]
    t0 = time.perf_counter()
    for i in range(n_pub):
        store.put(srv.version + i + 1, flat)
    t_put = time.perf_counter() - t0
    overhead = t_put / max(t_pub, 1e-9)
    check("snapshot-ring put <= 5% of transport publish",
          overhead <= 0.05,
          f"publish {t_pub / n_pub * 1e3:.3f} ms, ring put "
          f"{t_put / n_pub * 1e3:.4f} ms ({overhead:.2%})")
    srv.close()

    print("read_smoke: all checks green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
