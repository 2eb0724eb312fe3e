"""Homomorphic-aggregation smoke gate (make agg-smoke, in the default
`make test` path).

Three checks, each a hard assert:

1. **one decode per publish** — a real 2-process shm sync-barrier run
   over the top-k wire must arm aggregation (``agg_mode == 1.0``),
   report ``decodes_per_publish == 1.0`` in the canonical metrics AND
   the ``/health`` fleet rollup, account every push, and still train
   (loss improves);
2. **exactness on the wire** — the aggregated round the serve loop
   computes equals decode-then-sum on the same payload bytes to f32
   tolerance (exact-algebra codec, real ``CodecWire`` buffers);
3. **automatic fallback** — the same run with ``agg: "off"`` keeps the
   legacy decode-sum path (``agg_mode == 0.0``, ~world decodes per
   publish), so the knob is a real switch, not a label.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def check(name: str, cond: bool, detail: str = "") -> None:
    status = "ok" if cond else "FAIL"
    print(f"  [{status}] {name}" + (f" — {detail}" if detail else ""))
    if not cond:
        raise SystemExit(f"agg_smoke: {name} failed ({detail})")


def run_serve(agg: str):
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel import dcn
    from pytorch_ps_mpi_tpu.parallel.async_train import (
        join_workers,
        make_problem,
        serve,
        spawn_worker,
    )

    cfg = {
        "model": "mlp", "model_kw": {"features": (16, 4)},
        "in_shape": (8,), "batch": 32, "seed": 5,
        "codec": "topk", "codec_kw": {"fraction": 0.25},
        "optim": "sgd", "hyper": {"lr": 0.05}, "steps": 8,
        "frame_check": True, "health": True, "agg": agg,
    }
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_agg_smoke_{os.getpid()}_{agg}"
    server = dcn.ShmPSServer(
        name, num_workers=2, template=params0, max_staleness=10**9,
        code=get_codec("topk", fraction=0.25), frame=True)
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(2)]
        _, m = serve(server, cfg, total_grads=0, total_received=16,
                     sync_barrier=True, timeout=180.0)
        codes = join_workers(procs, timeout=120)
    finally:
        server.close()
    check(f"workers exited cleanly (agg={agg})", codes == [0, 0],
          str(codes))
    return m


def main() -> int:
    # -- 1. one decode per publish (the headline) -------------------------
    m = run_serve("auto")
    check("aggregation armed", m["agg_mode"] == 1.0)
    check("ONE decode per published version",
          m["decodes_per_publish"] == 1.0,
          f"decodes_per_publish={m['decodes_per_publish']}")
    check("no fallbacks", m["agg_fallbacks"] == 0.0)
    check("every push accounted",
          m["grads_received"] == 16 and m["applied"] == 16,
          f"received={m['grads_received']} applied={m['applied']}")
    check("training converged through the compressed domain",
          m["loss_final"] < m["loss_initial"],
          f"{m['loss_initial']:.3f} -> {m['loss_final']:.3f}")
    fleet = m["health"]["fleet"]
    check("/health carries the rollup",
          fleet["agg_mode"] == 1.0
          and fleet["decodes_per_publish"] == 1.0,
          json.dumps({k: fleet[k] for k in
                      ("agg_mode", "decodes_per_publish")}))

    # -- 2. wire-level exactness ------------------------------------------
    import jax

    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.dcn import CodecWire

    template = {"w": np.zeros((512, 16), np.float32),
                "b": np.zeros(33, np.float32)}
    wire = CodecWire(get_codec("topk", fraction=0.1), template)
    rng = np.random.RandomState(0)
    grads = [{"w": rng.randn(512, 16).astype(np.float32),
              "b": rng.randn(33).astype(np.float32)} for _ in range(3)]
    bufs = [np.copy(wire.encode_to_bytes(g)) for g in grads]
    ref = None
    for b in bufs:
        d = wire.decode_from_bytes(b)
        ref = d if ref is None else jax.tree.map(np.add, ref, d)
    agg = wire.agg_begin()
    for b in bufs:
        agg.fold(b)
    out = agg.finalize()
    err = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
              for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)))
    check("wire aggregate == decode-sum (exact algebra)", err < 1e-5,
          f"maxdiff={err:.2e}")

    # -- 3. the knob is real ----------------------------------------------
    m_off = run_serve("off")
    check("agg=off keeps the decode path",
          m_off["agg_mode"] == 0.0 and m_off["decodes_per_publish"] > 1.5,
          f"decodes_per_publish={m_off['decodes_per_publish']}")
    check("both paths trained comparably",
          m_off["loss_final"] < m_off["loss_initial"])

    print("agg_smoke: all checks green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
