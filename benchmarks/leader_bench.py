"""leader (ZeRO-1 sharded PS) vs allgather (replicated step) — Adam.

The measured case for the leader topology: both modes
move the same gradient bytes over the interconnect (psum and
reduce_scatter+all_gather are the same 2(w-1)/w·n volume), but leader
divides the *update* FLOPs and the optimizer-state memory by world size:

  allgather: every device steps the full model -> w·n update work total,
             3n floats of Adam state per device
  leader:    each device steps its 1/w flat shard -> n update work total,
             3n/w floats of Adam state per device

Run: ``python benchmarks/leader_bench.py [n_elems]`` (default ~11M) on
the devices JAX initialises; leader mode needs a multi-device mesh, so
the run fails below two. For the host-CPU form: ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``. Prints a table +
one JSON line naming the platform.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_ps_mpi_tpu import Adam

REPS = 10


def bench_mode(mode: str, params, grads, code=None):
    """Returns (min step seconds, per-device state bytes, lowering)."""
    opt = Adam(params, lr=1e-3, mode=mode, code=code)
    opt.step(grads=grads)  # compile
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        _, data = opt.step(grads=grads)
        times.append(time.perf_counter() - t0)
    if code is not None:
        print(f"  [{mode}+{type(code).__name__}] lowering="
              f"{data['wire_lowering']} "
              f"wire_bytes/worker={data['wire_bytes_per_worker']/1e6:.1f}MB",
              flush=True)
    # per-device optimizer-state bytes: leader's moments are sharded over
    # the mesh, allgather's replicated on every device
    state_elems = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(tuple(opt.opt_state)[1:])
    )
    world = opt.size
    per_device_state = state_elems * 4 // (world if mode == "leader" else 1)
    return min(times), per_device_state, data["wire_lowering"]


def main():
    world = len(jax.devices())
    if world < 2:
        raise SystemExit(
            f"leader_bench: {world} {jax.default_backend()} device: leader "
            "mode needs a multi-device mesh (>= 2)")
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 11_000_000
    # ~60 tensors like ResNet-18's parameter list
    k = jax.random.key(0)
    sizes = [n // 60] * 59 + [n - 59 * (n // 60)]
    params = {f"p{i}": jnp.zeros((s,), jnp.float32) for i, s in enumerate(sizes)}
    grads = {
        name: jax.random.normal(jax.random.fold_in(k, i), (world,) + p.shape)
        for i, (name, p) in enumerate(params.items())
    }

    t_all, mem_all, _ = bench_mode("allgather", params, grads)
    t_lead, mem_lead, _ = bench_mode("leader", params, grads)

    # the round-4 lowering choice, measured: leader + a weakly-compressing
    # codec (int8, ratio 4 < world 8) takes dense_scatter — decode own
    # payload locally + reduce_scatter — instead of payload all-gather
    from pytorch_ps_mpi_tpu.codecs import get_codec

    # the lowering is world-size dependent (dense_scatter needs ratio <
    # world): key the JSON field by what actually compiled
    t_ds, _, ds_lowering = bench_mode("leader", params, grads,
                                      code=get_codec("int8"))
    t_ag_codec, _, _ = bench_mode("allgather", params, grads,
                                  code=get_codec("int8"))

    print(f"backend={jax.default_backend()} "
          f"device_kind={jax.devices()[0].device_kind!r} world={world} n={n}")
    print("| mode | step ms | adam state bytes/device |")
    print("|---|---|---|")
    print(f"| allgather | {t_all*1e3:.2f} | {mem_all/1e6:.1f} MB |")
    print(f"| leader    | {t_lead*1e3:.2f} | {mem_lead/1e6:.1f} MB |")
    print(
        json.dumps(
            {
                "metric": "adam_11M_leader_vs_allgather_step_speedup",
                "value": round(t_all / t_lead, 3),
                "unit": "x",
                "vs_baseline": round(t_all / t_lead, 3),
                "backend": jax.default_backend(),
                "device_kind": jax.devices()[0].device_kind,
                "devices": world,
                "leader_step_ms": round(t_lead * 1e3, 3),
                "allgather_step_ms": round(t_all * 1e3, 3),
                "state_bytes_per_device_ratio": mem_all / mem_lead,
                f"leader_int8_{ds_lowering}_step_ms": round(t_ds * 1e3, 3),
                "allgather_int8_step_ms": round(t_ag_codec * 1e3, 3),
            }
        )
    )


if __name__ == "__main__":
    main()
