"""Scaling-efficiency benchmark (BASELINE.json north-star metric).

Two layers of host-CPU evidence (the script pins the CPU backend: it
measures the program's structure, not a chip):

1. **In-process sweep**: ResNet-18 data-parallel train step over 1→8
   virtual CPU devices, per-worker batch FIXED (weak scaling), with a
   per-step comm/compute breakdown from a real trace
   (``profiled_device_split``). Virtual devices share the host's fixed
   cores, so falling steps/s reflects compute CONTENTION, not collective
   cost — the transferable signal is the comm-time share column, which
   is what actually grows with world size on hardware.
2. **Cross-process (DCN) point**: the same step over an 8-device mesh
   split across 2 coordinated OS processes (``launch.py`` +
   ``jax.distributed``, 4 local devices each) — every psum crosses a
   real process boundary (loopback here; the identical code path is the
   multi-host pod's DCN hop).

Run: ``python benchmarks/scaling_bench.py [--steps 6] [--skip-dcn]``.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_platforms", "cpu")

from pytorch_ps_mpi_tpu import SGD
from pytorch_ps_mpi_tpu.mesh import make_mesh
from pytorch_ps_mpi_tpu.models import ResNet18
from pytorch_ps_mpi_tpu.utils.tracing import profiled_device_split

PER_WORKER_BATCH = 32


def make_problem(world: int):
    mesh = make_mesh(devices=jax.devices()[:world])
    model = ResNet18(num_classes=10, small_inputs=True)
    batch = PER_WORKER_BATCH * world
    x = jax.random.normal(jax.random.key(1), (batch, 32, 32, 3))
    y = jax.random.randint(jax.random.key(2), (batch,), 0, 10)
    params = jax.jit(model.init)(jax.random.key(0), x[:1])

    from pytorch_ps_mpi_tpu.data import cross_entropy_loss

    def loss_fn(p, b):
        xb, yb = b
        return cross_entropy_loss(model.apply(p, xb), yb)

    opt = SGD(params, mesh=mesh, lr=0.05, average=True)
    return opt, loss_fn, (x, y)


def run_world(world: int, steps: int) -> dict:
    opt, loss_fn, batch = make_problem(world)
    opt.step(loss_fn=loss_fn, batch=batch)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(steps):
        _, data = opt.step(loss_fn=loss_fn, batch=batch)
    wall = time.perf_counter() - t0
    # one traced step for the comm/compute split (device-op durations)
    _, split = profiled_device_split(
        lambda: opt.step(loss_fn=loss_fn, batch=batch)
    )
    busy = split["device_busy_s"]
    return {
        "workers": world,
        "processes": 1,
        "per_worker_batch": PER_WORKER_BATCH,
        "steps_per_sec": round(steps / wall, 4),
        "step_ms": round(1e3 * wall / steps, 2),
        "comm_ms_per_dev": round(split["comm_s"] * 1e3, 2),
        "compute_ms_per_dev": round(split["compute_s"] * 1e3, 2),
        "comm_share": round(split["comm_s"] / busy, 4) if busy > 0 else 0.0,
        "wire_lowering": data["wire_lowering"],
        "wire_bytes_per_worker": data["wire_bytes_per_worker"],
    }


def run_dcn_point(steps: int, n_procs: int = 2,
                  timeout: float = 1200.0) -> dict | None:
    """8 devices across ``n_procs`` coordinated processes via launch.py
    (4x2 exercises a LARGER process topology on the same runtime path —
    every psum crosses 3 process boundaries instead of 1).

    Children write to temp FILES, not pipes — a rank blocked on a full
    unread pipe while the other rank waits in a collective would
    deadlock both until the timeout. A hang (TimeoutExpired) degrades to
    an error row so the extrapolation row still prints."""
    import tempfile

    dev_per_proc = 8 // n_procs
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={dev_per_proc}"
    )
    env.pop("JAX_PLATFORMS", None)
    logs = [tempfile.NamedTemporaryFile("w+", suffix=f".rank{r}.log",
                                        delete=False)
            for r in range(n_procs)]
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "pytorch_ps_mpi_tpu.launch",
             "--platform", "cpu",
             "--coordinator", f"localhost:{port}",
             "--num-processes", str(n_procs), "--process-id", str(r),
             os.path.join(REPO, "benchmarks", "scaling_worker.py"),
             str(PER_WORKER_BATCH), str(steps)],
            cwd=REPO, env=env, text=True,
            stdout=logs[r], stderr=subprocess.STDOUT,
        )
        for r in range(n_procs)
    ]
    deadline = time.time() + timeout
    timed_out = False
    try:
        for p in procs:
            try:
                p.wait(timeout=max(1.0, deadline - time.time()))
            except subprocess.TimeoutExpired:
                timed_out = True
                break
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    outs = []
    for f in logs:
        f.flush()
        f.seek(0)
        outs.append(f.read())
        f.close()
        os.unlink(f.name)
    if timed_out:
        # every rank's tail: the rank that actually crashed pre-collective
        # is usually not rank 0 or N-1
        tails = " / ".join(f"r{r}:{o[-160:]!r}" for r, o in enumerate(outs))
        return {"workers": 8, "processes": n_procs,
                "error": f"timeout after {timeout}s; rank logs: {tails}"}
    for r, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            return {"workers": 8, "processes": n_procs,
                    "error": f"rank {r} rc={p.returncode}: {out[-400:]}"}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("SCALING_ROW "):
                return json.loads(line[len("SCALING_ROW "):])
    return {"workers": 8, "processes": n_procs, "error": "no row emitted"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--skip-dcn", action="store_true")
    args = ap.parse_args()

    base = None
    for world in (1, 2, 4, 8):
        row = run_world(world, args.steps)
        if base is None:
            base = row["steps_per_sec"]
        row["weak_scaling_efficiency"] = round(row["steps_per_sec"] / base, 4)
        row["note"] = (
            "virtual CPU devices share fixed host cores: efficiency here "
            "is bounded by compute contention; comm_share is the "
            "transferable column"
        ) if world > 1 else "baseline"
        print(json.dumps(row), flush=True)

    if not args.skip_dcn:
        for n_procs in (2, 4):
            dcn = run_dcn_point(args.steps, n_procs=n_procs)
            if dcn is not None:
                dcn["kind"] = (
                    f"cross-process (DCN code path, {n_procs} procs, "
                    "loopback)"
                )
                if "steps_per_sec" in dcn and base:
                    dcn["weak_scaling_efficiency"] = round(
                        dcn["steps_per_sec"] / base, 4
                    )
                print(json.dumps(dcn), flush=True)


if __name__ == "__main__":
    main()
