"""The asynchronous job (AsySG-InCon): this process is the parameter
server on the host's CPU backend — it must never touch the chip — and it
starts the workers as child processes (``async_worker.py``), the first of
which owns the chip.

The server is the program's own ``ShmPSServer`` + ``serve``. The
benchmark watches it from the two seams ``serve`` offers (``on_tick``,
``stop_when``) and from the transport's boundary (``poll_grad``,
``publish_flat``), where the staleness of a gradient is the published
version minus the version it was computed on — two numbers of the
protocol, not a counter of the program.

The window opens once ``warm_updates`` updates were applied (everything
compiled on both sides) and closes ``--seconds`` later; then the workers
are told to stop, the server drains what they were acknowledged, and
the guarantees are checked.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from chipbench.jobs.common import CompileCounter, say
from chipbench.stats import percentile

RUN_LIMIT_S = 900.0


def run(ctx) -> dict:
    ambient = os.environ.get("JAX_PLATFORMS", "")
    import jax

    jax.config.update("jax_platforms", "cpu")  # the server: host backend
    import importlib

    import numpy as np

    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel import dcn
    from pytorch_ps_mpi_tpu.parallel.async_train import make_problem, serve
    from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache

    cache = enable_compilation_cache()
    compiles = CompileCounter()
    config, traffic = ctx.config, ctx.traffic
    fam = importlib.import_module(
        f"chipbench.families.{config['family']}").build(config, traffic)
    optimizer = dict(config["optimizer"])
    batch = int(traffic["worker_batch"])
    cfg = {
        **fam.problem_cfg, "batch": batch, "seed": ctx.seed,
        "optim": optimizer.pop("name"), "hyper": optimizer,
        "steps": 10 ** 9,  # the workers run until they are told to stop
        "codec": traffic["codec"], "transport": traffic["transport"],
        "frame_check": bool(traffic["frame_check"]),
        "max_staleness": int(traffic["max_staleness"]),
        "open_timeout": 600.0, "push_timeout": 600.0,
        "tick_interval": 0.02,
    }
    if ctx.trace:
        cfg["telemetry_dir"] = ctx.scratch
    n_workers = int(traffic["workers"])
    _, params0, _, _ = make_problem(cfg)

    class ObservedServer(dcn.ShmPSServer):
        """The program's server, watched at the transport's boundary."""

        def __init__(self, *a, **kw):
            self.seen = []         # (time, staleness) of each gradient handed on
            self.published = []    # versions, in order
            self.published_at = []  # and when
            self.first_flats = []  # the first two published parameter vectors
            super().__init__(*a, **kw)

        def poll_grad(self, raw=False):
            item = super().poll_grad(raw=raw)
            if item is not None:
                self.seen.append((time.perf_counter(),
                                  max(0, self.version - item[1])))
            return item

        def publish_flat(self, flat):
            super().publish_flat(flat)
            self.published.append(self.version)
            self.published_at.append(time.perf_counter())
            if len(self.first_flats) < 2:
                self.first_flats.append(np.array(flat, np.float32))

    name = f"/chipbench_{os.getpid()}"
    server = ObservedServer(
        name, num_workers=n_workers, template=params0,
        max_staleness=cfg["max_staleness"], code=get_codec(cfg["codec"]),
        frame=cfg["frame_check"])

    # worker 0 gets the ambient placement (on the chip machine, the chip);
    # a chip belongs to one process, so any further worker stays on the CPU
    procs, logs = [], []
    for w in range(n_workers):
        env = dict(os.environ, JAX_PLATFORMS=ambient if w == 0 else "cpu")
        log = open(os.path.join(ctx.scratch, f"worker-{w}.stderr"), "w+")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "chipbench.jobs.async_worker",
             name, str(w), json.dumps(cfg), ctx.scratch,
             json.dumps({"stage_sizes": fam.stage_sizes,
                         "trace_seconds": float(traffic["trace_seconds"])
                         if ctx.trace and w == 0 else 0.0})],
            cwd=ctx.root, env=env, stderr=log))

    win: dict = {}

    def snapshot():
        return {"t": time.perf_counter(), "wall": time.time(),
                "version": server.version, "received": server.grads_received,
                "stale_drops": server.stale_drops,
                "bytes": server.bytes_received,
                "rejected": sum(getattr(server, "frames_rejected", {}).values())}

    timeline = {"spawned": time.perf_counter() - ctx.t0}

    def on_tick():
        if "open" not in win:
            if len(server.published) == 2 and "first_update" not in timeline:
                timeline["first_update"] = time.perf_counter() - ctx.t0
            if len(server.published) > int(traffic["warm_updates"]):
                win["open"] = snapshot()
                win["setup_s"] = win["open"]["t"] - ctx.t0
                say(check="setup", seconds_since_start=dict(
                    timeline, window_open=win["setup_s"]))
                touch(ctx.scratch, "trace.start")
        elif "close" not in win:
            if time.perf_counter() - win["open"]["t"] >= ctx.seconds:
                win["close"] = snapshot()
                touch(ctx.scratch, "stop")

    def stop_when():  # told to stop or dead: either way, drain and return
        return all(p.poll() is not None for p in procs)

    try:
        params, metrics = serve(server, cfg, total_grads=10 ** 12,
                                timeout=RUN_LIMIT_S, on_tick=on_tick,
                                stop_when=stop_when)
    finally:
        touch(ctx.scratch, "stop")
        codes = reap(procs)
        server.close()
    worker_said = []
    for log in logs:
        log.seek(0)
        text = log.read()
        log.close()
        sys.stderr.write(text)
        worker_said += [json.loads(l.split(": ", 1)[1])
                        for l in text.splitlines()
                        if l.startswith("worker ") and ": {" in l]
    if "close" not in win:
        raise SystemExit(f"the window never closed: workers exited {codes}, "
                         f"{len(server.published) - 1} updates applied")
    with open(os.path.join(ctx.scratch, "worker-0.report.json")) as f:
        report = json.load(f)
    if report["platform"] != "tpu" and not ctx.rehearsal:
        raise SystemExit(f"chipbench: the worker computed on platform "
                         f"{report['platform']!r}; {ctx.cell['name']} needs 'tpu'")

    o, c = win["open"], win["close"]
    window_s = c["t"] - o["t"]
    applied = c["version"] - o["version"]
    consumed = c["received"] - o["received"]
    dropped = c["stale_drops"] - o["stale_drops"]
    rejected = c["rejected"] - o["rejected"]
    failed = max(0, consumed - applied - dropped) + rejected
    compiled_in_window = compiles.between(o["wall"], c["wall"]) + sum(
        o["wall"] <= t <= c["wall"] for t in report["compile_times"])
    in_win = [s for t, s in server.seen if o["t"] <= t <= c["t"]]
    staleness_mean = sum(in_win) / max(1, len(in_win))
    # the rate of the MEDIAN update: the time from one published version
    # to the next, over the window (the mean over the window swings with
    # the neighbours on a shared host; PERF.md section 2)
    at = [t for t in server.published_at if o["t"] <= t <= c["t"]]
    cycles = [b - a for a, b in zip(at, at[1:])]
    cycle_s = percentile(cycles, 50)

    # -- the guarantees, over the whole run ---------------------------------
    pushed = sum(w["pushed"] for w in worker_said)
    total_dropped = server.stale_drops
    total_applied = len(server.published) - 1  # the first publish is p0
    flat = np.concatenate([np.ravel(np.asarray(a, np.float32))
                           for a in jax.tree.leaves(params)])
    checks = {
        # an interrupt can land between a push's acknowledgement and the
        # worker's count of it, so the server may hold one more a worker
        "acknowledged_accounted":
            len(worker_said) == n_workers
            and pushed <= server.grads_received <= pushed + n_workers
            and server.grads_received == total_applied + total_dropped,
        "staleness_bounded": all(s <= cfg["max_staleness"]
                                 for _, s in server.seen),
        "versions_monotonic": server.published == list(
            range(1, len(server.published) + 1)),
        "no_frame_rejected":
            sum(getattr(server, "frames_rejected", {}).values()) == 0,
        "server_on_cpu": jax.devices()[0].platform == "cpu",
        "worker_on_chip": report["platform"] == "tpu" or ctx.rehearsal,
        "finite_and_changed": bool(np.isfinite(flat).all())
            and not np.array_equal(flat, server.first_flats[0]),
        "first_update": first_update_ok(
            server.first_flats, params0, cfg["hyper"]["lr"],
            os.path.join(ctx.scratch, "g_ref.npy"), config["tolerances"]),
        "no_compile_in_window": compiled_in_window == 0,
        "workers_exited_cleanly": codes == [0] * n_workers,
    }
    say(check="guarantees", **checks, pushed=pushed,
        received=server.grads_received, applied=total_applied,
        stale_drops=total_dropped,
        staleness_hist=metrics.get("staleness_hist"),
        compile_cache={"server": cache.as_dict(),
                       "workers": [w["compile_cache"] for w in worker_said]},
        loss_initial=metrics.get("loss_initial"),
        loss_final=metrics.get("loss_final"))
    say(check="window", applied=applied, window_s=window_s,
        rate_over_window=applied * batch / window_s,
        cycle_s={f"p{q}": percentile(cycles, q) for q in (0, 25, 50, 75, 100)},
        cycles_ms=[round(1e3 * x, 1) for x in cycles])

    spans = {}
    if ctx.trace:
        from pytorch_ps_mpi_tpu.telemetry.recorder import load_jsonl

        for name in ["server.jsonl"] + [f"worker-{w}.jsonl"
                                        for w in range(n_workers)]:
            path = os.path.join(ctx.scratch, name)
            if not os.path.exists(path):
                continue
            for e in load_jsonl(path)[1]:
                if "dur" in e and o["wall"] <= e["wall"] <= c["wall"]:
                    spans.setdefault(e["name"], []).append(e)
    return {
        "correct": all(checks.values()) and failed == 0,
        "attempted": consumed, "failed": failed,
        "end_to_end": {f"{fam.unit}_per_s": cycle_s and batch / cycle_s,
                       "staleness_mean": staleness_mean,
                       "setup_s": win["setup_s"]},
        "device": {"platform": report["platform"], "kind": report["kind"],
                   "count": report["count"],
                   "memory_peak_bytes": report["memory_peak_bytes"]},
        "trace_dir": (os.path.join(ctx.scratch, "worker-trace")
                      if ctx.trace else None),
        "spans": spans,
        "counters": {
            "compiles_in_window": compiled_in_window,
            "cache_misses": cache.misses + sum(
                w["compile_cache"]["misses"] for w in worker_said),
            "peak_hbm_bytes": report["memory_peak_bytes"],
            "applied_in_window": applied, "window_s": window_s,
            "cycle_s_p50": cycle_s,
            "chips": 1,
            "wire_bytes_per_update": (c["bytes"] - o["bytes"]) / max(1, consumed),
        },
        "shape": None, "peaks": None,
    }


def touch(directory: str, name: str) -> None:
    open(os.path.join(directory, name), "w").close()


def reap(procs, patience: float = 120.0):
    """Wait for the workers; kill what does not end. Returns exit codes."""
    deadline = time.time() + patience
    for p in procs:
        try:
            p.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
    return [p.returncode for p in procs]


def first_update_ok(first_flats, template, lr: float, g_ref_path: str,
                    tol: dict) -> bool:
    """The first applied update against ``-lr * int8(g_ref)``, leaf by
    leaf (the codec scales each leaf by its own largest magnitude).
    Momentum's buffer starts at the gradient, so step 1 is plain SGD."""
    import jax
    import numpy as np

    from chipbench.reference.resnet import int8_roundtrip

    if len(first_flats) < 2 or not os.path.exists(g_ref_path):
        return False
    g_ref = np.load(g_ref_path)
    step = (first_flats[0] - first_flats[1]) / np.float32(lr)
    want = np.empty_like(g_ref)
    worst, at = 0.0, 0
    for leaf in jax.tree.leaves(template):
        n = int(np.prod(leaf.shape))
        want[at:at + n] = int8_roundtrip(g_ref[at:at + n])
        if n > tol["min_leaf"]:
            err = float(np.linalg.norm(step[at:at + n] - want[at:at + n])
                        / max(np.linalg.norm(want[at:at + n]), 1e-30))
            worst = max(worst, err)
        at += n
    whole = float(np.linalg.norm(step - want) / np.linalg.norm(want))
    say(check="first_update", rel_l2=whole, worst_leaf_rel_l2=worst,
        tolerances=tol)
    return (whole <= tol["first_update_rel_l2"]
            and worst <= tol["first_update_worst_leaf_rel_l2"])
