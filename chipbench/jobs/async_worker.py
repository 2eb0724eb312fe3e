"""One worker of the asynchronous job, as a process of its own: the
program's ``worker_main``, whole, with what only the process that owns
the chip can do around it — a device trace of a few seconds when the
server says the window is open, the peak device memory, the times of its
compilations, and afterwards the reference gradient of its first batch.

    python3 -m chipbench.jobs.async_worker <server> <id> <cfg> <dir> <extras>

It is told what to do through files in ``<dir>``: ``trace.start`` opens
the trace, ``stop`` interrupts ``worker_main`` (a KeyboardInterrupt, so
its own ``finally`` closes the transport, dumps its spans and reports
its pushes).
"""

from __future__ import annotations

import _thread
import json
import os
import sys
import threading
import time


def main(argv) -> int:
    name, wid, cfg, out_dir, extras = (argv[0], int(argv[1]),
                                       json.loads(argv[2]), argv[3],
                                       json.loads(argv[4]))
    import jax
    import numpy as np

    from chipbench.jobs.common import CompileCounter, peak_bytes
    from pytorch_ps_mpi_tpu.parallel.async_train import (
        make_problem,
        worker_main,
    )

    compiles = CompileCounter()
    done = threading.Event()

    def watch():
        traced = not extras["trace_seconds"]
        while not done.is_set():
            if not traced and os.path.exists(os.path.join(out_dir, "trace.start")):
                traced = True
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(os.path.join(out_dir, "worker-trace"),
                                         profiler_options=options)
                time.sleep(extras["trace_seconds"])
                jax.profiler.stop_trace()
            if os.path.exists(os.path.join(out_dir, "stop")):
                _thread.interrupt_main()
                return
            time.sleep(0.02)

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    try:
        try:
            worker_main(name, wid, cfg)
        finally:
            done.set()
        watcher.join()
    except KeyboardInterrupt:  # the watcher's, wherever it landed
        watcher.join()
    devices = jax.local_devices()
    in_window = list(compiles.times)
    _, params0, batch_fn, loss_fn = make_problem(cfg)
    # the runtime's peak counts buffers, not what a running program holds
    # besides: XLA's buffer assignment of the gradient program says that
    program = jax.jit(jax.value_and_grad(loss_fn)).lower(
        params0, batch_fn(0, wid)).compile().memory_analysis()
    buffers = peak_bytes(devices)
    report = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": buffers + program.temp_size_in_bytes,
              "buffers_peak_bytes": buffers,
              "runtime": devices[0].memory_stats(),
              "compile_times": in_window}
    if wid == 0:
        from chipbench.reference import resnet

        g = jax.jit(jax.grad(lambda p, b: resnet.loss(
            p, b, tuple(extras["stage_sizes"]))))(params0, batch_fn(0, wid))
        np.save(os.path.join(out_dir, "g_ref.npy"), np.concatenate(
            [np.ravel(np.asarray(a, np.float32)) for a in jax.tree.leaves(g)]))
    with open(os.path.join(out_dir, f"worker-{wid}.report.json"), "w") as f:
        json.dump(report, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
