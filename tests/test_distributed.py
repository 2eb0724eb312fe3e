"""Two coordinated OS processes through ``launch.py`` +
``jax.distributed`` — the reference's entire test harness was
multi-process (``mpirun -n 2 py.test``, ``Makefile:2-3``); this is the
TPU-native analog actually *executing* a 2-process collective over the
distributed runtime.

Each child pins platform=cpu with ONE local device, so the global mesh is
2 devices across 2 processes and every collective crosses the process
boundary for real.
"""

import os
import socket
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_allreduce_and_ps_step():
    port = _free_port()
    env = dict(os.environ)
    # children get ONE local CPU device each (override conftest's 8)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("JAX_PLATFORMS", None)
    procs = []
    for r in range(2):
        cmd = [
            sys.executable, "-m", "pytorch_ps_mpi_tpu.launch",
            "--platform", "cpu",
            "--coordinator", f"localhost:{port}",
            "--num-processes", "2",
            "--process-id", str(r),
            os.path.join(ROOT, "tests", "distributed_worker.py"),
        ]
        procs.append(
            subprocess.Popen(
                cmd, cwd=ROOT, env=env, text=True,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            )
        )
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"PS_TEST_OK rank={r}" in out, f"rank {r} output:\n{out}"
