"""Shared-memory async PS (native psqueue + dcn.py wrappers): the
multi-process AsySG-InCon transport. Protocol oracle: workers that push
(w − target) gradients must drive the server's params to the target, with
inconsistent (stale) reads tolerated and bounded."""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from pytorch_ps_mpi_tpu.parallel import dcn

pytestmark = pytest.mark.skipif(
    dcn.get_lib() is None, reason="native toolchain unavailable"
)

TEMPLATE = {"w": np.zeros((6,), np.float32)}
TARGET = np.arange(6, dtype=np.float32)


def _worker_loop(name, worker_id, n_pushes, code=None, in_step_of=0):
    """``in_step_of=n`` (the number of workers) keeps this worker within
    one push of the others, so that a test which expects EVERY push
    applied does not depend on the scheduler. Left alone, a worker that
    the OS parks between its read and its push while the others run on
    is carried past the server's staleness bound; its push is dropped,
    as designed, and the count the test waits for never comes. So before
    its push k the worker waits until the server has applied push k - 1
    of every worker (one version each, after the first publish): what it
    waits on is the state it reads, not the clock, and no push can then
    be staler than the number of workers."""
    w = dcn.ShmPSWorker(name, worker_id, TEMPLATE, code=code)
    try:
        for k in range(n_pushes):
            params, version = w.read_params()
            while version < in_step_of * k + 1:
                time.sleep(0.0005)
                params, version = w.read_params()
            grad = {"w": params["w"] - TARGET}   # ∇ of 0.5‖w − target‖²
            w.push_grad(grad, version)
    finally:
        w.close()


def _serve(server, total_grads, lr=0.2, timeout=30.0, hard_timeout=300.0):
    """``timeout`` is an IDLE timeout, refreshed on every consumed
    gradient (worker startup under full-suite contention can eat tens
    of seconds before the first delivery — a fixed overall deadline
    made this loop load-flaky, ISSUE 13's burn-down); ``hard_timeout``
    bounds the whole call regardless of progress."""
    params = {"w": TEMPLATE["w"].copy()}
    server.publish(params)
    got = 0
    hard_deadline = time.time() + hard_timeout
    deadline = time.time() + timeout
    while (got < total_grads and time.time() < deadline
           and time.time() < hard_deadline):
        item = server.poll_grad()
        if item is None:
            time.sleep(0.001)
            continue
        _, _, grad = item
        params = {"w": params["w"] - lr * grad["w"]}
        server.publish(params)
        got += 1
        deadline = time.time() + timeout
    return params, got


def test_inprocess_threads_roundtrip():
    name = f"/psq_test_{os.getpid()}_t"
    server = dcn.ShmPSServer(name, num_workers=2, template=TEMPLATE)
    try:
        threads = [
            threading.Thread(target=_worker_loop, args=(name, i, 20),
                             kwargs={"in_step_of": 2})
            for i in range(2)
        ]
        for t in threads:
            t.start()
        params, got = _serve(server, total_grads=40)
        for t in threads:
            t.join(timeout=10)
        assert got == 40 and server.stale_drops == 0
        np.testing.assert_allclose(params["w"], TARGET, atol=1e-2)
        # versions advanced once per applied update (+1 initial publish)
        assert server.version == 41
        assert sum(server.staleness_seen.values()) == 40
    finally:
        server.close()


def test_multiprocess_roundtrip():
    """Real OS processes over the shm segment — the reference's mpirun
    test harness analog (SURVEY §4: multi-node simulated by multi-process
    single-node)."""
    name = f"/psq_test_{os.getpid()}_p"
    server = dcn.ShmPSServer(name, num_workers=2, template=TEMPLATE)
    worker_src = f"""
import sys
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
import numpy as np
from tests.test_dcn import _worker_loop
_worker_loop({name!r}, int(sys.argv[1]), 15, in_step_of=2)
"""
    try:
        procs = [
            subprocess.Popen([sys.executable, "-c", worker_src, str(i)])
            for i in range(2)
        ]
        params, got = _serve(server, total_grads=30, timeout=60.0)
        for p in procs:
            assert p.wait(timeout=30) == 0
        assert got == 30
        np.testing.assert_allclose(params["w"], TARGET, atol=1e-2)
    finally:
        server.close()


def test_staleness_bound_drops_old_grads():
    name = f"/psq_test_{os.getpid()}_s"
    server = dcn.ShmPSServer(name, num_workers=1, template=TEMPLATE,
                             max_staleness=2)
    try:
        w = dcn.ShmPSWorker(name, 0, TEMPLATE)
        server.publish({"w": TEMPLATE["w"].copy()})
        _, v_old = w.read_params()
        # server races ahead 5 versions
        for _ in range(5):
            server.publish({"w": TEMPLATE["w"].copy()})
        w.push_grad({"w": np.ones(6, np.float32)}, v_old)  # staleness 5 > 2
        assert server.poll_grad() is None
        assert server.stale_drops == 1
        w.close()
    finally:
        server.close()


def test_worker_open_timeout():
    with pytest.raises(TimeoutError):
        dcn.ShmPSWorker("/psq_does_not_exist", 0, TEMPLATE, timeout=0.3)


def test_straggler_detection():
    name = f"/psq_test_{os.getpid()}_h"
    server = dcn.ShmPSServer(name, num_workers=3, template=TEMPLATE)
    try:
        w = dcn.ShmPSWorker(name, 0, TEMPLATE)
        server.publish({"w": TEMPLATE["w"].copy()})
        _, v = w.read_params()
        w.push_grad({"w": np.ones(6, np.float32)}, v)
        assert server.poll_grad() is not None
        time.sleep(0.15)
        lag = server.stragglers(timeout=0.1)
        # workers 1 and 2 never reported; worker 0 is fresh enough... but
        # 0.15s > 0.1s, so all three exceed the window except none pushed
        # within it: 0 pushed 0.15s ago -> also straggling
        assert set(lag) == {0, 1, 2}
        lag2 = server.stragglers(timeout=10.0)
        assert lag2 == {}
        w.close()
    finally:
        server.close()


def test_pending_grad_counts_as_alive():
    """A pushed-but-unpolled gradient must not be reported as straggling
    (regression: server polling pauses used to misreport workers)."""
    name = f"/psq_test_{os.getpid()}_p2"
    server = dcn.ShmPSServer(name, num_workers=1, template=TEMPLATE)
    try:
        w = dcn.ShmPSWorker(name, 0, TEMPLATE)
        server.publish({"w": TEMPLATE["w"].copy()})
        _, v = w.read_params()
        w.push_grad({"w": np.ones(6, np.float32)}, v)
        time.sleep(0.12)
        # mailbox FULL -> alive even though nothing was ever polled
        assert server.stragglers(timeout=0.05) == {}
        assert server.poll_grad() is not None
        time.sleep(0.12)
        # now consumed long ago and nothing pending -> straggler
        assert 0 in server.stragglers(timeout=0.05)
        w.close()
    finally:
        server.close()


# -- codecs on the async wire --------------------------

@pytest.mark.parametrize("codec_name,kw,min_ratio,atol,pushes", [
    ("sign", {"use_pallas": False}, 4.0, 0.3, 40),   # 5B vs 24B on the wire
    ("int8", {"use_pallas": False}, 2.0, 5e-2, 40),  # 10B vs 24B
    # ragged wire: per-message true length varies as coordinates reach the
    # target and leave the |g|>0 mask (uncapped so convergence is exact;
    # cap-overflow dynamics are covered deterministically in test_codecs)
    ("threshold", {"tau": 0.0, "max_fraction": 1.0}, 0.4, 1e-2, 40),
])
def test_codec_compressed_mailbox_trains(codec_name, kw, min_ratio, atol, pushes):
    """Training through a codec-compressed mailbox: encode on the worker,
    payload bytes (only) through the psqueue, decode+apply on the server
    (reference codec placement, ps.py:94,166). The server's metrics
    report the live compression ratio."""
    from pytorch_ps_mpi_tpu.codecs import get_codec

    name = f"/psq_test_{os.getpid()}_{codec_name[:3]}"
    code = get_codec(codec_name, **kw)
    server = dcn.ShmPSServer(name, num_workers=2, template=TEMPLATE, code=code)
    try:
        threads = [
            threading.Thread(
                target=_worker_loop,
                args=(name, i, pushes, get_codec(codec_name, **kw), 2),
            )
            for i in range(2)
        ]
        for t in threads:
            t.start()
        # sign's per-coordinate step is lr*mean|residual| independent of
        # the coordinate's own size — needs a larger lr to close the big
        # coordinates within the push budget (oscillation self-damps as
        # mean|residual| shrinks)
        lr = 0.3 if codec_name == "sign" else 0.2
        total = 2 * pushes
        params, got = _serve(server, total_grads=total, lr=lr, timeout=120.0)
        for t in threads:
            t.join(timeout=15)
        assert got == total
        np.testing.assert_allclose(params["w"], TARGET, atol=atol)
        m = server.metrics()
        assert m["compression_ratio"] >= min_ratio, m
        assert m["grads_received"] == total
        # every mailbox payload was the encoded wire size, not raw f32
        assert m["bytes_received"] == total * m["wire_bytes_per_grad"]
    finally:
        server.close()


def test_codec_wire_spec_roundtrip():
    """CodecWire byte round-trip is exact for the identity codec and
    shape-preserving for lossy ones."""
    from pytorch_ps_mpi_tpu.codecs import get_codec

    template = {"a": np.zeros((5, 3), np.float32), "b": np.zeros((7,), np.float32)}
    wire = dcn.CodecWire(get_codec("identity"), template)
    grad = {"a": np.arange(15, dtype=np.float32).reshape(5, 3),
            "b": -np.arange(7, dtype=np.float32)}
    buf = wire.encode_to_bytes(grad)
    assert len(buf) == wire.wire_bytes == 22 * 4
    out = wire.decode_from_bytes(buf)
    np.testing.assert_allclose(out["a"], grad["a"])
    np.testing.assert_allclose(out["b"], grad["b"])


def test_reset_worker_slot_unblocks_replacement():
    """Elastic-replacement primitive: after a worker dies leaving its
    mailbox occupied, reset_worker_slot discards the stale payload and a
    replacement on the same id can push again."""
    name = f"/psq_test_{os.getpid()}_r"
    server = dcn.ShmPSServer(name, num_workers=1, template=TEMPLATE)
    try:
        server.publish({"w": TEMPLATE["w"].copy()})
        w = dcn.ShmPSWorker(name, 0, TEMPLATE)
        _, v = w.read_params()
        w.push_grad({"w": np.ones(6, np.float32)}, v)
        w.close()  # "crash" with an unconsumed payload in the slot
        server.reset_worker_slot(0)
        assert server._lib.psq_grad_pending(server._h, 0) == 0
        w2 = dcn.ShmPSWorker(name, 0, TEMPLATE)
        w2.push_grad({"w": 2 * np.ones(6, np.float32)}, v)
        item = server.poll_grad()
        assert item is not None
        _, _, grad = item
        np.testing.assert_allclose(grad["w"], 2 * np.ones(6))
        w2.close()
        with pytest.raises(ValueError):
            server.reset_worker_slot(99)
    finally:
        server.close()
