"""The full AsySG-InCon stack with REAL jitted compute, across OS
processes: worker processes run a jitted
``value_and_grad`` of a flax MLP, encode with the sign codec (jitted),
push payload bytes through the native shm mailboxes; the in-process
server decodes (jitted) and applies jitted fused SGD updates in arrival
order. No gradient anywhere is computed outside ``jax.jit``.

Reference analog: the async loop every rank ran real backprop in
(``/root/reference/ps.py:65-66,98-101``; AsySG pseudo-code
``README.md:61-81``) — here the asynchrony is process-level with bounded
staleness instead of thread+MPI-request level.
"""

import os

import numpy as np
import pytest

from pytorch_ps_mpi_tpu.parallel import dcn
from pytorch_ps_mpi_tpu.parallel.async_train import (
    join_workers,
    make_problem,
    serve,
    spawn_worker,
)

pytestmark = pytest.mark.skipif(
    dcn.get_lib() is None, reason="native toolchain unavailable"
)


def test_async_jitted_workers_converge_with_staleness_and_drops():
    """3 worker processes (one deliberately slow) train a linear-teacher
    regression through the codec-compressed wire. Asserts: the loss
    converges, the staleness histogram is non-trivial, the slow worker's
    over-stale gradients were dropped, and the compression ratio is
    reported from the live wire."""
    fast_steps, slow_steps = 120, 4
    cfg = {
        "model": "mlp",
        "model_kw": {"features": (32, 4)},
        "in_shape": (8,),
        "batch": 64,
        "seed": 3,
        "codec": "sign",
        "codec_kw": {"use_pallas": False},
        "optim": "sgd",
        "hyper": {"lr": 0.02},
        "worker_steps": {"0": fast_steps, "1": fast_steps, "2": slow_steps},
        # worker 2 sleeps 250 ms between compute and push: by push time the
        # fast workers have advanced the server far past its read version
        "slow_ms": {"2": 250.0},
    }
    from pytorch_ps_mpi_tpu.codecs import get_codec

    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_async_{os.getpid()}"
    server = dcn.ShmPSServer(
        name, num_workers=3, template=params0, max_staleness=3,
        code=get_codec(cfg["codec"], **cfg["codec_kw"]),
    )
    total_pushes = 2 * fast_steps + slow_steps
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(3)]
        params, m = serve(
            server, cfg, total_grads=0, total_received=total_pushes,
            timeout=240.0,
        )
        # join_workers: a failed assert can no longer leak the rest of
        # the fleet (they are terminated and reaped on every exit path)
        assert join_workers(procs, timeout=120) == [0, 0, 0]
    finally:
        server.close()

    # every push was consumed; applied + dropped account for all of them
    assert m["grads_received"] == total_pushes
    assert m["applied"] == total_pushes - m["stale_drops"]

    # convergence: the async run must actually have trained the model
    assert m["loss_final"] < 0.35 * m["loss_initial"], m

    # the slow worker forced non-trivial staleness: at least one gradient
    # arrived >max_staleness versions old (and was dropped), and the
    # histogram spans more than the all-fresh bucket
    assert m["stale_drops"] >= 1
    hist = m["staleness_hist"]
    assert any(s > 3 for s in hist), hist
    assert sum(hist.values()) == total_pushes

    # live wire compression (sign codec: 1 bit + per-leaf scale)
    assert m["compression_ratio"] > 4.0
    assert m["bytes_received"] == total_pushes * m["wire_bytes_per_grad"]


def test_sync_barrier_collapses_to_straggler_async_does_not():
    """The wall-clock benefit asynchrony exists for: with one straggler, the synchronous-barrier PS is paced by the slow
    worker while AsySG keeps applying fast workers' gradients. Compare
    applied-updates/sec with identical worker fleets."""
    base = {
        "model": "mlp",
        "model_kw": {"features": (16, 4)},
        "in_shape": (8,),
        "batch": 16,
        "seed": 7,
        "optim": "sgd",
        "hyper": {"lr": 0.01},
        "slow_ms": {"1": 120.0},
    }
    _, params0, _, _ = make_problem(base)

    def run(sync_barrier: bool, steps_fast: int, steps_slow: int):
        cfg = dict(base)
        cfg["worker_steps"] = {"0": steps_fast, "1": steps_slow}
        name = f"/psq_sync_{os.getpid()}_{int(sync_barrier)}"
        server = dcn.ShmPSServer(
            name, num_workers=2, template=params0,
            max_staleness=10**9,  # isolate the pacing effect from drops
        )
        try:
            procs = [spawn_worker(name, i, cfg) for i in range(2)]
            _, m = serve(
                server, cfg, total_grads=0,
                total_received=steps_fast + steps_slow,
                sync_barrier=sync_barrier, timeout=240.0,
            )
            assert join_workers(procs, timeout=120) == [0, 0]
        finally:
            server.close()
        return m

    # sync barrier: fast worker is held to the slow worker's cadence, so
    # both push the same count; async: fast worker streams ahead
    m_sync = run(sync_barrier=True, steps_fast=6, steps_slow=6)
    m_async = run(sync_barrier=False, steps_fast=40, steps_slow=6)

    assert m_async["updates_per_sec"] > 2.0 * m_sync["updates_per_sec"], (
        m_sync["updates_per_sec"], m_async["updates_per_sec"],
    )


def test_poll_grad_deep_stale_backlog_iterative():
    """Regression: a backlog of thousands of
    consecutive stale gradients must drain iteratively — the old
    recursive ``poll_grad`` blew Python's recursion limit at ~1000."""
    import ctypes
    import sys

    n_workers = 2500
    assert n_workers > sys.getrecursionlimit() * 2
    template = {"w": np.zeros((6,), np.float32)}
    name = f"/psq_backlog_{os.getpid()}"
    server = dcn.ShmPSServer(
        name, num_workers=n_workers, template=template, max_staleness=2,
    )
    try:
        server.publish({"w": template["w"].copy()})
        v_old = server.version
        flat = np.ones(6, np.float32)
        buf = flat.view(np.uint8)
        ptr = buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        for w in range(n_workers):
            rc = server._lib.psq_push_grad(
                server._h, w, ptr, flat.nbytes, v_old
            )
            assert rc == 1
        for _ in range(6):  # staleness 6 > max_staleness 2
            server.publish({"w": template["w"].copy()})
        assert server.poll_grad() is None  # drains all 2500 without recursion
        assert server.stale_drops == n_workers
        assert server.grads_received == n_workers
    finally:
        server.close()


def test_worker_crash_and_elastic_replacement():
    """Failure recovery the reference's MPI lacked (SURVEY §5.3: any rank
    failure killed the whole job): a worker process is KILLED mid-
    training; the server keeps serving the survivors, flags the dead
    worker as a straggler, and a REPLACEMENT process attached to the same
    mailbox id resumes pushing — training continues to convergence with
    no server restart and no state loss."""
    import signal
    import time as _time

    cfg = {
        "model": "mlp",
        "model_kw": {"features": (32, 4)},
        "in_shape": (8,),
        "batch": 64,
        "seed": 11,
        "optim": "sgd",
        "hyper": {"lr": 0.05},
        "steps": 400,  # far more than needed; victim dies early
    }
    _, params0, batch_fn, loss_fn = make_problem(cfg)
    name = f"/psq_elastic_{os.getpid()}"
    server = dcn.ShmPSServer(name, num_workers=2, template=params0,
                             max_staleness=10**9)
    try:
        survivor = spawn_worker(name, 0, cfg)
        victim = spawn_worker(name, 1, cfg)

        # phase 1: run until both workers have contributed
        import jax
        from pytorch_ps_mpi_tpu.optim import OPTIMIZERS

        params = params0
        hyper_cls, init_state, update_fn = OPTIMIZERS["sgd"]
        h = hyper_cls(lr=0.05)
        state = init_state(params)
        update = jax.jit(lambda p, g, s: update_fn(p, g, s, h))
        eval_loss = jax.jit(loss_fn)
        eval_batch = batch_fn(10**6, 10**6)
        loss0 = float(eval_loss(params, eval_batch))
        server.publish(params)

        seen_workers = set()
        applied = 0
        deadline = _time.time() + 240
        killed = False
        replacement = None
        while applied < 120 and _time.time() < deadline:
            item = server.poll_grad()
            if item is None:
                _time.sleep(0.001)
                continue
            wid, _, grad = item
            seen_workers.add(wid)
            params, state = update(params, grad, state)
            server.publish(jax.tree.map(np.asarray, params))
            applied += 1
            if not killed and applied >= 30 and {0, 1} <= seen_workers:
                victim.send_signal(signal.SIGKILL)  # mid-flight crash
                victim.wait(timeout=30)
                killed = True
                t_kill = _time.time()
            if killed and replacement is None and applied >= 60:
                # dead worker shows up in the straggler report: wait for
                # its pending push (if any) to drain and its 0.5 s
                # silence window to elapse — timing-robust, the survivor
                # keeps streaming meanwhile
                flag_deadline = _time.time() + 30
                flagged = False
                while _time.time() < flag_deadline and not flagged:
                    drained = server.poll_grad()
                    if drained is not None:
                        wid_d, _, grad_d = drained
                        params, state = update(params, grad_d, state)
                        server.publish(jax.tree.map(np.asarray, params))
                        applied += 1
                    flagged = 1 in server.stragglers(timeout=0.5)
                    if not flagged:
                        _time.sleep(0.05)
                assert flagged
                # ...and an elastic replacement reuses its mailbox id.
                # Reset the slot first: a SIGKILL inside the WRITING
                # window would leave it wedged and the replacement could
                # never push (psq_reset_slot exists for exactly this).
                server.reset_worker_slot(1)
                replacement = spawn_worker(name, 1, cfg)

        assert killed and replacement is not None
        assert applied >= 120
        # replacement actually contributed after the crash: keep
        # draining until a wid==1 gradient arrives (its fresh process
        # needs seconds of jax import + compile before the first push)
        deadline = _time.time() + 180
        saw_replacement = False
        while not saw_replacement and _time.time() < deadline:
            item = server.poll_grad()
            if item is None:
                _time.sleep(0.001)
                continue
            wid, _, grad = item
            params, state = update(params, grad, state)
            server.publish(jax.tree.map(np.asarray, params))
            if wid == 1:
                saw_replacement = True
        assert saw_replacement
        assert float(eval_loss(params, eval_batch)) < 0.5 * loss0

        survivor.kill()
        survivor.wait(timeout=30)
        replacement.kill()
        replacement.wait(timeout=30)
    finally:
        server.close()


def test_gpt_causal_lm_over_async_wire():
    """A decoder-only causal LM trains through the async PS: jitted GPT
    value_and_grad in worker processes, bf16 wire, arrival-order server
    updates — the model-family x topology cell (transformers x async)
    the per-family unit tests don't cover."""
    cfg = {
        "model": "gpt",
        "model_kw": {"vocab_size": 64, "hidden_size": 32, "num_layers": 1,
                     "num_heads": 2, "intermediate_size": 64,
                     "max_position": 32},
        "seq_len": 16,
        "batch": 16,
        "seed": 2,
        "codec": "bf16",
        "optim": "adam",
        "hyper": {"lr": 1e-2},
        # 60 pushes/worker: enough Adam progress that arrival-order
        # nondeterminism (the point of the async path) cannot flake the
        # 0.85 convergence margin on a loaded host
        "steps": 60,
    }
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.async_train import make_problem, serve, spawn_worker

    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_gpt_{os.getpid()}"
    server = dcn.ShmPSServer(
        name, num_workers=2, template=params0, max_staleness=10**9,
        code=get_codec("bf16"),
    )
    total = 2 * cfg["steps"]
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(2)]
        _, m = serve(server, cfg, total_grads=0, total_received=total,
                     timeout=420.0)
        assert join_workers(procs, timeout=240) == [0, 0]
    finally:
        server.close()
    assert m["grads_received"] == total
    assert m["compression_ratio"] == pytest.approx(2.0)
    assert m["loss_final"] < 0.85 * m["loss_initial"], m


def test_inxla_sampled_staleness_matches_shm_arrival_histogram():
    """The in-XLA AsyncPS, fed the
    MEASURED arrival histogram of a real multi-process shm run, must (a)
    reproduce that staleness distribution (compared histogram-to-
    histogram) and (b) converge on the same problem — closing the loop
    between the algorithm-semantics vehicle and the wall-clock stack."""
    import jax
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu.parallel.async_ps import (
        AsyncPS,
        staleness_probs_from_histogram,
    )

    fast_steps, slow_steps = 60, 4
    max_staleness = 3
    cfg = {
        "model": "mlp",
        "model_kw": {"features": (32, 4)},
        "in_shape": (8,),
        "batch": 64,
        "seed": 11,
        "optim": "sgd",
        "hyper": {"lr": 0.02},
        "worker_steps": {"0": fast_steps, "1": fast_steps, "2": slow_steps},
        "slow_ms": {"2": 200.0},
    }
    _, params0, batch_fn, loss_fn = make_problem(cfg)
    name = f"/psq_hist_{os.getpid()}"
    server = dcn.ShmPSServer(
        name, num_workers=3, template=params0, max_staleness=max_staleness,
    )
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(3)]
        _, m = serve(
            server, cfg, total_grads=0,
            total_received=2 * fast_steps + slow_steps, timeout=240.0,
        )
        assert join_workers(procs, timeout=120) == [0, 0, 0]
    finally:
        server.close()
    shm_hist = m["staleness_hist"]
    assert m["loss_final"] < 0.35 * m["loss_initial"]

    # replay the measured arrival distribution inside the XLA program
    probs = staleness_probs_from_histogram(shm_hist, max_staleness)
    ps = AsyncPS(params0, loss_fn, num_workers=3, optim="sgd", lr=0.02,
                 max_staleness=max_staleness, staleness_probs=probs, seed=5)
    loss_initial = float(loss_fn(ps.params, batch_fn(0, 0)))
    rounds = 40
    for step in range(rounds):
        batches = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[batch_fn(step, w) for w in range(3)]
        )
        ps.step(batches)
    loss_final = float(loss_fn(ps.params, batch_fn(0, 0)))

    # (b) convergence matches the multi-process stack's criterion
    assert loss_final < 0.35 * loss_initial, (loss_initial, loss_final)

    # (a) histograms agree where the shm server applied gradients
    # (lags > max were dropped there, excluded from the distribution)
    kept = {k: v for k, v in shm_hist.items() if k <= max_staleness}
    tot_shm = sum(kept.values())
    tot_ps = sum(ps.staleness_hist.values())
    assert tot_ps == rounds * 3
    shm_p = np.array([kept.get(i, 0) / tot_shm
                      for i in range(max_staleness + 1)])
    ps_p = np.array([ps.staleness_hist.get(i, 0) / tot_ps
                     for i in range(max_staleness + 1)])
    tv = 0.5 * np.abs(shm_p - ps_p).sum()
    assert tv < 0.15, (shm_p.tolist(), ps_p.tolist(), tv)


# -- the host phase spans of the asynchronous worker --------------------------

ASYNC_SPANS = {  # span -> parent: the table of worker_main and push_grad
    "worker.step": None, "worker.read_params": "worker.step",
    "worker.grad": "worker.step", "worker.batch": "worker.grad",
    "worker.grad_dispatch": "worker.grad", "worker.grad_wait": "worker.grad",
    "worker.push_grad": "worker.step", "wire.encode": "worker.push_grad",
    "wire.send": "worker.push_grad"}

SPAN_CFG = {"model": "mlp", "model_kw": {"features": (16, 4)},
            "in_shape": (8,), "batch": 16, "seed": 5, "codec": "sign",
            "codec_kw": {"use_pallas": False}, "optim": "sgd",
            "hyper": {"lr": 0.02}}


def test_two_workers_record_the_phase_spans_of_every_cycle(tmp_path):
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.telemetry import load_jsonl

    steps = 4
    cfg = dict(SPAN_CFG, steps=steps, telemetry_dir=str(tmp_path))
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_spans_{os.getpid()}"
    server = dcn.ShmPSServer(
        name, num_workers=2, template=params0, max_staleness=8,
        code=get_codec(cfg["codec"], **cfg["codec_kw"]))
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(2)]
        serve(server, dict(cfg, telemetry_dir=None), total_grads=0,
              total_received=2 * steps, timeout=240.0)
        assert join_workers(procs, timeout=120) == [0, 0]
    finally:
        server.close()
    for wid in range(2):
        _, rows = load_jsonl(str(tmp_path / f"worker-{wid}.jsonl"))
        # the process's set-up rows come first (tests/test_setup_log.py)
        spans = [e for e in rows if e["kind"] == "span"
                 and not e["name"].startswith(("setup.", "compile."))]
        assert sorted(e["name"] for e in spans) == sorted(
            steps * list(ASYNC_SPANS))
        for e in spans:
            assert e.get("parent") == ASYNC_SPANS[e["name"]], e
            assert e["worker"] == wid
        for step in range(steps):
            mine = {e["name"]: e for e in spans if e["step"] == step}
            assert set(mine) == set(ASYNC_SPANS)
            for child, parent in ASYNC_SPANS.items():  # parents cover children
                if parent:
                    c, p = mine[child], mine[parent]
                    assert p["ts"] <= c["ts"]
                    assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-9
            # what the two old rows carried, they still carry
            assert mine["worker.grad"]["attrs"]["version"] >= 1
            assert mine["worker.push_grad"]["attrs"]["seq"] == step
            assert (mine["worker.push_grad"]["attrs"]["version"]
                    == mine["worker.grad"]["attrs"]["version"])


def test_recorder_off_worker_makes_no_annotation_and_no_row(
        monkeypatch, annotations_made):
    """``worker_main`` in this process (a thread beside the server) with
    the recorder off: no annotation object made, and no row but those of
    the set-up log."""
    import threading

    from pytorch_ps_mpi_tpu import telemetry
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.async_train import worker_main

    telemetry.disable()
    rows = []
    monkeypatch.setattr(telemetry.FlightRecorder, "event",
                        lambda self, name, **kw: rows.append(name))
    cfg = dict(SPAN_CFG, steps=3)
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_off_{os.getpid()}"
    server = dcn.ShmPSServer(
        name, num_workers=1, template=params0, max_staleness=8,
        code=get_codec(cfg["codec"], **cfg["codec_kw"]))
    pushed = []
    worker = threading.Thread(
        target=lambda: pushed.append(worker_main(name, 0, cfg)))
    try:
        worker.start()
        _, m = serve(server, cfg, total_grads=0, total_received=3,
                     timeout=120.0)
        worker.join(60)
    finally:
        server.close()
    assert pushed == [3] and m["grads_received"] == 3
    assert annotations_made == []
    assert [n for n in rows if not n.startswith(("setup.", "compile."))] == []
    assert "setup.worker" in rows and "setup.serve" in rows
