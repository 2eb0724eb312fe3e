"""Trainer loop: fit/metrics/checkpoint-resume (checkpointing was absent
in the reference, SURVEY §5.4 — here it's tested end to end), torch
interop converters (reference to_np/to_torch, mpi_comms.py:32-58), and
the bf16 comm path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu import SGD
from pytorch_ps_mpi_tpu.trainer import Trainer


def assert_trees_equal(a, b):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), a, b)


def quad_loss(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2)


def make_data(n=1000, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    w_true = jax.random.normal(k2, (4, 2))
    def gen():
        i = 0
        while True:
            k = jax.random.fold_in(k1, i)
            x = jax.random.normal(k, (16, 4))
            yield (x, x @ w_true)
            i += 1
    return {"w": jnp.zeros((4, 2))}, gen()


def test_fit_decreases_loss(mesh8):
    params, data = make_data()
    opt = SGD(params, mesh=mesh8, lr=0.1, average=True)
    t = Trainer(opt, quad_loss)
    out = t.fit(data, num_steps=20)
    assert out["final_loss"] < 1.0
    assert t.step_count == 20
    assert out["steps_per_sec_overall"] > 0


def test_fit_scan_chunks(mesh8):
    params, data = make_data()
    opt = SGD(params, mesh=mesh8, lr=0.1, average=True)
    t = Trainer(opt, quad_loss, scan_chunk=5)
    out = t.fit(data, num_steps=20)
    assert t.step_count == 20
    assert out["final_loss"] < 1.0


def test_checkpoint_resume(mesh8, tmp_path):
    params, data = make_data()
    opt = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9, average=True)
    t = Trainer(opt, quad_loss, checkpoint_dir=str(tmp_path / "ck"),
                checkpoint_every=5)
    t.fit(data, num_steps=10)

    # fresh trainer resumes at step 10 with identical params
    params2, data2 = make_data()
    opt2 = SGD(params2, mesh=mesh8, lr=0.05, momentum=0.9, average=True)
    t2 = Trainer(opt2, quad_loss, checkpoint_dir=str(tmp_path / "ck"))
    assert t2.maybe_restore()
    assert t2.step_count == 10
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        t2.opt.params, t.opt.params,
    )
    # and training continues from there
    t2.fit(data2, num_steps=3)
    assert t2.step_count == 13


def test_bf16_comm_close_to_f32(mesh8):
    params, data = make_data()
    batch = next(data)
    a = SGD(params, mesh=mesh8, lr=0.05, average=True)
    b = SGD(params, mesh=mesh8, lr=0.05, average=True, comm_dtype=jnp.bfloat16)
    la, _ = a.step(loss_fn=quad_loss, batch=batch)
    lb, _ = b.step(loss_fn=quad_loss, batch=batch)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-5)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=2e-2, atol=2e-3
        ),
        a.params, b.params,
    )


def test_torch_interop_roundtrip():
    torch = pytest.importorskip("torch")
    from pytorch_ps_mpi_tpu.utils.interop import (
        pytree_to_torch_params,
        to_jnp,
        to_np,
        torch_params_to_pytree,
    )

    model = torch.nn.Linear(4, 2)
    tree = torch_params_to_pytree(model.named_parameters())
    assert set(tree) == {"weight", "bias"}
    assert tree["weight"].shape == (2, 4)

    trained = jax.tree.map(lambda x: x + 1.0, tree)
    pytree_to_torch_params(trained, model)
    np.testing.assert_allclose(
        model.weight.detach().numpy(), np.asarray(trained["weight"]), rtol=1e-6
    )
    with pytest.raises(KeyError):
        pytree_to_torch_params({"nope": jnp.zeros(1)}, model)

    mixed = {"t": torch.ones(3), "j": jnp.zeros(2)}
    np_tree = to_np(mixed)
    assert isinstance(np_tree["t"], np.ndarray)
    j_tree = to_jnp(mixed, dtype=jnp.float32)
    assert j_tree["t"].dtype == jnp.float32


def test_examples_train_cli(mesh8, tmp_path, capsys):
    """The examples/train.py CLI end-to-end (mlp config, topk codec)."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from examples.train import main

    main([
        "--config", "mlp_mnist", "--steps", "4", "--batch", "16",
        "--codec", "topk", "--codec-arg", "fraction=0.25",
        "--checkpoint-dir", str(tmp_path / "ck"), "--log-every", "0",
    ])
    out = capsys.readouterr().out
    assert "final_loss" in out


def test_leader_mode_checkpoint_resume_equivalence(mesh8, tmp_path):
    """Save/restore of the ZeRO-1 leader mode: the sharded LeaderState
    (param shards + inner Adam moments, P('data')-sharded arrays) must
    round-trip through the checkpoint and continue training identically
    to an uninterrupted run."""
    from pytorch_ps_mpi_tpu import Adam

    def run(break_at):
        params, data = make_data(seed=3)
        opt = Adam(params, mesh=mesh8, lr=0.01, mode="leader")
        t = Trainer(opt, quad_loss,
                    checkpoint_dir=str(tmp_path / f"ck{break_at}"),
                    checkpoint_every=break_at)
        t.fit(data, num_steps=break_at)
        if break_at < 10:
            # fresh trainer, restore, continue with the SAME data stream
            params2, _ = make_data(seed=3)
            opt2 = Adam(params2, mesh=mesh8, lr=0.01, mode="leader")
            t2 = Trainer(opt2, quad_loss,
                         checkpoint_dir=str(tmp_path / f"ck{break_at}"))
            assert t2.maybe_restore()
            assert t2.step_count == break_at
            # `data` is the same generator t.fit consumed from, so the
            # resumed trainer continues on batch break_at+1 exactly as an
            # uninterrupted run would
            t2.fit(data, num_steps=10 - break_at)
            return t2.opt.params
        return t.opt.params

    p_resumed = run(break_at=4)
    p_straight = run(break_at=10)
    for a, b in zip(jax.tree.leaves(p_resumed), jax.tree.leaves(p_straight)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_examples_train_longcontext_cli(mesh8, capsys):
    """The examples/train_longcontext.py CLI end-to-end: ring attention
    over 8 sequence shards with remat, loss decreasing."""
    import json as _json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from examples.train_longcontext import main as lc_main

    lc_main(["--seq", "256", "--sp", "8", "--steps", "3",
             "--layers", "1", "--hidden", "32", "--heads", "2",
             "--vocab", "128"])
    out = capsys.readouterr().out
    losses = [_json.loads(ln)["loss"] for ln in out.splitlines()
              if ln.startswith("{")]
    assert len(losses) == 3
    assert losses[-1] < losses[0]


def test_adafactor_checkpoint_resume_bitexact(mesh8, tmp_path):
    """Adafactor's factored state (row/col vectors + sentinels) must
    round-trip the checkpoint path bit-exactly: resumed training equals
    uninterrupted training step for step."""
    from pytorch_ps_mpi_tpu import Adafactor

    def build():
        params, data = make_data()
        params = jax.tree.map(
            lambda p: p + 0.1, params)  # nonzero for parameter-scale
        return Adafactor(params, mesh=mesh8, lr=0.02, average=True), data

    opt, data = build()
    t = Trainer(opt, quad_loss, checkpoint_dir=str(tmp_path / "ck"),
                checkpoint_every=4)
    t.fit(data, num_steps=8)

    opt2, data2 = build()
    t2 = Trainer(opt2, quad_loss, checkpoint_dir=str(tmp_path / "ck"))
    assert t2.maybe_restore() and t2.step_count == 8
    assert_trees_equal((t2.opt.params, t2.opt.opt_state),
                       (t.opt.params, t.opt.opt_state))
    # uninterrupted twin: same data stream, same end state
    opt3, data3 = build()
    t3 = Trainer(opt3, quad_loss)
    t3.fit(data3, num_steps=8)
    for _ in range(8):   # advance the resumed run's stream to step 8
        next(data2)
    t2.fit(data2, num_steps=2)
    t3.fit(data3, num_steps=2)
    assert_trees_equal(t2.opt.params, t3.opt.params)


# -- the host phase spans of the synchronous loop -----------------------------

SYNC_SPANS = {  # span -> parent: the table of Trainer.fit and MPI_PS.step
    "trainer.step": None, "trainer.data": "trainer.step",
    "ps.step": "trainer.step", "ps.prepare": "ps.step",
    "ps.dispatch": "ps.step", "ps.wait": "ps.step",
    "trainer.loss_fetch": "trainer.step"}


def test_fit_records_the_phase_spans_of_every_step(mesh8, annotations_made):
    from pytorch_ps_mpi_tpu import telemetry

    params, data = make_data()
    t = Trainer(SGD(params, mesh=mesh8, lr=0.1, average=True), quad_loss)
    t.fit(data, 2)
    rec = telemetry.configure()
    set_up = len(rec)  # it starts with the rows of the set-up log
    try:
        out = t.fit(data, 3)
    finally:
        telemetry.disable()
    rows = rec.events()[set_up:]
    assert sorted(n for _, n, _ in annotations_made) == sorted(
        e["name"] for e in rows)
    assert sorted(e["name"] for e in rows) == sorted(3 * list(SYNC_SPANS))
    assert sorted({e["step"] for e in rows}) == [3, 4, 5]
    by_step = {s: {e["name"]: e for e in rows if e["step"] == s}
               for s in (3, 4, 5)}
    end = lambda e: e["ts"] + e["dur"]
    for step, mine in by_step.items():
        assert set(mine) == set(SYNC_SPANS)
        fetch = mine.pop("trainer.loss_fetch")
        for name, e in mine.items():  # a parent covers its children
            parent = SYNC_SPANS[name]
            assert e.get("parent") == parent, e
            if parent:
                p = mine[parent]
                assert p["ts"] <= e["ts"] and end(e) <= end(p) + 1e-9
        # the phases of ps.step follow one another
        assert (mine["ps.prepare"]["ts"] <= mine["ps.dispatch"]["ts"]
                <= mine["ps.wait"]["ts"])
        # the loss is on the row that fetched it, under the step it is of:
        # one step late, once the next step is launched and has waited for
        # this one; the last of the call after the loop, under no step
        assert np.isfinite(fetch["attrs"]["loss"])
        assert "loss" not in mine["trainer.step"].get("attrs", {})
        if step < 5:
            later = by_step[step + 1]
            assert fetch.get("parent") == "trainer.step"
            assert end(later["ps.step"]) <= fetch["ts"]
            assert end(fetch) <= end(later["trainer.step"]) + 1e-9
        else:
            assert fetch.get("parent") is None
            assert end(mine["trainer.step"]) <= fetch["ts"]
            assert fetch["attrs"]["loss"] == out["final_loss"]
        # what a row carried before, it still carries; and who was ahead
        assert mine["ps.step"]["attrs"]["step_time"] > 0
        assert "msg_bytes" in mine["ps.step"]["attrs"]
        ahead = mine["ps.wait"]["attrs"]["host_ahead"]
        assert ahead in (0.0, 1.0)
        assert mine["ps.step"]["attrs"]["host_ahead"] == ahead
    # the fit before this one drained: its last step was over
    assert by_step[3]["ps.wait"]["attrs"]["host_ahead"] == 0.0
    assert 0.0 <= out["host_ahead"] <= 1.0


@pytest.mark.parametrize("path", ["loss_fn", "grads"])
def test_step_spans_on_both_fused_paths(mesh8, path):
    from pytorch_ps_mpi_tpu import telemetry

    params, data = make_data()
    opt = SGD(params, mesh=mesh8, lr=0.1, average=True)
    if path == "loss_fn":
        step = lambda: opt.step(loss_fn=quad_loss, batch=next(data))
    else:
        g = {"w": jnp.ones((8, 4, 2))}
        step = lambda: opt.step(grads=g)
    step()
    rec = telemetry.configure()
    set_up = len(rec)  # it starts with the rows of the set-up log
    try:
        _, out = step()
    finally:
        telemetry.disable()
    rows = rec.events()[set_up:]
    assert [e["name"] for e in rows] == ["ps.prepare", "ps.dispatch",
                                         "ps.wait", "ps.step"]
    assert all(e["step"] == 2 for e in rows)  # the optimizer's own count
    assert [e.get("parent") for e in rows] == ["ps.step"] * 3 + [None]
    wait, whole = rows[2]["attrs"], rows[3]["attrs"]
    assert wait["host_ahead"] == whole["host_ahead"] == out["host_ahead"]
    assert out["host_ahead"] in ((0.0, 1.0) if path == "loss_fn" else (0.0,))


# -- one step in flight --------------------------------------------------------

def waited_losses(mesh8, n):
    """Losses and end state of ``n`` steps that each wait for themselves."""
    params, data = make_data()
    opt = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9, average=True)
    losses = []
    for _ in range(n):
        loss, _ = opt.step(loss_fn=quad_loss, batch=next(data))
        losses.append(float(loss))
        jax.block_until_ready(opt.params)
    return losses, opt


@pytest.mark.parametrize("n", [1, 4])
def test_fit_returns_the_loss_of_its_last_step(mesh8, n):
    """``fit`` drains: ``final_loss`` is step n's loss exactly, a float,
    and the next call goes on along the same trajectory."""
    losses, twin = waited_losses(mesh8, n + 3)
    params, data = make_data()
    t = Trainer(SGD(params, mesh=mesh8, lr=0.05, momentum=0.9, average=True),
                quad_loss)
    out = t.fit(data, n)
    assert type(out["final_loss"]) is float
    assert out["final_loss"] == losses[n - 1]
    assert t.fit(data, 3)["final_loss"] == losses[n + 2]
    assert_trees_equal((t.opt.params, tuple(t.opt.opt_state)),
                       (twin.params, tuple(twin.opt_state)))


def test_checkpoint_taken_mid_fit_holds_that_steps_state(mesh8, tmp_path):
    """A checkpoint inside ``fit`` is taken with its step in flight: it
    waits through ``state_dict`` and holds what a loop that waits after
    every step had at that step."""
    params, data = make_data()
    t = Trainer(SGD(params, mesh=mesh8, lr=0.05, momentum=0.9, average=True,
                    donate_buffers=True),
                quad_loss, checkpoint_dir=str(tmp_path / "ck"),
                checkpoint_every=2)
    del params
    t.fit(data, 5)
    _, twin = waited_losses(mesh8, 2)
    saved = t.ckpt.restore(t._state(), step=2)
    assert int(saved["trainer_step"]) == saved["step_count"] == 2
    assert_trees_equal(
        (saved["params"], tuple(saved["opt_state"]), saved["rng_data"]),
        (twin.params, tuple(twin.opt_state), twin.state_dict()["rng_data"]))


@pytest.mark.parametrize("recorder", ["off", "on"])
def test_fit_waits_the_same_with_the_recorder_on_and_off(
        mesh8, monkeypatch, recorder):
    """The loop is one loop: ``step`` n blocks on step n-1's loss, ``fit``
    fetches a loss one step late and the last one at return, whether or
    not a recorder is there to take the rows."""
    from pytorch_ps_mpi_tpu import telemetry

    params, data = make_data()
    opt = SGD(params, mesh=mesh8, lr=0.1, average=True)
    t = Trainer(opt, quad_loss)
    log, launched = [], []
    real_wait, real_step = jax.block_until_ready, opt.step

    class Loss:  # stands in for the array `step` returns
        def __init__(self, step):
            self.step = step

        def __float__(self):
            log.append(("fetch", self.step))
            return float(launched[self.step - 1])

    def wait(x):
        if x is not None:
            log.append(("wait", 1 + next(
                i for i, loss in enumerate(launched) if loss is x)))
        return real_wait(x)

    def step(**kw):
        loss, out = real_step(**kw)
        launched.append(loss)
        log.append(("returned", len(launched)))
        return Loss(len(launched)), out

    monkeypatch.setattr(jax, "block_until_ready", wait)
    monkeypatch.setattr(opt, "step", step)
    if recorder == "on":
        telemetry.configure()
    try:
        t.fit(data, 2)
        t.fit(data, 3, log_every=2)
    finally:
        telemetry.disable()
    R, W, F = "returned", "wait", "fetch"  # `step` n returned, ...
    assert log == [
        (R, 1), (W, 1), (R, 2), (F, 1), (F, 2),       # fit(2): drained
        (W, 2), (R, 3), (W, 3), (R, 4), (F, 3), (F, 4),  # log_every: step 4
        (W, 4), (R, 5), (F, 5)]


def test_recorder_off_fit_makes_no_annotation_and_no_row(
        mesh8, monkeypatch, annotations_made):
    from pytorch_ps_mpi_tpu import telemetry

    telemetry.disable()
    params, data = make_data()
    opt = SGD(params, mesh=mesh8, lr=0.1, average=True)
    trainer = Trainer(opt, quad_loss)
    trainer.fit(data, 2)  # set-up, which writes its rows, is over
    rows = []
    monkeypatch.setattr(telemetry.FlightRecorder, "event",
                        lambda self, name, **kw: rows.append(name))
    trainer.fit(data, 3)
    opt.step(loss_fn=quad_loss, batch=next(data))
    assert annotations_made == [] and rows == []
    assert telemetry.get_recorder() is None
