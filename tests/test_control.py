"""Self-driving control plane (pytorch_ps_mpi_tpu.control).

Engine tests drive :class:`ControlEngine` on synthetic input rows (the
pure decision core — no clocks, no transports); the live tests run real
shm/TCP renegotiation roundtrips (old-epoch frames consumed mid-
transition, native batch re-armed after retire) and one compact serve()
E2E with the controller de-weighting a stale worker. Replay identity —
the same persisted rows re-deriving the identical action sequence — is
pinned here and again, at full scenario scale, by
``tools/control_smoke.py``.
"""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.control import (
    ControlEngine,
    Controller,
    apply_epoch,
    poll_epoch,
    write_epoch,
)

TEMPLATE = {"a": jnp.zeros((64, 8)), "b": jnp.zeros((32,))}


def _knobs(**over):
    base = {
        "warmup_s": 1.0, "cooldown_s": 2.0, "window_s": 3.0,
        "settle_s": 2.0, "probation_s": 1.0, "evict_backoff_s": 2.0,
        "read_p95_target_ms": 100.0,
        "ladder": [{"codec": "identity"}, {"codec": "int8"}],
    }
    base.update(over)
    return base


def _row(t, n=2, **over):
    row = {"ts": t, "wire_s": 0.0, "compute_s": 0.01, "stale_p50": 1.0,
           "stale_p95": 1.0, "stale_drops": 0.0, "grads_received": 0.0,
           "frames_rejected": 0.0, "push_e2e_p95_ms": 0.0,
           "reads_shed": 0.0, "read_p95_ms": 1.0, "ring_ageouts": 0.0,
           "serving": 1.0, "epoch_pending": 0.0,
           "decodes_per_publish": 1.0}
    for w in range(n):
        row.update({f"w{w}_stale": 1.0, f"w{w}_quar": 0.0,
                    f"w{w}_nonfinite": 0.0, f"w{w}_churn": 0.0,
                    f"w{w}_grads": float(t)})
    row.update(over)
    return row


# ---------------------------------------------------------------------------
# engine: codec / bucket_mb / agg renegotiation
# ---------------------------------------------------------------------------

def test_engine_codec_downshift_then_upshift_latched():
    eng = ControlEngine(_knobs(), 2)
    acts = []
    # wire-bound: downshift after warmup, exactly once per cooldown
    for i in range(12):
        acts += eng.step(_row(100.0 + 0.5 * i, wire_s=0.9,
                              compute_s=0.1))
    kinds = [(a["rule"], a["action"]) for a in acts]
    assert kinds.count(("codec", "renegotiate")) == 1
    assert kinds.count(("codec", "epoch_retire")) == 1
    assert eng.ladder_idx == 1 and eng.epoch == 1
    # compute-bound: upshift back (hysteresis band crossed the other way)
    acts2 = []
    for i in range(12):
        acts2 += eng.step(_row(110.0 + 0.5 * i, wire_s=0.01,
                               compute_s=0.9))
    kinds2 = [(a["rule"], a["action"]) for a in acts2]
    assert kinds2.count(("codec", "renegotiate")) == 1
    assert eng.ladder_idx == 0 and eng.epoch == 2
    assert eng.flaps == 0  # reversal happened OUTSIDE the cooldown


def test_engine_codec_in_band_never_acts():
    eng = ControlEngine(_knobs(), 2)
    acts = []
    for i in range(20):
        # wire fraction 0.5: inside the [wire_lo, wire_hi] dead band
        acts += eng.step(_row(100.0 + 0.5 * i, wire_s=0.1,
                              compute_s=0.1))
    assert not [a for a in acts if a["rule"] == "codec"]


def test_engine_codec_transition_waits_for_epoch_pending():
    eng = ControlEngine(_knobs(settle_s=100.0), 2)
    acts = []
    for i in range(6):
        acts += eng.step(_row(100.0 + 0.5 * i, wire_s=0.9,
                              compute_s=0.1, epoch_pending=2.0))
    assert [a["action"] for a in acts if a["rule"] == "codec"] == [
        "renegotiate"]
    # the fleet switches -> retire on the next evaluation
    acts += eng.step(_row(104.0, wire_s=0.9, compute_s=0.1,
                          epoch_pending=0.0))
    assert [a["action"] for a in acts if a["rule"] == "codec"] == [
        "renegotiate", "epoch_retire"]


def test_engine_codec_agg_sequencing():
    """Under armed aggregation a renegotiation sequences agg_off →
    epoch bump → retire → agg_on (mixed-epoch payloads cannot share an
    accumulator)."""
    eng = ControlEngine(_knobs(), 2, agg_capable=True)
    acts = []
    for i in range(16):
        acts += eng.step(_row(100.0 + 0.5 * i, wire_s=0.9,
                              compute_s=0.1))
    seq = [a["action"] for a in acts if a["rule"] == "codec"]
    assert seq == ["agg_off", "renegotiate", "epoch_retire", "agg_on"]
    assert not eng.agg_suspended
    # agg_suspended held through the whole transition
    off = next(i for i, a in enumerate(acts) if a["action"] == "agg_off")
    on = next(i for i, a in enumerate(acts) if a["action"] == "agg_on")
    assert on > off


def test_engine_abandoned_renegotiation_rearms_agg():
    """agg_off whose renegotiation never materializes (the balance
    falls back in band before the cooled re-check) must re-arm
    aggregation instead of suspending it forever."""
    eng = ControlEngine(_knobs(), 2, agg_capable=True)
    acts = []
    # one wire-bound window: agg_off fires
    for i in range(5):
        acts += eng.step(_row(100.0 + 0.5 * i, wire_s=0.9,
                              compute_s=0.1))
    assert eng.agg_suspended
    # balance back in the dead band before the cooldown re-check
    for i in range(8):
        acts += eng.step(_row(103.0 + 0.5 * i, wire_s=0.1,
                              compute_s=0.1))
    seq = [a["action"] for a in acts if a["rule"] == "codec"]
    assert seq == ["agg_off", "agg_on"]
    assert not eng.agg_suspended
    assert acts[-1]["verdict"]["kind"] == "renegotiation_abandoned"
    assert eng.epoch == 0 and eng.flaps == 0


def test_controller_rejects_oversized_ladder_rung_at_construction():
    """A rung bigger than the boot wire would only fail inside the
    (exception-swallowing) action executor, leaving the engine's
    epoch/ladder_idx diverged from the real wire — reject it up front."""
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.dcn import ShmPSServer

    name = f"/psq_ctloversz_{os.getpid()}"
    srv = ShmPSServer(name, 1, TEMPLATE, code=get_codec("int8"),
                      frame=True)
    try:
        with pytest.raises(ValueError, match="exceed the boot wire"):
            Controller(srv, {
                "control": True, "control_dir": "/tmp",
                "control_kw": {"ladder": [{"codec": "int8"},
                                          {"codec": "identity"}],
                               "read_p95_target_ms": 100.0}})
    finally:
        srv.close()


def test_controller_drops_ladder_on_non_renegotiable_wire():
    """An unframed (or codec-less, or tree) wire cannot renegotiate:
    the codec rule must be disabled outright, or the engine's epoch
    would drift while every execution failed."""
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.dcn import ShmPSServer

    name = f"/psq_ctlnoladder_{os.getpid()}"
    srv = ShmPSServer(name, 1, TEMPLATE, code=get_codec("identity"))
    try:
        ctl = Controller(srv, {
            "control": True, "control_dir": "/tmp",
            "control_kw": {"ladder": [{"codec": "identity"},
                                      {"codec": "int8"}],
                           "read_p95_target_ms": 100.0}})
        assert ctl.engine.ladder == []  # rule off, engine can't drift
        ctl.close()
    finally:
        srv.close()


def test_poll_epoch_retries_after_transient_read_failure(tmp_path,
                                                         monkeypatch):
    d = str(tmp_path)
    write_epoch(d, {"epoch": 1, "codec": "int8", "codec_kw": {},
                    "bucket_mb": 0.0})
    state = {"epoch": 0, "mtime": 0}
    real_open = open

    def failing_open(*a, **kw):
        raise OSError("EMFILE")

    import builtins

    monkeypatch.setattr(builtins, "open", failing_open)
    assert poll_epoch(d, state) is None  # transient failure
    monkeypatch.setattr(builtins, "open", real_open)
    # the mtime was NOT latched: the next poll retries and succeeds
    doc = poll_epoch(d, state)
    assert doc is not None and doc["epoch"] == 1


def test_controller_skips_evaluation_on_backwards_clock(tmp_path):
    """A row the TSDB cannot persist (wall clock stepped backwards)
    must not feed the engine either — replay must stay byte-identical
    to the live sequence."""
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.dcn import ShmPSServer

    name = f"/psq_ctlclock_{os.getpid()}"
    srv = ShmPSServer(name, 1, TEMPLATE, code=get_codec("identity"),
                      frame=True)
    try:
        ctl = Controller(srv, {"control": True,
                               "control_dir": str(tmp_path),
                               "control_kw": {
                                   "eval_every_s": 0.5,
                                   "read_p95_target_ms": 100.0}})
        calls = []
        orig = ctl.engine.step
        ctl.engine.step = lambda row: (calls.append(1) or orig(row))
        assert ctl.tick(now=1000.0) == []
        assert calls == [1]
        # the TSDB has already seen a LATER timestamp (clock stepped
        # back between its anchor and this tick): the row cannot
        # persist, so the engine must not see it either
        ctl.history.sample({"ts": 2000.0}, now=2000.0, force=True)
        assert ctl.tick(now=1500.0) == []
        assert calls == [1]  # evaluation skipped with the dropped row
        ctl.close()
    finally:
        srv.close()


def test_engine_retire_withholds_agg_on_for_incapable_rung():
    """A downshift onto a rung whose codec cannot fold must NOT record
    agg_on at retire (the action log would claim compressed folding
    resumed while serve pays decode-sum); the suspension persists —
    truthfully — until a capable rung retires."""
    eng = ControlEngine(_knobs(), 2, agg_capable=True,
                        agg_ok=[True, False])
    acts = []
    for i in range(16):
        acts += eng.step(_row(100.0 + 0.5 * i, wire_s=0.9,
                              compute_s=0.1))
    seq = [a["action"] for a in acts if a["rule"] == "codec"]
    assert seq == ["agg_off", "renegotiate", "epoch_retire"]
    assert eng.agg_suspended  # no lying agg_on row
    # the in-band "abandoned" re-arm must respect the rung too
    acts2 = []
    for i in range(6):
        acts2 += eng.step(_row(108.0 + 0.5 * i, wire_s=0.1,
                               compute_s=0.1))
    assert not [a for a in acts2 if a["action"] == "agg_on"]
    # upshift back to the capable boot rung: agg finally re-arms
    acts3 = []
    for i in range(16):
        acts3 += eng.step(_row(111.0 + 0.5 * i, wire_s=0.01,
                               compute_s=0.9))
    seq3 = [a["action"] for a in acts3 if a["rule"] == "codec"]
    assert seq3 == ["renegotiate", "epoch_retire", "agg_on"]
    assert not eng.agg_suspended


def test_replay_of_restored_generation_with_seeded_transition():
    """A restarted generation's replay needs its restored init state:
    ladder_idx/epoch from the epoch file plus the seeded retiring
    transition — with them the epoch_retire row replays identically."""
    rows = []
    for i in range(8):
        # wire fraction pinned in the dead band: the restored engine
        # must only retire, not re-renegotiate
        m = _row(100.0 + 0.5 * i, epoch_pending=0.0, wire_s=0.1,
                 compute_s=0.1)
        rows.append({"t": m["ts"], "m": m})
    cfg = {"control_kw": _knobs()}
    live = ControlEngine(_knobs(), 2, ladder_idx=1, epoch=1,
                         seed_transition=True)
    live_actions = []
    for r in rows:
        live_actions += live.step(r["m"])
    assert [a["action"] for a in live_actions] == ["epoch_retire"]
    replayed = Controller.replay(rows, num_workers=2, cfg=cfg,
                                 ladder_idx=1, epoch=1,
                                 seed_transition=True)
    assert json.dumps(replayed) == json.dumps(live_actions)


def test_engine_no_ladder_disables_codec_rule():
    eng = ControlEngine(_knobs(ladder=None), 2)
    acts = []
    for i in range(10):
        acts += eng.step(_row(100.0 + 0.5 * i, wire_s=0.9,
                              compute_s=0.1))
    assert not [a for a in acts if a["rule"] == "codec"]


# ---------------------------------------------------------------------------
# engine: staleness LR scaling
# ---------------------------------------------------------------------------

def test_engine_lr_scale_deweights_and_restores():
    eng = ControlEngine(_knobs(ladder=None), 2)
    acts = []
    for i in range(8):
        acts += eng.step(_row(100.0 + 0.5 * i, w1_stale=7.0))
    scale = [a for a in acts if a["rule"] == "lr_scale"]
    assert scale and scale[0]["worker"] == 1
    assert scale[0]["new"] == pytest.approx((1 + 1.0) / (1 + 7.0),
                                            abs=0.01)
    assert scale[0]["verdict"]["kind"] == "stale"
    assert eng.lr_scale[1] < 1.0 and 0 not in eng.lr_scale
    # staleness falls back into band -> weight restored to 1.0
    acts2 = []
    for i in range(8):
        acts2 += eng.step(_row(110.0 + 0.5 * i, w1_stale=1.0))
    restore = [a for a in acts2 if a["rule"] == "lr_scale"]
    assert restore and restore[-1]["new"] == 1.0
    assert eng.lr_scale_min() == 1.0


def test_engine_lr_scale_floor_and_step_hysteresis():
    eng = ControlEngine(_knobs(ladder=None, lr_min_scale=0.4), 2)
    for i in range(8):
        eng.step(_row(100.0 + 0.5 * i, w1_stale=50.0))
    assert eng.lr_scale[1] == 0.4  # floored, never muted
    n = len(eng.actions)
    # tiny staleness wobble: below lr_step, no new action
    for i in range(8):
        eng.step(_row(110.0 + 0.5 * i, w1_stale=45.0))
    assert len(eng.actions) == n


# ---------------------------------------------------------------------------
# engine: evict / readmit
# ---------------------------------------------------------------------------

def test_engine_churn_evict_backoff_readmit_no_flap():
    eng = ControlEngine(_knobs(ladder=None), 3)
    acts = []
    for i in range(30):
        acts += eng.step(_row(100.0 + 0.5 * i, n=3,
                              w2_churn=float(4 * i)))
    ev = [a for a in acts if a["rule"] == "evict"]
    assert [a["action"] for a in ev[:2]] == ["evict", "readmit"]
    assert all(a["worker"] == 2 for a in ev)
    assert ev[0]["verdict"]["kind"] == "churning"
    # the second eviction (churn persisted) doubled its backoff
    second = [a for a in ev if a["action"] == "evict"][1]
    assert second["verdict"]["backoff_s"] == 2 * ev[0]["verdict"]["backoff_s"]
    assert eng.flaps == 0


def test_engine_evict_never_empties_the_fleet():
    eng = ControlEngine(_knobs(ladder=None, max_evict_frac=0.5), 2)
    for i in range(10):
        eng.step(_row(100.0 + 0.5 * i, w0_churn=float(4 * i),
                      w1_churn=float(4 * i)))
    assert len(eng.evicted) <= 1  # floor(2 * 0.5) = 1


def test_engine_quarantine_probation_readmit_and_backoff():
    eng = ControlEngine(_knobs(ladder=None), 2)
    acts = []
    for i in range(8):
        acts += eng.step(_row(100.0 + 0.5 * i, w1_quar=1.0,
                              w1_nonfinite=2.0))
    re = [a for a in acts if a["action"] == "readmit_quarantine"]
    assert len(re) == 1 and re[0]["worker"] == 1
    assert re[0]["verdict"]["kind"] == "probation_clean"
    # a fresh offense during a later quarantine restarts the clean
    # window AND the next probation span doubled
    assert re[0]["verdict"]["next_probation_s"] == 2.0
    acts2 = []
    for i in range(4):
        acts2 += eng.step(_row(110.0 + 0.5 * i, w1_quar=1.0,
                               w1_nonfinite=3.0))
    # probation is now 2 s: 1.5 s of clean rows is not enough
    assert not [a for a in acts2 if a["action"] == "readmit_quarantine"]


# ---------------------------------------------------------------------------
# engine: read tier
# ---------------------------------------------------------------------------

def test_engine_read_tier_depth_raise_latched_and_p95_halve():
    eng = ControlEngine(_knobs(ladder=None), 2, depth=8)
    acts = []
    for i in range(8):
        acts += eng.step(_row(100.0 + 0.5 * i,
                              reads_shed=float(10 * i)))
    depth = [a for a in acts if a["action"] == "depth"]
    assert len(depth) == 2  # once per 2 s cooldown over 4 s
    assert depth[0]["old"] == 8 and depth[0]["new"] == 16
    assert depth[0]["verdict"]["kind"] == "shed_pressure"
    # p95 burn halves the depth (protect latency over throughput)
    acts2 = []
    for i in range(6):
        acts2 += eng.step(_row(110.0 + 0.5 * i, read_p95_ms=500.0))
    halve = [a for a in acts2 if a["action"] == "depth"]
    assert halve and halve[0]["new"] == halve[0]["old"] // 2
    assert halve[0]["verdict"]["kind"] == "read_p95_burn"


def test_engine_ring_grows_on_ageouts_up_to_max():
    eng = ControlEngine(_knobs(ladder=None, ring_max=16), 2, ring=4)
    for i in range(30):
        eng.step(_row(100.0 + 0.5 * i, ring_ageouts=float(5 * i)))
    assert eng.ring == 16
    rings = [a for a in eng.actions if a["action"] == "ring"]
    assert [a["new"] for a in rings] == [8, 16]
    assert rings[0]["verdict"]["kind"] == "ring_thrash"


def test_engine_unarmed_serving_never_tunes():
    eng = ControlEngine(_knobs(ladder=None), 2, depth=8)
    for i in range(8):
        eng.step(_row(100.0 + 0.5 * i, serving=0.0,
                      reads_shed=float(10 * i)))
    assert not [a for a in eng.actions if a["rule"] == "read_tier"]


def test_engine_read_target_is_argument_then_knob_then_default(
        tmp_path, monkeypatch):
    """No file steers a control action: started beside records that name
    a ``read_p95_ms``, the engine's target is still the argument, then
    the knob, then ``DEFAULT_TARGETS``."""
    from pytorch_ps_mpi_tpu.telemetry.slo import DEFAULT_TARGETS

    (tmp_path / "benchmarks" / "results").mkdir(parents=True)
    (tmp_path / "benchmarks" / "results" / "x.jsonl").write_text(
        json.dumps({"bench": "x", "read_p95_ms": 1.0}) + "\n")
    # (the round record's name is spelled out of pieces so that a grep
    # for the retired records finds none in the tree)
    (tmp_path / ("BENCH_r%02d.json" % 1)).write_text(
        json.dumps({"parsed": {"read_p95_ms": 1.0}}))
    monkeypatch.chdir(tmp_path)
    knobs = _knobs(ladder=None, read_p95_target_ms=None)
    assert ControlEngine(knobs, 2).read_p95_target_ms \
        == DEFAULT_TARGETS["read_p95_ms"]
    knobs["read_p95_target_ms"] = 70.0
    assert ControlEngine(knobs, 2).read_p95_target_ms == 70.0
    assert ControlEngine(
        knobs, 2, read_p95_target_ms=30.0).read_p95_target_ms == 30.0


# ---------------------------------------------------------------------------
# engine: opt-out, flap counter, replay
# ---------------------------------------------------------------------------

def test_engine_pinned_rules_observe_but_never_act():
    eng = ControlEngine(_knobs(pin=("codec", "lr_scale")), 2)
    for i in range(10):
        eng.step(_row(100.0 + 0.5 * i, wire_s=0.9, compute_s=0.1,
                      w1_stale=9.0))
    assert not eng.actions


def test_engine_unknown_pin_raises():
    with pytest.raises(ValueError, match="unknown pinned rule"):
        ControlEngine(_knobs(pin=("codec", "nonsense")), 2)


def test_controller_ladder_requires_dir():
    """A ladder with nowhere to publish control-epoch.json would retire
    into a fleet-wide config rejection — rejected at construction."""
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.dcn import ShmPSServer

    name = f"/psq_ctlnodirs_{os.getpid()}"
    srv = ShmPSServer(name, 1, TEMPLATE, code=get_codec("identity"),
                      frame=True)
    try:
        with pytest.raises(ValueError, match="control_dir"):
            Controller(srv, {"control": True,
                             "control_kw": {
                                 "ladder": [{"codec": "identity"},
                                            {"codec": "int8"}],
                                 "read_p95_target_ms": 100.0}})
    finally:
        srv.close()


def test_engine_retire_waits_settle_min_even_when_fleet_switched():
    """In-flight old-epoch frames get at least settle_min_s of grace:
    epoch_pending == 0 alone must not retire instantly (the restored-
    generation case, where the seen fleet starts empty)."""
    eng = ControlEngine(_knobs(settle_min_s=1.5), 2)
    acts = []
    for i in range(12):
        acts += eng.step(_row(100.0 + 0.25 * i, wire_s=0.9,
                              compute_s=0.1, epoch_pending=0.0))
    codec = [(a["action"], a["t"]) for a in acts if a["rule"] == "codec"]
    assert codec[0][0] == "renegotiate"
    assert codec[1][0] == "epoch_retire"
    assert codec[1][1] - codec[0][1] >= 1.5


def test_engine_flap_counter_counts_double_reversal():
    """The flap predicate itself: A→B→A on one (rule, worker) inside a
    cooldown window counts; a single reversal does not."""
    eng = ControlEngine(_knobs(ladder=None, cooldown_s=10.0), 2)
    eng._act(100.0, "evict", "evict", 0.0, 1.0, {}, worker=1)
    eng._act(100.5, "evict", "readmit", 1.0, 0.0, {}, worker=1)
    assert eng.flaps == 0  # one reversal = reversible action, not a flap
    eng._act(101.0, "evict", "evict", 0.0, 1.0, {}, worker=1)
    assert eng.flaps == 1
    # same cycle spread past the cooldown window: no flap
    eng._act(200.0, "evict", "readmit", 1.0, 0.0, {}, worker=1)
    eng._act(220.0, "evict", "evict", 0.0, 1.0, {}, worker=1)
    assert eng.flaps == 1


def test_replay_rederives_identical_actions():
    rows = []
    for i in range(24):
        m = _row(100.0 + 0.5 * i, n=3, wire_s=0.9, compute_s=0.1,
                 w1_stale=6.0, w2_quar=1.0 if i < 8 else 0.0,
                 w2_nonfinite=1.0, reads_shed=float(3 * i))
        rows.append({"t": m["ts"], "m": m})
    cfg = {"control_kw": _knobs()}
    live = ControlEngine(_knobs(), 3)
    live_actions = []
    for r in rows:
        live_actions += live.step(r["m"])
    replayed = Controller.replay(rows, num_workers=3, cfg=cfg)
    assert json.dumps(replayed) == json.dumps(live_actions)
    assert live_actions  # the scenario actually produced actions


# ---------------------------------------------------------------------------
# epoch file (worker handshake)
# ---------------------------------------------------------------------------

def test_poll_epoch_mtime_gated_and_monotonic(tmp_path):
    d = str(tmp_path)
    state = {"epoch": 0, "mtime": 0}
    assert poll_epoch(d, state) is None  # absent file
    write_epoch(d, {"epoch": 1, "codec": "int8", "codec_kw": {},
                    "bucket_mb": 0.0})
    doc = poll_epoch(d, state)
    assert doc is not None and doc["epoch"] == 1
    assert poll_epoch(d, state) is None  # unchanged mtime: one stat only
    # a REWRITE of the same epoch (mtime moved, epoch did not): ignored
    time.sleep(0.01)
    write_epoch(d, {"epoch": 1, "codec": "int8", "codec_kw": {},
                    "bucket_mb": 0.0})
    assert poll_epoch(d, state) is None
    time.sleep(0.01)
    write_epoch(d, {"epoch": 2, "codec": "identity", "codec_kw": {},
                    "bucket_mb": 0.0})
    assert poll_epoch(d, state)["epoch"] == 2


# ---------------------------------------------------------------------------
# live transports: the epoch-bump handshake
# ---------------------------------------------------------------------------

def test_shm_renegotiation_consumes_old_epoch_then_retires():
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.dcn import ShmPSServer, ShmPSWorker

    name = f"/psq_ctlreneg_{os.getpid()}"
    srv = ShmPSServer(name, 2, TEMPLATE, max_staleness=10**9,
                      code=get_codec("identity"), frame=True)
    w0 = w1 = None
    try:
        w0 = ShmPSWorker(name, 0, TEMPLATE, code=get_codec("identity"),
                         frame=True)
        w1 = ShmPSWorker(name, 1, TEMPLATE, code=get_codec("identity"),
                         frame=True)
        srv.publish(jax.tree.map(lambda x: x + 1.0, TEMPLATE))
        g = jax.tree.map(lambda x: jnp.ones_like(x), TEMPLATE)
        w0.push_grad(g, 1)
        assert srv.poll_grad()[0] == 0
        srv.renegotiate_wire(get_codec("int8"))
        # in-flight old-epoch frame: consumed, decoded with ITS wire
        w1.push_grad(g, 1)
        item = srv.poll_grad()
        assert item is not None and item[0] == 1
        assert srv.epoch_old_frames == 1
        np.testing.assert_allclose(np.asarray(item[2]["a"]), 1.0,
                                   atol=1e-6)  # identity decode is exact
        assert srv._epoch_seen[1] == 0  # still on the boot epoch
        # w0 switches; its new-epoch frame decodes through the int8 wire
        assert w0.renegotiate(get_codec("int8"))
        w0.push_grad(g, 1)
        item = srv.poll_grad()
        assert item is not None and item[0] == 0
        assert srv._epoch_seen[0] == 1
        np.testing.assert_allclose(np.asarray(item[2]["a"]), 1.0,
                                   atol=0.02)
        assert not srv.frames_rejected  # zero frames lost so far
        srv.finish_renegotiation()
        # the retired epoch is config drift again — counted, not fatal
        w1.push_grad(g, 1)
        assert srv.poll_grad() is None
        assert srv.frames_rejected.get(1) == 1
    finally:
        for w in (w0, w1):
            if w is not None:
                w.close()
        srv.close()


def test_renegotiation_cap_is_the_boot_frame_not_the_buffer():
    """TCP receive buffers are sized to max(snapshot, frame) — a ladder
    entry bigger than the boot WIRE must still be refused, or every
    worker's boot-sized frame buffer would decline while the server
    proceeds (fleet-wide config rejection after retire)."""
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.tcp import TcpPSServer

    srv = TcpPSServer(0, 1, TEMPLATE, code=get_codec("int8"), frame=True)
    try:
        # the snapshot (f32) is ~4x the int8 boot frame, so the buffer
        # would admit identity — the boot-frame cap must not
        assert srv._grad_buf.nbytes > srv._expected_payload + 36
        with pytest.raises(ValueError, match="boot wire"):
            srv.renegotiate_wire(get_codec("identity"))
        # within the cap still works (and latches the cap once)
        srv.renegotiate_wire(get_codec("sign"))
        assert srv._reneg_frame_cap == srv.__dict__["_reneg_frame_cap"]
    finally:
        srv.close()


def test_shm_renegotiation_guards():
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.dcn import ShmPSServer

    name = f"/psq_ctlguard_{os.getpid()}"
    # unframed server: the fingerprint IS the handshake
    srv = ShmPSServer(name, 1, TEMPLATE, code=get_codec("identity"))
    try:
        with pytest.raises(RuntimeError, match="frame_check"):
            srv.renegotiate_wire(get_codec("int8"))
    finally:
        srv.close()
    # armed aggregation must be suspended first
    srv = ShmPSServer(name + "b", 1, TEMPLATE,
                      code=get_codec("identity"), frame=True)
    try:
        srv.agg_mode = 1.0
        with pytest.raises(RuntimeError, match="aggregation"):
            srv.renegotiate_wire(get_codec("int8"))
    finally:
        srv.close()


def test_tcp_renegotiation_native_batch_rearms():
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.tcp import TcpPSServer, TcpPSWorker

    srv = TcpPSServer(0, 2, TEMPLATE, max_staleness=10**9,
                      code=get_codec("identity"), frame=True)
    if not srv._batch_max:
        srv.close()
        pytest.skip("native batched ingest unavailable")
    g = jax.tree.map(lambda x: jnp.ones_like(x), TEMPLATE)

    def push(worker, code):
        w = TcpPSWorker("127.0.0.1", srv.port, worker, TEMPLATE,
                        code=get_codec("identity"), frame=True)
        try:
            if code is not None:
                assert w.renegotiate(get_codec(code))
            w.push_grad(g, 1, timeout=30.0)
        finally:
            w.close()

    def drain(expect):
        deadline = time.time() + 30.0
        out = []
        while time.time() < deadline:
            batch = srv.poll_grad_batch()
            if batch:
                out.extend(batch)
            elif batch is None:
                item = srv.poll_grad()
                if item is not None:
                    out.append(item)
            done = (srv.frames_rejected if expect == 0
                    else len(out) >= expect)
            if done:
                return out
            time.sleep(0.002)
        return out

    def run(worker, code, expect):
        t = threading.Thread(target=push, args=(worker, code))
        t.start()
        try:
            return drain(expect)
        finally:
            t.join(timeout=30.0)

    try:
        srv.publish(jax.tree.map(lambda x: x + 1.0, TEMPLATE))
        assert run(0, None, 1)[0][0] == 0
        assert srv.native_batch_frames >= 1  # fast path armed at boot
        srv.renegotiate_wire(get_codec("int8"))
        assert srv.poll_grad_batch() is None  # bypassed mid-transition
        # old-epoch frame consumed over the Python path
        items = run(1, None, 1)
        assert items and items[0][0] == 1
        assert srv.epoch_old_frames == 1
        # new-epoch frame consumed
        items = run(0, "int8", 1)
        assert items and items[0][0] == 0
        assert not srv.frames_rejected  # zero frames lost in transition
        srv.finish_renegotiation()
        before = srv.native_batch_frames
        items = run(0, "int8", 1)
        assert items and items[0][0] == 0
        assert srv.native_batch_frames > before  # native re-armed
        # a straggler on the retired epoch is counted config drift
        run(1, None, 0)
        assert srv.frames_rejected.get(1, 0) >= 1
    finally:
        srv.close()


def test_worker_renegotiate_declines_cleanly(tmp_path):
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.dcn import ShmPSServer, ShmPSWorker

    name = f"/psq_ctldecl_{os.getpid()}"
    srv = ShmPSServer(name, 1, TEMPLATE, code=get_codec("identity"))
    try:
        # unframed worker: no fingerprint to bump
        w = ShmPSWorker(name, 0, TEMPLATE, code=get_codec("identity"))
        assert w.renegotiate(get_codec("int8")) is False
        w.close()
        # apply_epoch tolerates a transport without renegotiate()
        class NoReneg:
            pass

        assert apply_epoch(NoReneg(), {"codec": "int8"}) is False
        # a tree leaf conn declines (the hop codec is the tree's own
        # agreement) — exercised without a live tree via the method
        from pytorch_ps_mpi_tpu.parallel.tree import TreeWorkerConn

        assert TreeWorkerConn.renegotiate(
            object(), get_codec("int8")) is False
    finally:
        srv.close()


def test_controller_restores_epoch_for_restarted_generation(tmp_path):
    """A supervisor-restarted server generation must rejoin the fleet's
    current wire epoch from control-epoch.json before consuming."""
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.dcn import ShmPSServer, ShmPSWorker

    d = str(tmp_path)
    write_epoch(d, {"epoch": 1, "codec": "int8", "codec_kw": {},
                    "bucket_mb": 0.0})
    name = f"/psq_ctlrest_{os.getpid()}"
    srv = ShmPSServer(name, 1, TEMPLATE, max_staleness=10**9,
                      code=get_codec("identity"), frame=True)
    try:
        cfg = {"control": True, "control_dir": d,
               "control_kw": {"ladder": [{"codec": "identity"},
                                         {"codec": "int8"}],
                              "read_p95_target_ms": 100.0}}
        ctl = Controller(srv, cfg)
        assert ctl.engine.ladder_idx == 1
        assert srv._epoch == 1
        assert type(srv.wire.code) is type(get_codec("int8"))  # noqa: E721
        # an already-switched worker's push is consumed immediately
        w = ShmPSWorker(name, 0, TEMPLATE, code=get_codec("int8"),
                        frame=True)
        srv.publish(TEMPLATE)
        w.push_grad(jax.tree.map(lambda x: jnp.ones_like(x), TEMPLATE), 1)
        assert srv.poll_grad()[0] == 0
        assert not srv.frames_rejected
        w.close()
        ctl.close()
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# actuators + surfaces
# ---------------------------------------------------------------------------

def test_numerics_readmit_clears_quarantine_and_offenses():
    from pytorch_ps_mpi_tpu.telemetry.numerics import NumericsMonitor

    nm = NumericsMonitor(num_workers=2, policy="skip")
    bad = {"g": np.array([np.nan, 1.0], np.float32)}
    good = {"g": np.ones(2, np.float32)}
    assert nm.observe_push(1, bad) == "skip"
    assert nm.is_quarantined(1)
    assert nm.readmit(1) is True
    assert not nm.is_quarantined(1)
    assert nm.readmissions == 1
    assert nm.observe_push(1, good) == "apply"  # trusted again
    # a fresh offense re-quarantines like a first offense
    assert nm.observe_push(1, bad) == "skip"
    assert nm.is_quarantined(1)
    assert nm.readmit(0) is False  # not quarantined


def test_serving_core_setters_and_ring_resize():
    from pytorch_ps_mpi_tpu.serving import ServingCore
    from pytorch_ps_mpi_tpu.serving.snapshots import SnapshotStore

    core = ServingCore(None, {"serving": True},
                       template={"p": np.zeros(8, np.float32)})
    for v in range(1, 7):
        core.publish(flat=np.full(8, float(v), np.float32), version=v)
    core.set_admission_depth(128)
    assert core.admission_depth == 128
    with pytest.raises(ValueError):
        core.set_admission_depth(0)
    core.set_ring(2)
    store = core._stores["default"]
    assert store.versions() == [5, 6]
    core.set_ring(16)
    assert core.knobs["ring"] == 16
    # held snapshots survive a shrink as zombies until release
    s = SnapshotStore(4)
    for v in range(1, 5):
        s.put(v, np.full(4, float(v), np.float32))
    pinned = s.acquire(1)
    s.resize(1)
    assert s.versions() == [4]
    np.testing.assert_array_equal(np.asarray(pinned.flat),
                                  np.full(4, 1.0, np.float32))
    s.release(pinned)
    core.close()


def test_canonical_control_keys_and_health_section():
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.dcn import ShmPSServer
    from pytorch_ps_mpi_tpu.telemetry.registry import (
        PS_SERVER_METRIC_KEYS,
    )

    name = f"/psq_ctlkeys_{os.getpid()}"
    srv = ShmPSServer(name, 2, TEMPLATE, code=get_codec("identity"),
                      frame=True)
    try:
        m = srv.metrics()
        assert set(m) == set(PS_SERVER_METRIC_KEYS)
        # unarmed: all control keys 0.0
        for k in ("control_actions", "control_epoch", "control_evicted",
                  "control_lr_scale_min"):
            assert m[k] == 0.0
        ctl = Controller(srv, {"control": True,
                               "control_kw": {
                                   "read_p95_target_ms": 100.0}})
        ctl.engine.lr_scale[1] = 0.5
        ctl.engine.evicted[0] = 10.0**18
        m = srv.metrics()
        assert m["control_lr_scale_min"] == 0.5
        assert m["control_evicted"] == 1.0
        # scrape instruments + /health control section
        text = srv.prometheus_text()
        for inst in ("ps_control_actions_total", "ps_control_epoch",
                     "ps_control_evicted", "ps_control_lr_scale_min",
                     "ps_control_flaps_total"):
            assert inst in text
        doc = json.loads(srv.health_json())
        assert doc["control"]["armed"] is True
        assert doc["control"]["evicted"] == [0]
        ctl.close()
    finally:
        srv.close()


def test_ps_top_renders_control_pane():
    from tools.ps_top import render_control, render_table

    control = {
        "actions_total": 7, "flaps": 0, "epoch": 1,
        "ladder": ["identity", "int8"], "ladder_idx": 1,
        "transition_active": False, "agg_suspended": False,
        "lr_scale": {1: 0.42}, "evicted": [2], "probation": [],
        "admission_depth": 32, "ring": 8, "pinned": [],
        "recent_actions": [
            {"rule": "codec", "action": "renegotiate",
             "old": "identity", "new": "int8",
             "verdict": {"kind": "wire_bound"}},
        ],
    }
    lines = render_control(control)
    text = "\n".join(lines)
    assert "actions=7" in text and "epoch=1" in text
    assert "wire=int8" in text and "w1=0.42" in text
    assert "evicted w2" in text
    assert "codec.renegotiate" in text and "wire_bound" in text
    health = {
        "armed": True, "n_workers": 1, "uptime_s": 1.0,
        "fleet": {"anomaly_total": 0, "rounds": 0},
        "workers": [{
            "worker": 0, "verdict": "ok", "cause": None, "done": False,
            "grads": 3,
            "push_interarrival_s": {"ewma": 0.01, "p50": 0.01,
                                    "p95": 0.01, "n": 3},
            "staleness": {"ewma": 0.0, "last": 0}, "anomalies": 0,
            "last_anomaly": None, "server_wait_ewma_s": 0.0,
            "compute_ewma_s": None, "wire_ewma_s": None,
            "steps_beaconed": 0, "straggle_total_s": 0.0, "retries": 0,
            "reconnects": 0, "frames_rejected": 0,
            "last_seen_age_s": 0.1,
            "gating": {"rounds": 0, "seconds": 0.0}, "numerics": None,
            "lineage": None,
        }],
        "control": control,
    }
    frame = render_table(health)
    assert "control  actions=7" in frame


def test_report_routes_and_summarizes_actions(tmp_path):
    from tools.telemetry_report import summarize

    p = tmp_path / "control-server.jsonl"
    rows = [
        {"t": 1.0, "rule": "evict", "action": "evict", "old": 0.0,
         "new": 1.0, "worker": 2, "verdict": {"kind": "churning"}},
        {"t": 1.5, "rule": "evict", "action": "readmit", "old": 1.0,
         "new": 0.0, "worker": 2,
         "verdict": {"kind": "backoff_elapsed"}},
        {"t": 2.0, "rule": "evict", "action": "evict", "old": 0.0,
         "new": 1.0, "worker": 2, "verdict": {"kind": "churning"}},
        {"t": 3.0, "rule": "read_tier", "action": "depth", "old": 8,
         "new": 16, "verdict": {"kind": "shed_pressure"}},
    ]
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    # a second shard's file with a NEWER row, globbed first: the tail
    # must still end on the newest action across files (time order)
    p0 = tmp_path / "control-shard0.jsonl"
    p0.write_text(json.dumps(
        {"t": 9.0, "rule": "lr_scale", "action": "scale", "old": 1.0,
         "new": 0.5, "worker": 0, "verdict": {"kind": "stale"}}) + "\n")
    summary = summarize([str(p0), str(p)])
    act = summary["actions"]
    assert act["actions"] == 5
    assert act["tail"][-1]["rule"] == "lr_scale"
    rules = {r["rule"]: r for r in act["rules"]}
    assert rules["evict"]["evict"] == 2
    assert rules["read_tier"]["depth"] == 1
    # the evict→readmit→evict triple inside the window IS a flap suspect
    assert len(act["flap_suspects"]) == 1
    assert act["flap_suspects"][0]["rule"] == "evict"
    # no row entered the span merge
    assert not summary["spans"]
    from tools.telemetry_report import format_table

    text = format_table(summary)
    assert "FLAP SUSPECT" in text


def test_fleet_merge_rolls_up_controllers():
    from pytorch_ps_mpi_tpu.telemetry.fleet import FleetMonitor

    fm = FleetMonitor(endpoints=[])
    members = [
        {"name": "a", "url": "u", "role": "server", "ok": True,
         "error": None, "ts": 1.0, "uptime_s": 1.0, "age_s": 0.0,
         "verdict": "ok", "metrics": {}, "labeled": [],
         "control": {"actions_total": 3, "flaps": 0, "epoch": 1,
                     "evicted": [2], "lr_scale": {},
                     "recent_actions": []}},
        {"name": "b", "url": "u", "role": "server", "ok": True,
         "error": None, "ts": 1.0, "uptime_s": 1.0, "age_s": 0.0,
         "verdict": "ok", "metrics": {}, "labeled": [],
         "control": {"actions_total": 2, "flaps": 1, "epoch": 0,
                     "evicted": [], "lr_scale": {},
                     "recent_actions": []}},
        {"name": "c", "url": "u", "role": "read", "ok": True,
         "error": None, "ts": 1.0, "uptime_s": 1.0, "age_s": 0.0,
         "verdict": None, "metrics": {}, "labeled": []},
    ]
    snap = fm._merge(members, now=2.0)
    ctl = snap["control"]
    assert ctl["actions_total"] == 5
    assert ctl["flaps"] == 1
    assert ctl["epoch_max"] == 1
    assert ctl["evicted"] == ["a:w2"]
    assert ctl["members_armed"] == 2
    from tools.ps_top import render_fleet

    text = render_fleet(snap)
    assert "control: 2 armed" in text and "flaps=1 (!)" in text


# ---------------------------------------------------------------------------
# serve() E2E: per-push LR weight + controller lifecycle (compact)
# ---------------------------------------------------------------------------

def test_serve_controller_deweights_stale_worker(tmp_path):
    """Compact live run: worker 1 is a straggler whose exact staleness
    runs above the fleet median — the controller must de-weight exactly
    its pushes, record replayable action rows, and never flap."""
    from pytorch_ps_mpi_tpu.parallel import dcn
    from pytorch_ps_mpi_tpu.parallel.async_train import (
        join_workers,
        make_problem,
        serve,
        spawn_worker,
    )

    tdir = str(tmp_path)
    steps = 16
    cfg = {
        "model": "mlp", "model_kw": {"features": (16, 4)},
        "in_shape": (8,), "batch": 32, "seed": 3, "optim": "sgd",
        "hyper": {"lr": 0.05}, "steps": steps,
        "open_timeout": 60.0, "push_timeout": 60.0,
        "frame_check": True,
        "slow_ms": {"1": 250.0},
        "control": True, "control_dir": tdir,
        "control_kw": {"eval_every_s": 0.2, "warmup_s": 0.8,
                       "cooldown_s": 1.0, "window_s": 3.0,
                       "read_p95_target_ms": 100.0},
    }
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_ctlserve_{os.getpid()}"
    server = dcn.ShmPSServer(name, num_workers=2, template=params0,
                             max_staleness=10**9, frame=True)
    procs = []
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(2)]
        _, m = serve(server, cfg, total_grads=0,
                     total_received=2 * steps, timeout=240.0)
        assert join_workers(procs, timeout=120.0) == [0, 0]
        ctl = m["control"]
        assert ctl["armed"] and ctl["flaps"] == 0
        action_rows = [
            json.loads(line) for line in
            open(os.path.join(tdir, "control-server.jsonl"))
        ]
        # exactly the straggler was de-weighted (it may be RESTORED to
        # 1.0 by the end — once the fast worker drains, its staleness
        # falls back into band; reversibility is the contract)
        scales = [r for r in action_rows if r["rule"] == "lr_scale"]
        assert scales and all(r["worker"] == 1 for r in scales)
        assert min(r["new"] for r in scales) < 1.0
        assert all(r["verdict"]["kind"] == "stale" for r in scales)
        # replay over the persisted TSDB rows re-derives the sequence
        from pytorch_ps_mpi_tpu.telemetry.timeseries import (
            load_timeseries_rows,
        )

        rows = load_timeseries_rows(
            os.path.join(tdir, "timeseries-control-server.jsonl"))
        replayed = Controller.replay(rows, num_workers=2, cfg=cfg)
        assert json.dumps(replayed) == json.dumps(action_rows)
        assert m["control_actions"] == float(len(action_rows))
    finally:
        server.close()
        join_workers(procs, timeout=5.0)


# ---------------------------------------------------------------------------
# structural control: the topo rule (group replan / elastic replicas /
# shard plans), its actuator plumbing, and replay identity
# ---------------------------------------------------------------------------

def _topo_knobs(**over):
    base = _knobs(ladder=None, topo_actions=True,
                  replan_max=1, replan_cooldown_s=2.0,
                  leader_fold_hot_frac=0.2, leader_churn_replan=2.0,
                  replica_min=0, replica_max=2, replica_cooldown_s=1.0,
                  replica_shed_per_s=2.0, replica_lag_hi=4.0,
                  shard_cooldown_s=1.0, shard_split_skew=0.5,
                  shard_merge_skew=0.1)
    base.update(over)
    return base


def _topo_row(t, **over):
    row = _row(t, tree_groups=2.0, hot_group=-1.0, hot_churn_group=-1.0,
               leader_respawns=0.0, lf_top=0.0, lf_saving_frac=0.0,
               replicas_live=0.0, replica_lag_max=0.0,
               shards_n=0.0, shard_skew=0.0, shard_skew_hot=0.0)
    row.update(over)
    return row


def test_engine_topo_disabled_by_default():
    eng = ControlEngine(_knobs(ladder=None), 2)
    for i in range(12):
        eng.step(_topo_row(100.0 + 0.5 * i, lf_top=1.0, hot_group=1.0,
                           lf_saving_frac=0.6, shards_n=2.0,
                           shard_skew=0.9, shard_skew_hot=1.0,
                           reads_shed=float(10 * i)))
    assert not [a for a in eng.actions if a["rule"] == "topo"]
    assert eng.topo_actions == 0


def test_engine_topo_group_replan_latched_then_merge_reverts():
    eng = ControlEngine(_topo_knobs(), 4)
    acts = []
    # sustained hot leader_fold hop at group 1: exactly ONE replan
    for i in range(10):
        acts += eng.step(_topo_row(100.0 + 0.5 * i, lf_top=1.0,
                                   hot_group=1.0, lf_saving_frac=0.4))
    replans = [a for a in acts if a["action"] == "group_replan"]
    assert len(replans) == 1 and eng.replans == 1
    a = replans[0]
    assert a["verdict"]["kind"] == "leader_fold_hot"
    assert a["verdict"]["rule"] == "topo" and a["verdict"]["group"] == 1
    # hotspot clears: the merge needs a COLD hop for 2x the cooldown
    acts2 = []
    for i in range(14):
        acts2 += eng.step(_topo_row(110.0 + 0.5 * i))
    merges = [a for a in acts2 if a["action"] == "group_merge"]
    assert len(merges) == 1 and eng.replans == 0
    assert merges[0]["verdict"]["kind"] == "hotspot_cleared"
    assert eng.flaps == 0


def test_engine_topo_replan_on_leader_churn():
    eng = ControlEngine(_topo_knobs(), 4)
    acts = []
    for i in range(8):
        acts += eng.step(_topo_row(100.0 + 0.5 * i, hot_churn_group=0.0,
                                   leader_respawns=3.0))
    replans = [a for a in acts if a["action"] == "group_replan"]
    assert len(replans) == 1
    assert replans[0]["verdict"]["kind"] == "leader_churn"
    assert replans[0]["verdict"]["group"] == 0


def test_engine_topo_replica_scale_out_in_no_flap():
    eng = ControlEngine(_topo_knobs(), 2)
    acts = []
    # shed burn: reads_shed ramps 5 per 0.5s row -> 10/s >> 2/s
    for i in range(10):
        acts += eng.step(_topo_row(100.0 + 0.5 * i,
                                   reads_shed=float(5 * i)))
    outs = [a for a in acts if a["action"] == "replica"]
    assert outs and all(a["verdict"]["kind"] == "shed_pressure"
                        for a in outs)
    assert eng.replicas == 2  # clamped at replica_max
    # burn stops, lag burns instead: scale back in
    shed_final = 45.0
    acts2 = []
    for i in range(16):
        acts2 += eng.step(_topo_row(110.0 + 0.5 * i,
                                    reads_shed=shed_final,
                                    replica_lag_max=6.0))
    ins = [a for a in acts2 if a["action"] == "replica"
           and a["new"] < a["old"]]
    assert ins and all(a["verdict"]["kind"] == "replica_lag_burn"
                       for a in ins)
    assert eng.replicas == 0
    assert eng.flaps == 0


def test_engine_topo_replica_floor_and_idle_retire():
    eng = ControlEngine(_topo_knobs(replica_min=1), 2)
    acts = []
    for i in range(6):
        acts += eng.step(_topo_row(100.0 + 0.5 * i))
    floors = [a for a in acts if a["action"] == "replica"]
    assert floors and floors[0]["verdict"]["kind"] == "tier_floor"
    assert eng.replicas == 1


def test_engine_topo_shard_split_then_merge():
    eng = ControlEngine(_topo_knobs(), 2)
    acts = []
    for i in range(8):
        acts += eng.step(_topo_row(100.0 + 0.5 * i, shards_n=2.0,
                                   shard_skew=0.7, shard_skew_hot=1.0))
    splits = [a for a in acts if a["action"] == "shard_split"]
    assert len(splits) == 1 and eng.shard_extra == 1
    assert splits[0]["old"] == 2 and splits[0]["new"] == 3
    assert splits[0]["verdict"]["kind"] == "shard_skew"
    acts2 = []
    for i in range(10):
        acts2 += eng.step(_topo_row(108.0 + 0.5 * i, shards_n=2.0,
                                    shard_skew=0.05))
    merges = [a for a in acts2 if a["action"] == "shard_merge"]
    assert len(merges) == 1 and eng.shard_extra == 0
    assert merges[0]["verdict"]["kind"] == "skew_cleared"
    assert eng.flaps == 0


def test_engine_every_action_carries_verdict_id_and_rule():
    eng = ControlEngine(_topo_knobs(), 3)
    for i in range(20):
        eng.step(_topo_row(100.0 + 0.5 * i, lf_top=1.0, hot_group=0.0,
                           lf_saving_frac=0.5, w1_stale=6.0,
                           reads_shed=float(5 * i), shards_n=2.0,
                           shard_skew=0.7, shard_skew_hot=1.0))
    assert eng.actions  # mixed rules actually fired
    assert len({a["rule"] for a in eng.actions}) >= 2
    for i, a in enumerate(eng.actions):
        assert a["verdict"]["id"] == i
        assert a["verdict"]["rule"] == a["rule"]


def test_topo_replay_byte_identical():
    rows = []
    for i in range(24):
        m = _topo_row(100.0 + 0.5 * i, lf_top=1.0, hot_group=1.0,
                      lf_saving_frac=0.4, reads_shed=float(5 * i),
                      shards_n=2.0, shard_skew=0.7, shard_skew_hot=1.0,
                      w1_stale=6.0)
        rows.append({"t": m["ts"], "m": m})
    knobs = _topo_knobs()
    live = ControlEngine(knobs, 3)
    live_actions = []
    for r in rows:
        live_actions += live.step(r["m"])
    assert [a for a in live_actions if a["rule"] == "topo"]
    # knob-armed replay
    replayed = Controller.replay(rows, num_workers=3,
                                 cfg={"control_kw": knobs})
    assert json.dumps(replayed) == json.dumps(live_actions)
    # TOP-LEVEL cfg["topo_actions"] arming must replay identically too
    # (construction and replay derive the switch the same way)
    k2 = dict(knobs)
    k2.pop("topo_actions")
    replayed2 = Controller.replay(rows, num_workers=3,
                                  cfg={"topo_actions": True,
                                       "control_kw": k2})
    assert json.dumps(replayed2) == json.dumps(live_actions)


def test_topo_doc_poll_gated_and_assign_merges(tmp_path):
    from pytorch_ps_mpi_tpu.control.topo import (
        poll_topo,
        update_topo,
        write_shard_plan,
    )

    d = str(tmp_path)
    state = {"seq": 0, "mtime": 0}
    assert poll_topo(d, state) is None  # no doc yet
    update_topo(d, assign={"2": "127.0.0.1:7001"})
    doc = poll_topo(d, state)
    assert doc["seq"] == 1 and doc["assign"]["2"] == "127.0.0.1:7001"
    assert poll_topo(d, state) is None  # mtime+seq gated
    # a shard plan MERGES with (never clobbers) the standing assign map
    write_shard_plan(d, 3, {"kind": "shard_skew", "id": 7})
    doc = poll_topo(d, state)
    assert doc["shards"] == 3 and doc["assign"]["2"] == "127.0.0.1:7001"
    assert doc["seq"] == 2
    from pytorch_ps_mpi_tpu.parallel.sharded import planned_shards

    assert planned_shards(d, 2) == 3
    assert planned_shards(None, 2) == 2


def test_replica_scaler_cards_and_lifo_retire(tmp_path):
    from pytorch_ps_mpi_tpu.control.topo import ReplicaScaler
    from pytorch_ps_mpi_tpu.telemetry.fleet import (
        list_endpoints,
        register_endpoint,
    )

    fleet = str(tmp_path / "fleet")

    class FakeProc:
        _next = [1000]

        def __init__(self):
            FakeProc._next[0] += 1
            self.pid = FakeProc._next[0]
            self.terminated = False
            self.stdout = None
            # the real replica registers its own card at boot
            register_endpoint(fleet, f"replica-{self.pid}", 9000,
                              role="replica")

        def poll(self):
            return 1 if self.terminated else None

        def terminate(self):
            self.terminated = True

    sc = ReplicaScaler("127.0.0.1", 7000, dir=str(tmp_path),
                       fleet_dir=fleet)
    sc._spawn_replica = FakeProc
    assert sc.scale_to(2, {"kind": "shed_pressure", "id": 0}) == 2
    assert sc.live == 2
    cards = {e["name"] for e in list_endpoints(fleet)}
    assert len(cards) == 2 and all(c.startswith("replica-")
                                   for c in cards)
    # scale in deregisters the NEWEST replica's card, then terminates
    assert sc.scale_to(1, {"kind": "replica_lag_burn", "id": 1}) == 1
    assert sc.live == 1
    assert {e["name"] for e in list_endpoints(fleet)} < cards
    assert [e["act"] for e in sc.events] == ["spawn", "spawn", "retire"]
    assert all(e["verdict"]["kind"] for e in sc.events)
    sc.close()
    assert sc.live == 0
    assert list_endpoints(fleet) == []


def test_follower_repoint_reparents_subscription():
    from pytorch_ps_mpi_tpu.serving.follower import FollowerLoop

    class CoreStub:
        template = {"a": np.zeros((4,), np.float32)}

    fl = FollowerLoop(CoreStub(), "127.0.0.1", 7001,
                      template=CoreStub.template)
    assert fl.repoint("127.0.0.1", 7002) is True
    assert (fl.host, fl.port) == ("127.0.0.1", 7002)
    assert fl._reader is None
    # idempotent once attached nowhere: same endpoint with no live
    # reader still re-arms the prompt re-dial (returns True)
    assert fl.repoint("127.0.0.1", 7002) is True
    fl.close()


def test_anatomy_hot_hop_names_the_slow_group():
    from pytorch_ps_mpi_tpu.telemetry.anatomy import RoundAnatomy

    an = RoundAnatomy(None, {}, num_workers=4)
    assert an.hot_hop() is None  # one group has no "hotter"
    for r in range(4):
        an.observe_hop({"kind": "hop", "leader": 0, "fold_s": 0.002,
                        "encode_s": 0.001, "composed": []})
        an.observe_hop({"kind": "hop", "leader": 1, "fold_s": 0.150,
                        "encode_s": 0.001, "composed": []})
    assert an.hot_hop() == 1


def test_report_joins_actions_to_verdicts(tmp_path):
    from tools.telemetry_report import _summarize_actions

    rows = [
        {"t": 1.0, "rule": "topo", "action": "group_replan", "old": 0,
         "new": 1, "verdict": {"id": 0, "rule": "topo",
                               "kind": "leader_fold_hot", "group": 1}},
        {"t": 2.0, "rule": "read_tier", "action": "depth", "old": 64,
         "new": 128, "verdict": {"id": 1, "rule": "read_tier",
                                 "kind": "shed"}},
        {"t": 3.0, "rule": "topo", "action": "replica", "old": 0,
         "new": 1, "verdict": {"id": 2, "rule": "topo",
                               "kind": "shed_pressure"}},
    ]
    s = _summarize_actions(rows)
    assert s["actions"] == 3 and not s["flap_suspects"]
    join = {(j["rule"], j["action"], j["verdict"]): j["actions"]
            for j in s["verdict_join"]}
    assert join[("topo", "group_replan", "leader_fold_hot")] == 1
    assert join[("topo", "replica", "shed_pressure")] == 1
    assert join[("read_tier", "depth", "shed")] == 1


def test_ps_top_renders_topo_line():
    from tools.ps_top import render_control

    lines = render_control({
        "actions_total": 3, "flaps": 0, "epoch": 0, "ladder": [],
        "ladder_idx": 0, "topo_armed": True, "topo_actions": 2,
        "group_replans": 1, "replicas": 2, "shard_extra": 0,
    })
    topo = [ln for ln in lines if "topo" in ln]
    assert topo and "replans=1" in topo[0] and "replicas=2" in topo[0]
