"""Fleet observability plane: metrics history (TSDB), continuous
profiling, SLO burn-rate watchdog, fleet aggregation, and the
``/history`` + ``/fleet`` HTTP routes on both transports — including
the concurrent-scrape and teardown-by-``server.close()`` contracts."""

import json
import math
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pytorch_ps_mpi_tpu import telemetry
from pytorch_ps_mpi_tpu.telemetry import MetricsRegistry
from pytorch_ps_mpi_tpu.telemetry.fleet import (
    FleetMonitor,
    deregister_endpoint,
    endpoint_path,
    list_endpoints,
    parse_prometheus_text,
    register_endpoint,
)
from pytorch_ps_mpi_tpu.telemetry.profiler import (
    SamplingProfiler,
    load_profile,
    merge_profiles,
    top_frames,
)
from pytorch_ps_mpi_tpu.telemetry.slo import SLOWatchdog
from pytorch_ps_mpi_tpu.telemetry.timeseries import (
    MetricsHistory,
    history_from_rows,
    load_timeseries_rows,
)


@pytest.fixture(autouse=True)
def _no_global_recorder():
    telemetry.disable()
    yield
    telemetry.disable()


def _fill(h, n, dt=0.2, t0=1000.0, fn=None):
    for i in range(n):
        m = {"a": float(i), "lat": 5.0 + (i % 10)}
        if fn is not None:
            m.update(fn(i))
        h.sample(m, now=t0 + i * dt)
    return t0 + (n - 1) * dt


# -- MetricsHistory (the TSDB) ----------------------------------------------

def test_history_ring_bounds_and_monotonicity():
    h = MetricsHistory(name="t", raw_capacity=64)
    end = _fill(h, 200)
    pts = h.range("a", 0.0, tier=-1)
    assert len(pts) == 64  # raw ring bounded
    ts = [t for t, _ in pts]
    assert ts == sorted(ts)
    # non-monotone and duplicate timestamps are rejected, not stored
    assert not h.sample({"a": 1.0}, now=end)
    assert not h.sample({"a": 1.0}, now=end - 5.0)
    # ...and so is a sample under the ingest throttle (default 0.2 s)
    assert not h.sample({"a": 1.0}, now=end + 0.05)
    assert h.sample({"a": 1.0}, now=end + 0.25)


def test_history_non_numeric_and_nonfinite_skipped():
    h = MetricsHistory(name="t")
    h.sample({"a": 1.0, "s": "nope", "nan": float("nan"),
              "flag": True}, now=1.0)
    assert h.keys() == ["a"]


def test_history_downsampled_tier_answers_aged_window():
    # raw ring too short for the window -> the 1 s tier answers, with
    # per-bucket means (the "within downsampling error" contract)
    h = MetricsHistory(name="t", raw_capacity=16,
                       tiers=((1.0, 900), (10.0, 90)))
    end = _fill(h, 400, dt=0.25)  # 100 s of samples, raw covers 4 s
    stats = h.window_stats("lat", 60.0, now=end)
    assert stats["tier_s"] == 1.0
    assert stats["n"] > 100  # fold counts weight the buckets
    # bucket means of lat (cycle 5..14) stay within the raw bounds
    assert 5.0 <= stats["p50"] <= 14.0
    assert 5.0 <= stats["mean"] <= 14.0
    pts = h.range("a", end - 60.0)
    ts = [t for t, _ in pts]
    assert ts == sorted(ts) and len(pts) >= 55


def test_history_windowed_quantiles_match_exact():
    h = MetricsHistory(name="t")
    rng = np.random.RandomState(0)
    vals = rng.exponential(10.0, 300)
    for i, v in enumerate(vals):
        h.sample({"x": float(v)}, now=1000.0 + i * 0.2)
    now = 1000.0 + 299 * 0.2
    window = vals[-100:]
    got = h.quantile("x", 0.95, 100 * 0.2 - 1e-6, now=now)
    exact = float(np.quantile(window, 0.95, method="inverted_cdf"))
    # raw-tier query: exact weighted quantile over the window samples
    assert abs(got - exact) / exact < 0.05


def test_history_rate_and_counter_reset_clamp():
    h = MetricsHistory(name="t")
    for i in range(50):
        h.sample({"c": float(i * 3)}, now=1000.0 + i)
    assert abs(h.rate("c", 30.0, now=1049.0) - 3.0) < 0.2
    # counter reset (server restart): negative delta clamps to 0
    h2 = MetricsHistory(name="t")
    h2.sample({"c": 100.0}, now=1.0)
    h2.sample({"c": 5.0}, now=2.0)
    assert h2.rate("c", 10.0, now=2.0) == 0.0


def test_history_persistence_roundtrip_and_replayability(tmp_path):
    h = MetricsHistory(name="srv", dir=str(tmp_path), flush_every=16)
    end = _fill(h, 100)
    h.close()
    path = tmp_path / "timeseries-srv.jsonl"
    assert path.exists()
    rows = load_timeseries_rows(str(path))
    assert len(rows) == 100
    rebuilt = history_from_rows(rows)
    # the rebuilt history answers the same windows (determinism — what
    # makes SLO replay possible)
    for key in ("a", "lat"):
        a = h.window_stats(key, 10.0, now=end)
        b = rebuilt.window_stats(key, 10.0, now=end)
        assert a["n"] == b["n"] and a["p95"] == b["p95"]


def test_history_range_default_covers_replayed_samples(tmp_path):
    # a history rebuilt offline holds samples that predate its own
    # construction — range() with default bounds must still return them
    h = MetricsHistory(name="srv", dir=str(tmp_path), flush_every=4)
    _fill(h, 20)
    h.close()
    rows = load_timeseries_rows(str(tmp_path / "timeseries-srv.jsonl"))
    rebuilt = history_from_rows(rows)
    assert len(rebuilt.range("a")) == 20


def test_history_retention_compacts_file(tmp_path):
    h = MetricsHistory(name="srv", dir=str(tmp_path), flush_every=8,
                       retention_rows=64)
    _fill(h, 300)
    h.close()
    with open(tmp_path / "timeseries-srv.jsonl") as f:
        n_lines = sum(1 for _ in f)
    assert n_lines <= 64 + 8  # bounded: compaction kept the newest half
    rows = load_timeseries_rows(str(tmp_path / "timeseries-srv.jsonl"))
    assert rows[-1]["m"]["a"] == 299.0  # newest rows survive


def test_history_query_document():
    h = MetricsHistory(name="t", max_points=50)
    end = _fill(h, 200)
    listing = h.query({})
    assert listing["armed"] and "a" in listing["key_names"]
    doc = h.query({"key": "lat", "window": str(end)})
    assert 0 < len(doc["points"]) <= 50  # strided to max_points
    assert doc["stats"]["n"] > 0
    assert "error" in h.query({"key": "nope"})
    q = h.query({"key": "lat", "window": str(end), "q": "0.5"})
    assert 5.0 <= q["quantile"]["value"] <= 14.0


# -- SamplingProfiler -------------------------------------------------------

def _busy_for(seconds):
    x = 0.0
    end = time.time() + seconds
    while time.time() < end:
        x += math.sin(x) + 1e-9
    return x


def test_profiler_captures_busy_frames_with_thread_root():
    p = SamplingProfiler(name="t", hz=250).start()
    t = threading.Thread(target=_busy_for, args=(0.6,),
                         name="busy-thread")
    t.start()
    t.join()
    p.stop()
    assert p.samples > 20
    collapsed = p.collapsed()
    assert "_busy_for" in collapsed
    assert "busy-thread" in collapsed  # stacks rooted at the thread name
    top = p.top(10)
    assert any("_busy_for" in r["frame"] for r in top)
    assert all(r["cum"] >= r["self"] for r in top)


def test_profiler_overhead_budget_throttles_rate():
    # an impossible budget forces the adaptive backoff: the effective
    # interval must grow away from the target rate
    p = SamplingProfiler(name="t", hz=500.0, max_frac=1e-9,
                         adjust_every=8, min_hz=2.0)
    p.start()
    time.sleep(0.5)
    p.stop()
    assert p._interval > 1.0 / 500.0
    assert p.snapshot()["budget_frac"] == 1e-9


def test_profile_write_load_merge(tmp_path):
    p = SamplingProfiler(name="w1", dir=str(tmp_path), hz=200).start()
    _busy_for(0.3)
    p.stop()
    path = p.write()
    assert path is not None and os.path.exists(path)
    meta, counts = load_profile(path)
    assert meta["samples"] == p.samples and counts
    merged = merge_profiles([path, path])
    assert sum(merged.values()) == 2 * sum(counts.values())
    top = top_frames(merged, 5)
    assert top and abs(sum(r["self_frac"]
                           for r in top_frames(merged, 10**6)) - 1.0) < 0.01


# -- SLO watchdog -----------------------------------------------------------

def _lat_rule(target=8.0):
    return [{"name": "lat", "key": "lat", "mode": "value",
             "target": target}]


def _drive(h, wd, values, t0, dt=0.2):
    out = []
    t = t0
    for v in values:
        t += dt
        h.sample({"lat": v}, now=t)
        out.extend(wd.evaluate(now=t))
    return out, t


def test_slo_breach_is_latched_and_recovers_once():
    h = MetricsHistory(name="t")
    wd = SLOWatchdog(history=h, rules=_lat_rule(),
                     short_window_s=5.0, long_window_s=20.0,
                     eval_every_s=0.2)
    v, t = _drive(h, wd, [1.0] * 150, 1000.0)  # healthy warmup
    assert v == []
    v, t = _drive(h, wd, [50.0] * 150, t)  # sustained burn
    assert [x["kind"] for x in v] == ["breach"]  # EXACTLY one
    assert wd.breaches_total == 1
    assert wd.snapshot()["burning"] == ["lat"]
    v, t = _drive(h, wd, [1.0] * 200, t)
    assert [x["kind"] for x in v] == ["recover"]
    assert wd.snapshot()["burning"] == []
    assert wd.breaches_total == 1  # recovery is not a breach


def test_slo_multi_window_suppresses_transient_spike():
    h = MetricsHistory(name="t")
    wd = SLOWatchdog(history=h, rules=_lat_rule(),
                     short_window_s=2.0, long_window_s=30.0,
                     eval_every_s=0.2)
    v, t = _drive(h, wd, [1.0] * 150, 1000.0)
    # a 2 s spike burns the short window but not the 30 s one
    v, t = _drive(h, wd, [100.0] * 10, t)
    v2, t = _drive(h, wd, [1.0] * 100, t)
    assert v == [] and v2 == []
    assert wd.breaches_total == 0


def test_slo_rate_rule_on_counter():
    h = MetricsHistory(name="t")
    wd = SLOWatchdog(history=h,
                     rules=[{"name": "drops", "key": "drops",
                             "mode": "rate", "target": 0.5}],
                     short_window_s=5.0, long_window_s=15.0,
                     eval_every_s=0.2)
    t, verdicts = 1000.0, []
    drops = 0.0
    for i in range(300):
        t += 0.2
        if i > 100:
            drops += 1.0  # 5 drops/s >> 0.5/s target
        h.sample({"drops": drops}, now=t)
        verdicts.extend(wd.evaluate(now=t))
    assert [x["kind"] for x in verdicts] == ["breach"]
    assert verdicts[0]["burn_long"] > 1.0


def test_slo_verdicts_replay_identically(tmp_path):
    h = MetricsHistory(name="srv", dir=str(tmp_path), flush_every=8)
    wd = SLOWatchdog(history=h, rules=_lat_rule(),
                     short_window_s=5.0, long_window_s=20.0,
                     eval_every_s=0.2, dir=str(tmp_path))
    live = []
    t = 1000.0
    for v in [1.0] * 150 + [50.0] * 150 + [1.0] * 200:
        t += 0.2
        h.sample({"lat": v}, now=t)
        live.extend(wd.evaluate(now=t))
    h.close()
    wd.close()
    rows = load_timeseries_rows(str(tmp_path / "timeseries-srv.jsonl"))
    replayed = SLOWatchdog.replay(
        rows, rules=_lat_rule(), short_window_s=5.0, long_window_s=20.0,
        eval_every_s=0.2)
    strip = lambda xs: [{k: x[k] for k in ("kind", "rule", "t",
                                           "burn_short", "burn_long")}
                        for x in xs]
    assert strip(replayed) == strip(live)
    # and the persisted slo-*.jsonl carries the same events
    with open(tmp_path / "slo-server.jsonl") as f:
        persisted = [json.loads(ln) for ln in f if ln.strip()]
    assert strip(persisted) == strip(live)


def test_slo_scrape_instruments_and_bad_target():
    h = MetricsHistory(name="t")
    wd = SLOWatchdog(history=h, rules=_lat_rule(), eval_every_s=0.2)
    reg = MetricsRegistry()
    wd.register(reg)
    _drive(h, wd, [50.0] * 200, 1000.0)
    text = reg.prometheus_text()
    assert 'ps_slo_burn_rate{rule="lat"}' in text
    assert 'ps_slo_breaches_total{rule="lat"} 1' in text
    assert "ps_slo_breaches_all_total 1" in text
    with pytest.raises(ValueError):
        SLOWatchdog(history=h, rules=[{"name": "bad", "key": "x",
                                       "mode": "value", "target": 0.0}])


# -- fleet: registration + merging ------------------------------------------

def test_endpoint_registration_overwrite_and_deregister(tmp_path):
    d = str(tmp_path)
    register_endpoint(d, "server", 1111, role="server")
    # a respawned generation re-registers under the same name: ONE card,
    # pointing at the NEW port — the pane follows, no orphan
    register_endpoint(d, "server", 2222, role="server")
    eps = list_endpoints(d)
    assert len(eps) == 1 and eps[0]["url"].endswith(":2222")
    register_endpoint(d, "shard0", 3333, role="shard")
    assert len(list_endpoints(d)) == 2
    deregister_endpoint(d, "server")
    assert [e["name"] for e in list_endpoints(d)] == ["shard0"]
    deregister_endpoint(d, "server")  # idempotent
    # a torn card is skipped, not fatal
    with open(endpoint_path(d, "torn"), "w") as f:
        f.write("{not json")
    assert [e["name"] for e in list_endpoints(d)] == ["shard0"]


def test_parse_prometheus_text_labels_and_inf():
    rows = parse_prometheus_text(
        "# HELP x y\n# TYPE x counter\nx 3\n"
        'x_bucket{le="+Inf",worker="1"} 7\nbad{ 1\n')
    assert {"name": "x", "labels": {}, "value": 3.0} in rows
    assert any(r["labels"].get("worker") == "1"
               and r["labels"].get("le") == "+Inf" for r in rows)


class _FakeServer:
    """Bare PSServerTelemetry carrier for endpoint tests — the mixin
    needs only these attributes (same trick as tests/test_lineage.py)."""

    def __init__(self, num_workers=1, grads=0):
        self.wire = None
        self.template = {"w": np.zeros((4,), np.float32)}
        self.num_workers = num_workers
        self.grads_received = grads
        self.bytes_received = 0
        self.stale_drops = 0
        self.staleness_seen = {}
        self.max_staleness = 4
        self.version = grads
        self.last_seen = {}

    def close(self):
        self.close_observability()
        self.close_metrics_http()


from pytorch_ps_mpi_tpu.telemetry.registry import (  # noqa: E402
    PSServerTelemetry,
)


class _FakePS(_FakeServer, PSServerTelemetry):
    pass


def test_fleet_monitor_merges_members_and_detects_skew(tmp_path):
    d = str(tmp_path)
    a, b = _FakePS(grads=100), _FakePS(grads=10)
    try:
        pa = a.start_metrics_http(0, host="127.0.0.1")
        pb = b.start_metrics_http(0, host="127.0.0.1")
        register_endpoint(d, "shard0", pa, role="shard")
        register_endpoint(d, "shard1", pb, role="shard")
        mon = FleetMonitor(fleet_dir=d, skew_min=8.0, min_poll_s=0.0)
        snap = mon.poll()
        assert snap["n_members"] == 2 and snap["n_ok"] == 2
        assert snap["fleet"]["grads_received"] == 110.0
        for m in snap["members"].values():
            assert m["ok"] and m["uptime_s"] is not None
            assert m["age_s"] is not None and m["age_s"] < 30.0
        skew = snap["skew"]["grads_received"]
        assert skew["flagged"] and skew["max"] == 100.0
        # one member dies -> polled as unreachable, the pane survives
        b.close()
        snap2 = mon.poll(force=True)
        assert snap2["n_ok"] == 1
        assert snap2["members"]["shard1"]["error"] == "unreachable"
        assert snap2["fleet"]["grads_received"] == 100.0
    finally:
        a.close()
        b.close()


def test_fleet_monitor_poll_cache_coalesces():
    mon = FleetMonitor(endpoints=["127.0.0.1:1"],  # nothing listens
                       min_poll_s=60.0, timeout_s=0.2)
    s1 = mon.poll()
    s2 = mon.poll()
    assert s1 is s2 and mon.polls == 1
    assert mon.poll(force=True) is not s1


def test_fleet_concurrent_scrapes_cost_one_sweep():
    # N threads hitting a cold cache serialize behind ONE sweep and
    # reuse its result (the /fleet coalescing contract under
    # ThreadingHTTPServer's thread-per-request model)
    mon = FleetMonitor(endpoints=["127.0.0.1:1"],
                       min_poll_s=60.0, timeout_s=0.3)
    snaps = []
    threads = [threading.Thread(target=lambda: snaps.append(mon.poll()))
               for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(snaps) == 6 and mon.polls == 1
    assert all(s is snaps[0] for s in snaps)


def test_render_fleet_and_sparkline():
    from tools.ps_top import render_fleet, sparkline

    assert sparkline([]) == ""
    assert sparkline([1.0, 1.0]) == "▁▁"
    s = sparkline([0, 1, 2, 3, 4, 5, 6, 7])
    assert s[0] == "▁" and s[-1] == "█"
    snap = {
        "armed": True, "n_members": 2, "n_ok": 1,
        "fleet": {"grads_received": 5, "stale_drops": 1,
                  "reads_total": 2, "reads_shed": 0,
                  "worst_verdict": "slow"},
        "slo": {"breaches_total": 1, "burning": ["shard0:lat"]},
        "skew": {"grads_received": {"min": 1, "max": 4,
                                    "spread_frac": 0.75,
                                    "flagged": True}},
        "members": {
            "shard0": {"name": "shard0", "role": "shard", "ok": True,
                       "verdict": "slow", "uptime_s": 9.0,
                       "age_s": 0.1, "url": "http://x",
                       "metrics": {"grads_received": 4,
                                   "publish_version": 4,
                                   "staleness_p95": 1.0,
                                   "push_e2e_p95_ms": 2.0,
                                   "reads_total": 2}},
            "shard1": {"name": "shard1", "role": "shard", "ok": False,
                       "error": "unreachable", "metrics": {}},
        },
    }
    frame = render_fleet(snap, {("shard0", "staleness_p95"):
                                [0.0, 1.0, 2.0]})
    assert "worst=slow" in frame and "SKEW" in frame
    assert "BURNING: shard0:lat" in frame
    assert "unreachable" in frame
    assert "▁" in frame and "staleness_p95" in frame


# -- /history + /fleet routes on live transports ----------------------------

def _make_server(transport, template, **kw):
    if transport == "shm":
        from pytorch_ps_mpi_tpu.parallel import dcn

        if dcn.get_lib() is None:
            pytest.skip("native toolchain unavailable")
        return dcn.ShmPSServer(f"/psq_obs_{os.getpid()}_{transport}",
                               num_workers=1, template=template, **kw)
    from pytorch_ps_mpi_tpu.parallel import tcp

    if tcp.get_lib() is None:
        pytest.skip("native toolchain unavailable")
    return tcp.TcpPSServer(0, num_workers=1, template=template, **kw)


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.read().decode()


@pytest.mark.parametrize("transport", ["shm", "tcp"])
def test_routes_unarmed_are_explicit_markers(transport):
    server = _make_server(transport, {"w": np.zeros((4,), np.float32)})
    try:
        port = server.start_metrics_http(0, host="127.0.0.1")
        assert json.loads(_get(port, "/history"))["armed"] is False
        assert json.loads(_get(port, "/fleet"))["armed"] is False
    finally:
        server.close()


@pytest.mark.parametrize("transport", ["shm", "tcp"])
def test_concurrent_scrapes_consistent_and_torn_down(transport, tmp_path):
    """The satellite contract: parallel /metrics + /health + /history +
    /fleet on BOTH transports return consistent snapshots while the
    serve thread samples, and server.close() tears every route down
    (no leaked sockets across supervisor restarts)."""
    server = _make_server(transport, {"w": np.zeros((8,), np.float32)})
    try:
        port = server.start_metrics_http(0, host="127.0.0.1")
        server.arm_observability(
            {"timeseries": True, "slo": True,
             "fleet": True, "fleet_dir": str(tmp_path),
             "telemetry_dir": str(tmp_path)})
        for _ in range(6):
            server.observability_tick()
            time.sleep(0.02)
        errs, results = [], {p: [] for p in
                             ("/metrics", "/health",
                              "/history?key=grads_received&window=60",
                              "/fleet")}

        def hammer(path):
            try:
                for _ in range(5):
                    results[path].append(_get(port, path))
                    # interleave with serve-thread-style sampling races
            except Exception as e:  # pragma: no cover
                errs.append((path, repr(e)))

        threads = [threading.Thread(target=hammer, args=(p,))
                   for p in results for _ in range(2)]
        sampler_stop = threading.Event()

        def sampler():
            while not sampler_stop.is_set():
                server.observability_tick()
                time.sleep(0.005)

        # NOTE: in production sampling happens on the serve thread; here
        # a dedicated thread stands in for it to force scrape overlap
        st = threading.Thread(target=sampler)
        st.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        sampler_stop.set()
        st.join(timeout=5)
        assert not errs, errs
        for path, bodies in results.items():
            assert len(bodies) == 10
        for body in results["/health"]:
            doc = json.loads(body)
            assert doc["ts"] > 0 and "slo" in doc
        hist_docs = [json.loads(b) for b in results[
            "/history?key=grads_received&window=60"]]
        for doc in hist_docs:
            assert doc["key"] == "grads_received"
            ts = [p[0] for p in doc["points"]]
            assert ts == sorted(ts)
        for body in results["/fleet"]:
            assert json.loads(body)["armed"] is True
        assert "ps_slo_burn_rate" in results["/metrics"][0]
        # registration card exists while live...
        assert list_endpoints(str(tmp_path))
    finally:
        server.close()
    # ...and close() deregistered it and killed every route's socket
    assert list_endpoints(str(tmp_path)) == []
    for path in ("/metrics", "/health", "/history", "/fleet"):
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=2)


def test_history_route_serves_query_params(tmp_path):
    server = _make_server("shm", {"w": np.zeros((4,), np.float32)})
    try:
        port = server.start_metrics_http(0, host="127.0.0.1")
        server.arm_observability(
            {"timeseries": True, "telemetry_dir": str(tmp_path),
             # unthrottled: the test ticks far faster than the serve
             # loop's cadence
             "timeseries_kw": {"sample_min_interval_s": 0.0}})
        for _ in range(5):
            server.observability_tick()
            time.sleep(0.02)
        listing = json.loads(_get(port, "/history"))
        assert "uptime_s" in listing["key_names"]
        doc = json.loads(_get(
            port, "/history?key=uptime_s&window=60&q=0.95"))
        assert doc["stats"]["n"] >= 5
        assert doc["quantile"]["q"] == 0.95
        assert doc["quantile"]["value"] >= 0.0
        # uptime is monotone -> sampled series must be too
        vals = [p[1] for p in doc["points"]]
        assert vals == sorted(vals)
    finally:
        server.close()


def test_serve_loop_arms_observability_end_to_end(tmp_path):
    """ONE in-process serve() run with the whole plane armed: history
    sampled at tick cadence, SLO evaluated, profiler written, sections
    in the returned metrics, artifacts on disk."""
    from pytorch_ps_mpi_tpu.parallel import dcn
    from pytorch_ps_mpi_tpu.parallel.async_train import (
        join_workers,
        make_problem,
        serve,
        spawn_worker,
    )

    if dcn.get_lib() is None:
        pytest.skip("native toolchain unavailable")
    cfg = {
        "model": "mlp", "model_kw": {"features": (16, 4)},
        "in_shape": [4], "batch": 8, "seed": 0, "steps": 6,
        "optim": "sgd", "hyper": {"lr": 0.05},
        "frame_check": True,
        "timeseries": True, "slo": True, "profile": True,
        "telemetry_dir": str(tmp_path),
        "fleet": True, "fleet_dir": str(tmp_path / "fleet"),
        "metrics_port": 0,
        "slo_kw": {"targets": {"push_e2e_p95_ms": 10_000.0}},
        "tick_interval": 0.05,
    }
    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_obs_e2e_{os.getpid()}"
    server = dcn.ShmPSServer(name, num_workers=2, template=params0,
                             frame=True)
    procs = [spawn_worker(name, i, cfg) for i in range(2)]
    try:
        _, m = serve(server, cfg, total_grads=0, total_received=12,
                     timeout=120.0)
        assert join_workers(procs, timeout=60.0) == [0, 0]
    finally:
        server.close()
        join_workers(procs, timeout=5.0)
    assert m["history"]["samples"] > 0
    assert m["slo"]["breaches_total"] == 0  # healthy run: silent
    assert m["profile"]["samples"] > 0
    assert os.path.exists(tmp_path / "timeseries-server.jsonl")
    assert os.path.exists(tmp_path / "profile-server.txt")
    # the serve loop itself is on the sampled stacks
    _, counts = load_profile(str(tmp_path / "profile-server.txt"))
    assert any("serve" in stack for stack in counts)
    # worker-side profiles landed too (cfg rides the spawn argv)
    assert os.path.exists(tmp_path / "profile-worker-0.txt")
    rows = load_timeseries_rows(str(tmp_path / "timeseries-server.jsonl"))
    assert rows and rows[-1]["m"]["grads_received"] >= 0.0


# -- report sections --------------------------------------------------------

def test_report_routes_obs_artifacts_to_sections(tmp_path):
    from tools.telemetry_report import format_table, summarize

    h = MetricsHistory(name="server", dir=str(tmp_path), flush_every=4)
    wd = SLOWatchdog(history=h, rules=_lat_rule(), dir=str(tmp_path),
                     short_window_s=5.0, long_window_s=20.0,
                     eval_every_s=0.2)
    _drive(h, wd, [50.0] * 200, 1000.0)
    h.close()
    wd.close()
    p = SamplingProfiler(name="server", dir=str(tmp_path), hz=200)
    p.start()
    _busy_for(0.2)
    p.stop()
    p.write()
    # a recorder jsonl beside them proves the span merge is untouched
    rec = telemetry.FlightRecorder(capacity=16, worker="w")
    rec.event("phase.x", kind="span", ts=0.0, dur=0.5)
    rec.dump_jsonl(str(tmp_path / "server.jsonl"))
    summary = summarize([str(tmp_path / f) for f in os.listdir(tmp_path)])
    assert summary["history"]["samples"] == 200
    assert any(k["key"] == "lat" for k in summary["history"]["keys"])
    assert summary["slo"]["rules"] == [
        {"rule": "lat", "breach": 1, "recover": 0}]
    assert summary["profile"]["samples"] > 0
    # the obs jsonls never polluted the span table
    assert [r["name"] for r in summary["spans"]] == ["phase.x"]
    text = format_table(summary)
    for section in ("history (", "profile (merged", "slo ("):
        assert section in text
