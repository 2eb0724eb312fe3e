"""Homomorphic aggregation — the ``Codec.aggregate`` contract.

Three layers of coverage for summing gradients in the compressed domain
(THC / SparCML, PAPERS.md):

1. **Exactness suite** — for every codec with an exact algebra,
   ``agg_decode(aggregate(payloads))`` must be BIT-IDENTICAL to
   ``decode_sum`` across worker counts including 1 and odd counts. The
   approximate sign vote algebra is excluded (it ships behind the
   measured fidelity contract) but must still be exact when per-frame
   scales agree.
2. **Streaming suite** — the host-side ``agg_init``/``agg_fold``/
   ``agg_finalize`` accumulators (what the serve loop's
   ``WireAggregator`` runs per push) must match ``decode_sum`` to
   sequential-f32 tolerance, and the wire-level aggregator must match
   decode-then-tree-sum on real payload bytes, bucketed wires included.
3. **Serve-loop E2E** — a real 2-process shm run in sync-barrier mode
   must arm aggregation (``agg_mode == 1.0``), perform exactly ONE
   decode per published version (``decodes_per_publish == 1.0``), and
   still train; codecs without the algebra must fall back, counted when
   explicitly requested.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.codecs import get_codec
from pytorch_ps_mpi_tpu.codecs.base import Codec

# (name, kwargs, shape) — every EXACT-algebra codec at an awkward
# (non-aligned) shape; worker counts below include 1 and odd counts
EXACT_CODECS = [
    ("int8", {}, (97,)),
    ("qsgd", {"levels": 16}, (97,)),
    ("terngrad", {}, (97,)),
    ("topk", {"k": 7}, (97,)),
    ("topk", {"fraction": 0.1}, (97,)),
    ("randomk", {"k": 7}, (97,)),
    ("randomk", {"fraction": 0.1}, (97,)),
    ("blocktopk", {"fraction": 0.05, "block_size": 128}, (300,)),
    ("blocktopk8", {"fraction": 0.05, "block_size": 128}, (300,)),
    ("threshold", {"tau": 0.5, "max_fraction": 0.5}, (97,)),
    ("powersgd", {"rank": 2, "min_compression_elems": 16}, (16, 12)),
    ("powersgd", {"rank": 2}, (7,)),  # raw (uncompressed) branch
    ("identity", {}, (97,)),
    ("bf16", {}, (97,)),
    ("f16", {}, (97,)),
    ("ef", {"inner_name": "topk", "fraction": 0.1}, (97,)),
]


def _payloads(code, shape, world, seed=0):
    state = code.init_state(shape, jnp.float32)
    out = []
    for i in range(world):
        g = jax.random.normal(jax.random.key(seed + i), shape)
        rng = jax.random.key(100 + i) if code.needs_rng else None
        p, state = code.encode(g, state, rng)
        out.append(p)
    return out


def _stack(payloads):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *payloads)


@pytest.mark.parametrize("world", [1, 3, 4])
@pytest.mark.parametrize("name,kw,shape", EXACT_CODECS,
                         ids=[f"{n}-{s}" for n, k, s in EXACT_CODECS])
def test_aggregate_bit_identical_to_decode_sum(name, kw, shape, world):
    code = get_codec(name, **kw)
    assert code.supports_aggregate and code.agg_exact
    stacked = _stack(_payloads(code, shape, world))
    ref = np.asarray(code.decode_sum(stacked, shape, jnp.float32))
    agg, meta = code.aggregate(stacked, shape, jnp.float32)
    out = np.asarray(code.agg_decode(agg, meta, shape, jnp.float32))
    assert meta["frames"] == world
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("name,kw,shape", EXACT_CODECS,
                         ids=[f"{n}-{s}" for n, k, s in EXACT_CODECS])
def test_streaming_fold_matches_decode_sum(name, kw, shape):
    """agg_init/agg_fold/agg_finalize (numpy, per-push) vs decode_sum:
    exact for concat-domain codecs, sequential-f32-tolerance for the
    scale-folded integer accumulators (summation order differs from the
    einsum by design)."""
    code = get_codec(name, **kw)
    world = 3
    payloads = _payloads(code, shape, world)
    stacked = _stack(payloads)
    ref = np.asarray(code.decode_sum(stacked, shape, jnp.float32))
    acc = code.agg_init(shape, jnp.float32)
    for p in payloads:
        code.agg_fold(acc, jax.tree.map(np.asarray, p))
    out = np.asarray(code.agg_finalize(acc, shape, jnp.float32))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name,kw", [
    ("int8", {}), ("qsgd", {"levels": 16}), ("terngrad", {}),
])
def test_streaming_fold_jitted_large_unit(name, kw, monkeypatch):
    """Units past the fold crossover run the jitted fused kernel —
    same result as decode_sum to f32 tolerance (and as the small-unit
    numpy fold path, covered above). The native fast path outranks the
    jit crossover when armed, so it is force-disabled here to pin the
    jit fallback (native parity lives in tests/test_native_fold.py)."""
    monkeypatch.setenv("PS_NO_NATIVE", "1")
    code = get_codec(name, **kw)
    shape = ((1 << 16) + 5,)  # past base.FOLD_JIT_MIN, ragged
    payloads = _payloads(code, shape, 3)
    stacked = _stack(payloads)
    ref = np.asarray(code.decode_sum(stacked, shape, jnp.float32))
    acc = code.agg_init(shape, jnp.float32)
    assert acc.get("jit"), "expected the jitted fold path"
    for p in payloads:
        code.agg_fold(acc, jax.tree.map(np.asarray, p))
    out = np.asarray(code.agg_finalize(acc, shape, jnp.float32))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_aggregate_payload_is_payload_sized():
    """The SparCML property: the aggregated payload of a sparse codec is
    sized by world × k, never by n — aggregation never densifies."""
    code = get_codec("topk", k=5)
    shape = (10_000,)
    stacked = _stack(_payloads(code, shape, 4))
    agg, meta = code.aggregate(stacked, shape, jnp.float32)
    assert agg["values"].shape == (20,)
    assert agg["indices"].shape == (20,)
    # powersgd: factors of rank world*r, not an [n, m] matrix
    code = get_codec("powersgd", rank=2, min_compression_elems=16)
    shape = (64, 32)
    stacked = _stack(_payloads(code, shape, 4))
    agg, _ = code.aggregate(stacked, shape, jnp.float32)
    assert agg["P"].shape == (64, 8)
    assert agg["Q"].shape == (32, 8)


def test_sign_vote_exact_when_scales_agree_and_measured_when_not():
    code = get_codec("sign", use_pallas=False)
    assert code.supports_aggregate and not code.agg_exact
    shape = (97,)
    g = jax.random.normal(jax.random.key(0), shape)
    p, _ = code.encode(g, ())
    # identical frames -> identical scales -> vote algebra is exact
    stacked = jax.tree.map(lambda x: jnp.stack([x, x]), p)
    ref = np.asarray(code.decode_sum(stacked, shape, jnp.float32))
    agg, meta = code.aggregate(stacked, shape, jnp.float32)
    out = np.asarray(code.agg_decode(agg, meta, shape, jnp.float32))
    np.testing.assert_array_equal(out, ref)
    # streaming form agrees too
    acc = code.agg_init(shape, jnp.float32)
    for _ in range(2):
        code.agg_fold(acc, jax.tree.map(np.asarray, p))
    np.testing.assert_allclose(
        np.asarray(code.agg_finalize(acc, shape, jnp.float32)), ref,
        rtol=1e-6)
    # differing scales: approximate, with SMALL relative error (the
    # number fidelity_bench --aggregate commits per worker count)
    stacked = _stack(_payloads(code, shape, 4, seed=3))
    ref = np.asarray(code.decode_sum(stacked, shape, jnp.float32))
    agg, meta = code.aggregate(stacked, shape, jnp.float32)
    out = np.asarray(code.agg_decode(agg, meta, shape, jnp.float32))
    rel = np.linalg.norm(out - ref) / np.linalg.norm(ref)
    assert 0.0 < rel < 0.25, rel


def test_sign_pallas_layout_declines_aggregation():
    """Per-unit fallback: the Pallas bit layout has no host-side unpack,
    so kernel-eligible sizes refuse aggregation while ragged sizes (jnp
    layout) accept it."""
    code = get_codec("sign", use_pallas=True)
    assert not code.can_aggregate((2048,), jnp.float32)
    assert code.can_aggregate((97,), jnp.float32)


def test_non_algebraic_codec_falls_back():
    """A codec without the algebra: supports_aggregate stays False,
    aggregate raises, and a CodecWire over it reports agg_supported
    False — the serve loop's automatic decode-sum fallback."""
    from pytorch_ps_mpi_tpu.parallel.dcn import CodecWire

    class PlainCodec(Codec):
        def encode(self, grad, state=(), rng=None):
            return grad, state

        def decode(self, payload, shape, dtype):
            return payload.astype(dtype).reshape(shape)

    code = PlainCodec()
    assert not code.supports_aggregate
    with pytest.raises(NotImplementedError):
        code.aggregate(jnp.zeros((2, 4)), (4,), jnp.float32)
    wire = CodecWire(code, {"w": np.zeros(8, np.float32)})
    assert not wire.agg_supported


def test_default_decode_sum_scan_fold():
    """Satellite: the default decode_sum is a lax.scan fold — bit-exact
    to the sequential left-fold definition, 1-ulp from the old
    vmap-then-sum form (XLA's axis-0 reduce used a tree order), and its
    lowered program carries no [world, n]-sized f32 temp."""
    code = get_codec("sign", use_pallas=False)  # uses the base default
    shape = (1 << 16,)
    world = 4
    payloads = _payloads(code, shape, world)
    stacked = _stack(payloads)
    out = np.asarray(code.decode_sum(stacked, shape, jnp.float32))
    # sequential left-fold reference: bit-exact
    seq = np.zeros(shape, np.float32)
    for p in payloads:
        seq = seq + np.asarray(code.decode(p, shape, jnp.float32))
    np.testing.assert_array_equal(out, seq)
    # old vmap-then-sum form: 1-ulp-per-element agreement
    old = np.asarray(jax.vmap(
        lambda p: code.decode(p, shape, jnp.float32))(stacked).sum(axis=0))
    # atol: elements where per-rank scales nearly cancel sit at the ulp
    # of the addends, not of the tiny result
    np.testing.assert_allclose(out, old, rtol=1e-6, atol=1e-6)
    # peak-memory: the scan's lowered temps stay far below the
    # [world, n] f32 stack the vmap form materialized
    f = jax.jit(lambda s: code.decode_sum(s, shape, jnp.float32))
    stats = f.lower(stacked).compile().memory_analysis()
    if stats is not None and hasattr(stats, "temp_size_in_bytes"):
        stack_bytes = world * shape[0] * 4
        assert stats.temp_size_in_bytes < stack_bytes, (
            stats.temp_size_in_bytes, stack_bytes)


def test_terngrad_chunked_encode_wire_compatible():
    """Satellite: the scan-chunked terngrad encode produces the same
    wire format (packed length, scale) and a valid ternary stream at
    ragged and aligned sizes."""
    for n in (4096, 9001):
        chunked = get_codec("terngrad", scan_block=2048, scan_threshold=2048)
        whole = get_codec("terngrad", scan_threshold=n + 1)
        g = jax.random.normal(jax.random.key(2), (n,))
        pc, _ = chunked.encode(g, (), jax.random.key(9))
        pw, _ = whole.encode(g, (), jax.random.key(9))
        assert pc["packed"].shape == pw["packed"].shape == ((n + 3) // 4,)
        np.testing.assert_allclose(float(pc["scale"]), float(pw["scale"]),
                                   rtol=1e-6)
        dec = np.asarray(chunked.decode(pc, (n,), jnp.float32))
        s = float(pc["scale"])
        assert np.all(np.isin(np.round(dec / s).astype(int), [-1, 0, 1]))
        nz = dec != 0
        assert np.all(np.sign(dec[nz]) == np.sign(np.asarray(g)[nz]))


def test_terngrad_chunked_encode_bounds_hlo_temps():
    """The lowered chunked encode must not materialize a full-size f32
    intermediate: its temps are those of ONE ``scan_block`` chunk,
    whatever the gradient's size.

    Derivation of the bound, from what the chunked form lowers to under
    this XLA (``compiled.memory_analysis()``, jax 0.9.0, CPU backend):
    24.0 MiB of temps at 4M, 8M, 16M, 32M and 132M elements alike with
    the default 1M-element ``scan_block`` — 24 bytes per CHUNK element:
    threefry's u64 counter lane (8) and two u32 halves (8), the f32
    uniform draw (4) and the s32 digit select (4); the pred keep mask
    and u8 digits reuse freed lanes. A ragged size may add one copy of
    the packed output where the tail is concatenated (n/4 bytes,
    measured +2.0 MiB at 8M+100). The whole-tensor form costs 16 bytes
    per GRADIENT element (134 MB at 8M). So: temps within 32 B per chunk
    element plus n/4, at every size — flat in n, which the last
    assertion pins directly."""
    code = get_codec("terngrad")
    key = jax.random.key(0)
    temps = {}
    for n in (8 << 20, (8 << 20) + 100, 32 << 20):
        f = jax.jit(lambda g, k: code.encode(g, (), k)[0])
        compiled = f.lower(
            jax.ShapeDtypeStruct((n,), jnp.float32), key).compile()
        stats = compiled.memory_analysis()
        if stats is None or not hasattr(stats, "temp_size_in_bytes"):
            pytest.skip("backend reports no memory analysis")
        temps[n] = stats.temp_size_in_bytes
        assert temps[n] < 32 * code.scan_block + n // 4, (n, temps[n])
    # 4x the gradient, the same temps: nothing scales with n
    assert temps[32 << 20] < 1.05 * temps[8 << 20], temps


def test_ef_delegates_aggregation_to_inner():
    ef = get_codec("ef", inner_name="topk", fraction=0.1)
    assert ef.supports_aggregate and ef.agg_exact
    ef_sign = get_codec("ef", inner_name="sign", use_pallas=False)
    assert ef_sign.supports_aggregate and not ef_sign.agg_exact


def test_spmd_decode_sum_payloads_prefers_exact_algebra_only():
    """ps.decode_sum_payloads: exact algebras route through aggregate
    (bit-identical), the approximate sign vote NEVER enters the SPMD
    path implicitly."""
    from pytorch_ps_mpi_tpu.ps import decode_sum_payloads

    shape = (97,)
    code = get_codec("int8")
    stacked = _stack(_payloads(code, shape, 3))
    np.testing.assert_array_equal(
        np.asarray(decode_sum_payloads(code, stacked, shape, jnp.float32)),
        np.asarray(code.decode_sum(stacked, shape, jnp.float32)))
    sign = get_codec("sign", use_pallas=False)
    stacked = _stack(_payloads(sign, shape, 3))
    # must equal decode_sum EXACTLY (i.e. took the decode_sum branch;
    # the vote algebra would differ for differing scales)
    np.testing.assert_array_equal(
        np.asarray(decode_sum_payloads(sign, stacked, shape, jnp.float32)),
        np.asarray(sign.decode_sum(stacked, shape, jnp.float32)))


# -- wire-level aggregator -------------------------------------------------

def _wire_template():
    return {"w": np.zeros((64, 8), np.float32),
            "b": np.zeros(9, np.float32)}


@pytest.mark.parametrize("name,kw,bucket_mb", [
    ("topk", {"fraction": 0.1}, 0.0),
    ("int8", {}, 0.0),
    ("int8", {}, 0.001),          # bucketed wire units
    ("terngrad", {}, 0.0),
    ("qsgd", {"levels": 16}, 0.0),
    ("randomk", {"fraction": 0.1}, 0.0),
    ("powersgd", {"rank": 2, "min_compression_elems": 16}, 0.0),
    ("bf16", {}, 0.0),
])
def test_wire_aggregator_matches_decode_sum(name, kw, bucket_mb):
    from pytorch_ps_mpi_tpu.parallel.dcn import CodecWire

    wire = CodecWire(get_codec(name, **kw), _wire_template(),
                     bucket_mb=bucket_mb)
    assert wire.agg_supported
    rng = np.random.RandomState(0)
    grads = [{"w": rng.randn(64, 8).astype(np.float32),
              "b": rng.randn(9).astype(np.float32)} for _ in range(3)]
    bufs = [np.copy(wire.encode_to_bytes(g)) for g in grads]
    ref = None
    for b in bufs:
        d = wire.decode_from_bytes(b)
        ref = d if ref is None else jax.tree.map(np.add, ref, d)
    agg = wire.agg_begin()
    for b in bufs:
        agg.fold(b)
    out = agg.finalize()
    assert agg.frames == 3
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        out, ref)


def test_wire_payload_finite_screen():
    from pytorch_ps_mpi_tpu.parallel.dcn import CodecWire

    wire = CodecWire(get_codec("topk", fraction=0.1), _wire_template())
    rng = np.random.RandomState(0)
    good = {"w": rng.randn(64, 8).astype(np.float32),
            "b": rng.randn(9).astype(np.float32)}
    assert wire.payload_finite(wire.encode_to_bytes(good))
    bad = {"w": np.full((64, 8), np.nan, np.float32),
           "b": good["b"]}
    assert not wire.payload_finite(wire.encode_to_bytes(bad))
    # int8: only the f32 scale scalar is screened — still catches the
    # NaN-poisoned frame (NaN absmax -> NaN scale)
    wire8 = CodecWire(get_codec("int8"), _wire_template())
    assert not wire8.payload_finite(wire8.encode_to_bytes(bad))
    # bf16: the ml_dtypes payload dtype has numpy kind 'V', not 'f' —
    # the screen must still catch it (a kind=='f' test is inert for
    # exactly the wires that ship raw float payloads)
    wireb = CodecWire(get_codec("bf16"), _wire_template())
    assert wireb.payload_finite(wireb.encode_to_bytes(good))
    assert not wireb.payload_finite(wireb.encode_to_bytes(bad))


# -- canonical metrics / surfaces ------------------------------------------

def test_canonical_metrics_grow_agg_keys():
    from pytorch_ps_mpi_tpu.telemetry import (
        PS_SERVER_METRIC_KEYS,
        PSServerTelemetry,
        ps_server_metrics,
    )

    for k in ("agg_mode", "decodes_per_publish", "agg_fallbacks"):
        assert k in PS_SERVER_METRIC_KEYS

    class Fake(PSServerTelemetry):
        wire = None
        template = {"w": np.zeros(4, np.float32)}
        num_workers = 2
        max_staleness = 4
        grads_received = 6
        bytes_received = 0
        stale_drops = 0
        staleness_seen = {}
        version = 3

    s = Fake()
    m = ps_server_metrics(s)
    assert m["agg_mode"] == 0.0
    assert m["decodes_per_publish"] == 0.0  # no publish yet
    assert m["agg_fallbacks"] == 0.0
    s.agg_mode = 1.0
    s.decodes_done = 3
    s.grad_publishes = 3
    s.agg_fallbacks = 2
    m = ps_server_metrics(s)
    assert m["agg_mode"] == 1.0
    assert m["decodes_per_publish"] == 1.0
    assert m["agg_fallbacks"] == 2.0
    # scrape instruments land in the registry text
    text = s.prometheus_text()
    assert "ps_decodes_per_publish 1" in text
    assert "ps_agg_fallbacks_total 2" in text
    assert "ps_agg_mode 1" in text


def test_ps_top_renders_agg_rollup():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ps_top", os.path.join(os.path.dirname(__file__), os.pardir,
                               "tools", "ps_top.py"))
    ps_top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ps_top)
    doc = {
        "armed": True, "n_workers": 2, "uptime_s": 1.0,
        "fleet": {"grads_received": 8, "stale_drops": 0,
                  "staleness_p50": 0, "staleness_p95": 0,
                  "staleness_p99": 0, "anomaly_total": 0, "rounds": 4,
                  "agg_mode": 1.0, "decodes_per_publish": 1.0,
                  "agg_fallbacks": 3},
        "workers": [],
    }
    frame = ps_top.render_table(doc)
    assert "agg=on" in frame
    assert "dec/pub=1.00" in frame
    assert "agg_fb=3" in frame
    doc["fleet"]["agg_mode"] = 0.0
    doc["fleet"]["agg_fallbacks"] = 0
    assert "agg=off" in ps_top.render_table(doc)


# -- serve-loop E2E --------------------------------------------------------

from pytorch_ps_mpi_tpu.parallel import dcn  # noqa: E402

needs_native = pytest.mark.skipif(
    dcn.get_lib() is None, reason="native toolchain unavailable")


def _serve_cfg(codec, codec_kw, **extra):
    cfg = {
        "model": "mlp", "model_kw": {"features": (16, 4)},
        "in_shape": (8,), "batch": 32, "seed": 5,
        "codec": codec, "codec_kw": codec_kw,
        "optim": "sgd", "hyper": {"lr": 0.05}, "steps": 8,
        "frame_check": True,
    }
    cfg.update(extra)
    return cfg


def _run_sync_serve(cfg, n_workers=2, frame=True):
    from pytorch_ps_mpi_tpu.parallel.async_train import (
        join_workers,
        make_problem,
        serve,
        spawn_worker,
    )

    _, params0, _, _ = make_problem(cfg)
    name = f"/psq_agg_{os.getpid()}_{abs(hash(str(cfg))) % 10000}"
    server = dcn.ShmPSServer(
        name, num_workers=n_workers, template=params0,
        max_staleness=10**9,
        code=get_codec(cfg["codec"], **cfg["codec_kw"]), frame=frame)
    try:
        procs = [spawn_worker(name, i, cfg) for i in range(n_workers)]
        _, m = serve(server, cfg, total_grads=0,
                     total_received=n_workers * cfg["steps"],
                     sync_barrier=True, timeout=180.0)
        assert join_workers(procs, timeout=120) == [0] * n_workers
    finally:
        server.close()
    return m


@needs_native
def test_serve_loop_one_decode_per_publish():
    """THE headline: a sync-barrier shm run over a sparse codec folds
    every push into the compressed accumulator and decodes exactly once
    per published version — while training still converges and every
    push is accounted."""
    m = _run_sync_serve(_serve_cfg("topk", {"fraction": 0.25}))
    assert m["agg_mode"] == 1.0
    assert m["decodes_per_publish"] == 1.0, m["decodes_per_publish"]
    assert m["agg_fallbacks"] == 0.0
    assert m["applied"] == 16
    assert m["loss_final"] < m["loss_initial"]
    # /health carries the rollup
    assert m["grads_received"] == 16


@needs_native
@pytest.mark.slow  # make agg-smoke exercises the same paths in CI
def test_serve_loop_fallback_counts_when_requested():
    """sign + use_pallas=False has only the APPROXIMATE algebra: 'auto'
    must NOT arm it (a default config never changes training numerics);
    the explicit agg='on' is the opt-in to the measured fidelity
    contract and does arm it."""
    # auto + approximate algebra: decode-sum path, no fallback counting
    # (nothing was explicitly requested)
    m = _run_sync_serve(_serve_cfg("sign", {"use_pallas": False}))
    assert m["agg_mode"] == 0.0
    assert m["agg_fallbacks"] == 0.0
    assert m["decodes_per_publish"] > 1.5

    # explicit opt-in: vote algebra armed
    m = _run_sync_serve(
        _serve_cfg("sign", {"use_pallas": False}, agg="on"))
    assert m["agg_mode"] == 1.0
    assert m["decodes_per_publish"] == 1.0
    assert m["loss_final"] < m["loss_initial"]

    # agg explicitly ON but numerics armed -> decode path + counted
    # fallbacks (numerics validation needs decoded trees)
    cfg = _serve_cfg("topk", {"fraction": 0.25}, agg="on", numerics=True)
    m = _run_sync_serve(cfg)
    assert m["agg_mode"] == 0.0
    assert m["agg_fallbacks"] == 16.0
    assert m["decodes_per_publish"] > 1.5  # ~2 with 2 workers


@needs_native
@pytest.mark.slow  # the agg="off" leg also runs inside make agg-smoke
def test_serve_loop_agg_off_keeps_legacy_path():
    m = _run_sync_serve(_serve_cfg("topk", {"fraction": 0.25}, agg="off"))
    assert m["agg_mode"] == 0.0
    assert m["decodes_per_publish"] > 1.5
    assert m["loss_final"] < m["loss_initial"]


@needs_native
def test_serve_loop_screens_nonfinite_payload():
    """Armed aggregation must never fold a non-finite payload: a worker
    whose step-3 gradient is NaN-poisoned (the resilience layer's 'nan'
    fault) has exactly that push rejected through the payload screen
    (``frames_rejected``, reason nonfinite), the barrier waits for its
    next push, and the published params stay finite."""
    cfg = _serve_cfg(
        "topk", {"fraction": 0.25},
        fault_plan=[{"at_step": 3, "worker": 1, "kind": "nan"}])
    m = _run_sync_serve(cfg)
    assert m["agg_mode"] == 1.0
    assert m["decodes_per_publish"] == 1.0
    assert m["frames_rejected"] == 1.0
    # the poisoned push composed no round: 16 received, 7 full rounds
    # (+1 degraded drain round when the dead-worker timeout fires)
    assert m["grads_received"] == 16 and m["applied"] in (14.0, 15.0)
    assert np.isfinite(m["loss_final"])


@needs_native
def test_poll_grad_raw_requires_codec_wire():
    """raw=True on a no-codec server must raise, not hand back a
    silently mis-sized f32 view of the receive buffer."""
    template = {"w": np.zeros(8, np.float32)}
    server = dcn.ShmPSServer(f"/psq_rawguard_{os.getpid()}",
                             num_workers=1, template=template)
    try:
        with pytest.raises(ValueError, match="codec wire"):
            server.poll_grad(raw=True)
    finally:
        server.close()
