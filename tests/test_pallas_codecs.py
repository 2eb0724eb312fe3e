"""Pallas codec kernels (interpret mode on CPU): the exact top-k
selection kernel and the fused sign / terngrad encode paths.

Interpret mode runs the same kernel logic element-for-element, so
these tests pin correctness; that the kernels compile for a TPU is
``tests/test_kernels_compile_tpu.py``, that they execute there against
their references is ``chip_smoke.py`` phase (c); their speed has no
chip number yet (``PERF.md`` section 7).
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pytorch_ps_mpi_tpu.codecs import get_codec  # noqa: E402
from pytorch_ps_mpi_tpu.ops.topk_pallas import exact_topk  # noqa: E402


# ---------------------------------------------------------------------------
# exact top-k (threshold refine + chunked compaction)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k", [
    (16_384, 64),       # multiple of the count tile
    (100_000, 1024),    # ragged vs the tile, k > chunk survivors per chunk
    (8_192 + 7, 100),   # ragged n
    (40_000, 1),        # k = 1
    (9_000, 3000),      # k > chunk (2048): multi-chunk survivor prefixes
])
def test_exact_topk_matches_lax_topk_multiset(n, k):
    rng = np.random.RandomState(n % 97)
    x = jnp.asarray(rng.randn(n).astype(np.float32))
    v, i = exact_topk(x, k, chunk=2048)
    ref_v, ref_i = jax.lax.top_k(jnp.abs(x), k)
    # same VALUE multiset (ties may pick different indices); indices
    # unique, in range, and values actually live at their indices
    np.testing.assert_allclose(np.sort(np.abs(np.asarray(v))),
                               np.sort(np.asarray(ref_v)), rtol=0, atol=0)
    idx = np.asarray(i)
    assert len(np.unique(idx)) == k
    assert idx.min() >= 0 and idx.max() < n
    np.testing.assert_array_equal(np.asarray(v), np.asarray(x)[idx])


def test_exact_topk_with_ties_fills_exactly_k():
    # heavy ties at the threshold: 0.5 appears many times, and the
    # kernel must take strict survivors first, then EXACTLY enough ties
    x = np.full(20_000, 0.5, np.float32)
    x[::7] = 2.0          # 2858 strict survivors
    k = 4000
    v, i = exact_topk(jnp.asarray(x), k, chunk=2048)
    idx = np.asarray(i)
    assert len(np.unique(idx)) == k
    vals = np.abs(np.asarray(v))
    assert (vals == 2.0).sum() == (np.abs(x) == 2.0).sum()
    assert (vals == 0.5).sum() == k - (np.abs(x) == 2.0).sum()


def test_exact_topk_small_or_large_k_falls_back():
    x = jnp.asarray(np.random.RandomState(0).randn(512).astype(np.float32))
    v, i = exact_topk(x, 512)  # k == n (the codec clamps k <= n)
    assert v.shape[0] == 512
    v2, i2 = exact_topk(x, 16)  # n < 4*chunk
    ref_v, _ = jax.lax.top_k(jnp.abs(x), 16)
    np.testing.assert_allclose(np.sort(np.abs(np.asarray(v2))),
                               np.sort(np.asarray(ref_v)))


def test_topk_codec_pallas_roundtrip_and_flags():
    n = 100_000
    g = jnp.asarray(np.random.RandomState(3).randn(n).astype(np.float32))
    code = get_codec("topk", k=256, pallas=True)
    exact = get_codec("topk", k=256)
    p, _ = code.encode(g)
    pe, _ = exact.encode(g)
    # same selected-value multiset as the exact sort-based encode
    np.testing.assert_allclose(
        np.sort(np.abs(np.asarray(p["values"]))),
        np.sort(np.abs(np.asarray(pe["values"]))))
    d = code.decode(p, (n,), jnp.float32)
    nz = np.flatnonzero(np.asarray(d))
    assert len(nz) == 256
    np.testing.assert_array_equal(np.asarray(d)[nz], np.asarray(g)[nz])
    with pytest.raises(ValueError, match="alternative selection"):
        get_codec("topk", k=4, approx=True, pallas=True)


# ---------------------------------------------------------------------------
# fused sign encode (pack + |g|-sum in one pass)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1024, 4096, 1024 * 300])
def test_sign_fused_encode_matches_two_pass(n):
    from pytorch_ps_mpi_tpu.ops.sign_pallas import encode_signs, pack_signs

    g = jnp.asarray(np.random.RandomState(5).randn(n).astype(np.float32))
    packed, abs_sum = encode_signs(g)
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(pack_signs(g)))
    ref = float(jnp.sum(jnp.abs(g)))
    assert abs(float(abs_sum) - ref) <= 1e-5 * ref


def test_sign_codec_pallas_scale_and_decode():
    n = 2048
    g = jnp.asarray(np.random.RandomState(6).randn(n).astype(np.float32))
    code = get_codec("sign")  # use_pallas defaults True
    p, _ = code.encode(g)
    ref_scale = float(jnp.mean(jnp.abs(g)))
    assert abs(float(p["scale"]) - ref_scale) <= 1e-5 * ref_scale
    d = code.decode(p, (n,), jnp.float32)
    np.testing.assert_array_equal(np.sign(np.asarray(d)),
                                  np.where(np.asarray(g) >= 0, 1.0, -1.0))


# ---------------------------------------------------------------------------
# fused terngrad ternarize + pack
# ---------------------------------------------------------------------------


def test_terngrad_pallas_decode_roundtrip_and_signs():
    n = 4096
    g = jnp.asarray(np.random.RandomState(8).randn(n).astype(np.float32))
    code = get_codec("terngrad", use_pallas=True)
    p, _ = code.encode(g, rng=jax.random.PRNGKey(0))
    assert p["packed"].shape[0] == n // 4
    d = np.asarray(code.decode(p, (n,), jnp.float32))
    s = float(p["scale"])
    assert s == pytest.approx(float(jnp.max(jnp.abs(g))), rel=1e-6)
    ratios = np.round(d / s).astype(int)
    assert set(np.unique(ratios)) <= {-1, 0, 1}
    nz = d != 0
    np.testing.assert_array_equal(np.sign(d[nz]), np.sign(np.asarray(g)[nz]))
    # the largest-|g| element is kept with probability 1
    assert d[np.abs(np.asarray(g)).argmax()] != 0


def test_terngrad_pallas_keep_probability_tracks_magnitude():
    """Bernoulli(|g|/s): over many draws the keep rate of a constant-
    magnitude vector must track |g|/s (the 24-bit compare is the same
    resolution jax.random.uniform has)."""
    n = 8192
    g = np.full(n, 0.25, np.float32)
    g[0] = 1.0  # pins scale to 1 -> keep prob 0.25 elsewhere
    code = get_codec("terngrad", use_pallas=True)
    p, _ = code.encode(jnp.asarray(g), rng=jax.random.PRNGKey(42))
    d = np.asarray(code.decode(p, (n,), jnp.float32))
    keep_rate = (d[1:] != 0).mean()
    assert 0.22 < keep_rate < 0.28, keep_rate


def test_terngrad_pallas_scan_path_consistent_with_decode():
    """Above the scan threshold the per-chunk fused packs must
    concatenate into exactly the whole-tensor Pallas layout — decode
    (one global unpack) sees well-formed digits with correct signs."""
    code = get_codec("terngrad", use_pallas=True, scan_block=2048,
                     scan_threshold=4096)
    n = 2048 * 3 + 1024  # ragged tail, still % 512
    g = np.random.RandomState(9).randn(n).astype(np.float32)
    p, _ = code.encode(jnp.asarray(g), rng=jax.random.PRNGKey(1))
    assert p["packed"].shape[0] == n // 4
    d = np.asarray(code.decode(p, (n,), jnp.float32))
    s = float(p["scale"])
    assert set(np.unique(np.round(d / s).astype(int))) <= {-1, 0, 1}
    nz = d != 0
    np.testing.assert_array_equal(np.sign(d[nz]), np.sign(g[nz]))
    # a keep rate in the right ballpark proves the random bits differ
    # per chunk (identical chunks would show banded keep patterns; we
    # check the aggregate instead of the pattern for robustness)
    expect = np.abs(g).mean() / s
    assert abs(nz.mean() - expect) < 0.05


def test_terngrad_pallas_streaming_fold_matches_decode_sum():
    """The layout-aware numpy fold (native C++ declines the sublane
    layout) must equal per-frame decode + add exactly."""
    n = 2048
    code = get_codec("terngrad", use_pallas=True)
    rng = jax.random.PRNGKey(3)
    payloads = []
    for i in range(3):
        g = jnp.asarray(np.random.RandomState(i).randn(n).astype(np.float32))
        p, _ = code.encode(g, rng=jax.random.fold_in(rng, i))
        payloads.append({k: np.asarray(v) for k, v in p.items()})
    acc = code.agg_init((n,), jnp.float32)
    for p in payloads:
        code.agg_fold(acc, p)
    out = np.asarray(code.agg_finalize(acc, (n,), jnp.float32))
    ref = sum(np.asarray(code.decode(p, (n,), jnp.float32))
              for p in payloads)
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)


def test_terngrad_pallas_unbiased_expectation():
    """E[decode] -> g over repeated draws (the estimator survives the
    fused kernel's 24-bit Bernoulli compare)."""
    n = 512
    g = np.random.RandomState(11).randn(n).astype(np.float32)
    code = get_codec("terngrad", use_pallas=True)
    acc = np.zeros(n, np.float64)
    R = 60
    key = jax.random.PRNGKey(7)
    for i in range(R):
        p, _ = code.encode(jnp.asarray(g), rng=jax.random.fold_in(key, i))
        acc += np.asarray(code.decode(p, (n,), jnp.float32))
    err = np.abs(acc / R - g).mean() / np.abs(g).mean()
    assert err < 0.35, err  # ~1/sqrt(60) Monte Carlo noise per element
