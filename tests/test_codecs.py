"""Codec unit tests — pure-logic coverage the reference never had
(SURVEY §4: "no unit tests of pure logic anywhere in the repo")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.codecs import (
    ErrorFeedback,
    IdentityCodec,
    Int8Codec,
    QSGDCodec,
    RandomKCodec,
    SignCodec,
    TernGradCodec,
    TopKCodec,
    get_codec,
)


def grad(shape=(33,), seed=0):
    return jax.random.normal(jax.random.key(seed), shape)


def roundtrip(codec, g, rng=None):
    state = codec.init_state(g.shape, g.dtype)
    payload, _ = codec.encode(g, state, rng)
    return codec.decode(payload, g.shape, g.dtype)


def test_registry():
    assert isinstance(get_codec("identity"), IdentityCodec)
    assert isinstance(get_codec("topk", k=4), TopKCodec)
    with pytest.raises(KeyError):
        get_codec("nope")


def test_identity_exact():
    g = grad((4, 5))
    np.testing.assert_array_equal(np.asarray(roundtrip(IdentityCodec(), g)), np.asarray(g))


def test_topk_keeps_largest():
    g = jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05])
    out = np.asarray(roundtrip(TopKCodec(k=2), g))
    np.testing.assert_allclose(out, [0.0, -5.0, 0.0, 3.0, 0.0])


def test_topk_fraction_and_bits():
    c = TopKCodec(fraction=0.25)
    g = grad((100,))
    out = np.asarray(roundtrip(c, g))
    assert (out != 0).sum() <= 25
    assert c.payload_bits(g.shape, g.dtype) == 25 * (32 + 32)


def test_topk_approx_recalls_most_mass():
    # approx_max_k (TPU hardware top-k) has ~0.95 recall; on CPU it is
    # exact for small inputs — either way the kept mass must dominate.
    g = grad((4096,))
    exact = np.asarray(roundtrip(TopKCodec(fraction=0.1), g))
    approx = np.asarray(roundtrip(TopKCodec(fraction=0.1, approx=True), g))
    assert (approx != 0).sum() <= 410
    exact_mass = np.abs(exact).sum()
    assert np.abs(approx).sum() >= 0.8 * exact_mass


def test_topk_decode_sum_fused_equals_loop():
    c = TopKCodec(k=3)
    gs = [grad((20,), seed=i) for i in range(4)]
    payloads = [c.encode(g, ())[0] for g in gs]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *payloads)
    fused = np.asarray(c.decode_sum(stacked, (20,), jnp.float32))
    loop = sum(np.asarray(c.decode(p, (20,), jnp.float32)) for p in payloads)
    np.testing.assert_allclose(fused, loop, rtol=1e-6)


def test_randomk_unbiased_expectation():
    c = RandomKCodec(k=8)
    g = grad((32,))
    outs = [
        np.asarray(roundtrip(c, g, jax.random.key(i))) for i in range(500)
    ]
    # per-coordinate std of the mean is ~|g|*sqrt(3/500); 0.5 is ~4 sigma
    mean = np.mean(outs, axis=0)
    np.testing.assert_allclose(mean, np.asarray(g), atol=0.5)


def test_int8_accuracy():
    g = grad((256,))
    out = np.asarray(roundtrip(Int8Codec(use_pallas=False), g))
    scale = float(jnp.max(jnp.abs(g))) / 127
    np.testing.assert_allclose(out, np.asarray(g), atol=scale)


def test_int8_pallas_matches_jnp():
    g = grad((2048,))
    a = np.asarray(roundtrip(Int8Codec(use_pallas=True), g))
    b = np.asarray(roundtrip(Int8Codec(use_pallas=False), g))
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_int8_pallas_ragged_trailing_block():
    # rows=1040 is not a multiple of the 1024-row kernel block: the absmax
    # pass must mask the trailing block's overhang, not read past the data.
    g = grad((1040 * 128,))
    a = np.asarray(roundtrip(Int8Codec(use_pallas=True), g))
    b = np.asarray(roundtrip(Int8Codec(use_pallas=False), g))
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_qsgd_unbiased():
    c = QSGDCodec(levels=4)
    g = grad((32,))
    outs = [
        np.asarray(roundtrip(c, g, jax.random.key(i))) for i in range(300)
    ]
    np.testing.assert_allclose(np.mean(outs, axis=0), np.asarray(g), atol=0.15)


def test_sign_codec():
    g = jnp.asarray([1.0, -2.0, 3.0, -4.0, 5.0])
    c = SignCodec()
    out = np.asarray(roundtrip(c, g))
    scale = np.abs(np.asarray(g)).mean()
    np.testing.assert_allclose(out, scale * np.sign(np.asarray(g)))
    # 1 bit/element + fp32 scale, packed
    assert c.payload_bits((1000,), jnp.float32) == 125 * 8 + 32


def test_terngrad_values_and_bits():
    c = TernGradCodec()
    g = grad((37,))
    out = np.asarray(roundtrip(c, g, jax.random.key(3)))
    scale = float(jnp.max(jnp.abs(g)))
    # every decoded coordinate is in {-s, 0, +s} with the sign of g
    np.testing.assert_allclose(
        out, np.where(out != 0, scale * np.sign(np.asarray(g)), 0), rtol=1e-6
    )
    # 2 bits/element packed 4-per-byte + fp32 scale
    assert c.payload_bits((1000,), jnp.float32) == 250 * 8 + 32


def test_terngrad_unbiased_expectation():
    c = TernGradCodec()
    g = grad((32,))
    outs = [np.asarray(roundtrip(c, g, jax.random.key(i))) for i in range(500)]
    np.testing.assert_allclose(np.mean(outs, axis=0), np.asarray(g), atol=0.5)


def test_terngrad_decode_sum_matches_loop():
    c = TernGradCodec()
    gs = [grad((20,), seed=i) for i in range(4)]
    payloads = [c.encode(g, (), jax.random.key(10 + i))[0] for i, g in enumerate(gs)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *payloads)
    fused = np.asarray(c.decode_sum(stacked, (20,), jnp.float32))
    loop = sum(np.asarray(c.decode(p, (20,), jnp.float32)) for p in payloads)
    np.testing.assert_allclose(fused, loop, rtol=1e-6)


def test_error_feedback_accumulates_residual():
    inner = TopKCodec(k=1)
    c = ErrorFeedback(inner)
    g = jnp.asarray([1.0, 0.6])
    state = c.init_state(g.shape, g.dtype)
    payload, state = c.encode(g, state)
    # transmitted [1, 0]; memory keeps the dropped 0.6
    np.testing.assert_allclose(np.asarray(state["memory"]), [0.0, 0.6])
    # next round the residual wins: corrected = [1, 1.2] → index 1 sent
    payload2, state2 = c.encode(g, state)
    out2 = np.asarray(c.decode(payload2, g.shape, g.dtype))
    np.testing.assert_allclose(out2, [0.0, 1.2])


def test_payload_bits_identity():
    c = IdentityCodec()
    assert c.payload_bits((10, 10), jnp.float32) == 100 * 32


def test_powersgd_lowrank_roundtrip():
    from pytorch_ps_mpi_tpu.codecs import PowerSGDCodec

    c = PowerSGDCodec(rank=4, min_compression_elems=16)
    # exactly rank-4 matrix -> one power iteration with warm start
    # converges to near-exact reconstruction within a few rounds
    k1, k2 = jax.random.split(jax.random.key(0))
    g = jax.random.normal(k1, (32, 4)) @ jax.random.normal(k2, (4, 24))
    state = c.init_state(g.shape, g.dtype)
    for _ in range(4):
        payload, state = c.encode(g, state)
    out = np.asarray(c.decode(payload, g.shape, g.dtype))
    np.testing.assert_allclose(out, np.asarray(g), rtol=1e-3, atol=1e-3)


def test_powersgd_small_tensors_raw():
    from pytorch_ps_mpi_tpu.codecs import PowerSGDCodec

    c = PowerSGDCodec(rank=2)
    g = grad((7,))
    payload, _ = c.encode(g, c.init_state(g.shape, g.dtype))
    assert "raw" in payload
    np.testing.assert_array_equal(
        np.asarray(c.decode(payload, g.shape, g.dtype)), np.asarray(g)
    )
    # payload_bits: raw for vectors, r*(n+m)*32 for big matrices
    assert c.payload_bits((7,), jnp.float32) == 7 * 32
    assert c.payload_bits((64, 64), jnp.float32) == 2 * 128 * 32


def test_powersgd_error_feedback_builtin():
    from pytorch_ps_mpi_tpu.codecs import PowerSGDCodec

    c = PowerSGDCodec(rank=1, min_compression_elems=4)
    g = jax.random.normal(jax.random.key(3), (8, 8))
    state = c.init_state(g.shape, g.dtype)
    payload, state = c.encode(g, state)
    # memory holds the residual of the rank-1 approximation
    approx = np.asarray(c.decode(payload, g.shape, g.dtype))
    np.testing.assert_allclose(
        np.asarray(state["memory"]), np.asarray(g) - approx, rtol=1e-4, atol=1e-5
    )


def test_sign_pallas_roundtrip_selfconsistent():
    """Pallas pack/unpack kernels: decode(encode(g)) recovers the signs
    for kernel-eligible sizes (n % 1024 == 0)."""
    c = SignCodec(use_pallas=True)
    g = jax.random.normal(jax.random.key(5), (2048,))
    state = c.init_state(g.shape, g.dtype)
    payload, _ = c.encode(g, state)
    assert payload["packed"].shape == (256,)
    out = np.asarray(c.decode(payload, g.shape, g.dtype))
    scale = float(jnp.mean(jnp.abs(g)))
    np.testing.assert_allclose(out, scale * np.where(np.asarray(g) >= 0, 1, -1),
                               rtol=1e-6)


def test_sign_pallas_matches_jnp_training_effect():
    # same decoded values regardless of backend path (different bit
    # layouts, identical decoded gradient)
    g = jax.random.normal(jax.random.key(6), (1024,))
    a = np.asarray(roundtrip(SignCodec(use_pallas=True), g))
    b = np.asarray(roundtrip(SignCodec(use_pallas=False), g))
    np.testing.assert_allclose(a, b, rtol=1e-6)


# -- threshold: the genuinely ragged codec ---------------------------------

def test_threshold_length_is_data_dependent():
    """Survivor count varies with the data — the ragged property."""
    from pytorch_ps_mpi_tpu.codecs import ThresholdCodec

    c = ThresholdCodec(tau=2.0, max_fraction=1.0)
    spiky = jnp.zeros(64).at[jnp.array([3, 17])].set(100.0)
    flat_g = jnp.ones(64)
    p1, _ = c.encode(spiky, c.init_state((64,), jnp.float32))
    p2, _ = c.encode(flat_g, c.init_state((64,), jnp.float32))
    assert int(p1["length"]) == 2
    assert int(p2["length"]) == 0  # nothing exceeds 2x the mean
    assert int(p1["length"]) != int(p2["length"])


def test_threshold_decode_masks_garbage_tail():
    """Slots past `length` are garbage by design; decode must ignore them
    using the sidecar (the receive half of the ragged protocol)."""
    from pytorch_ps_mpi_tpu.codecs import ThresholdCodec

    c = ThresholdCodec(tau=2.0, max_fraction=0.5)
    g = jnp.zeros(32).at[jnp.array([5, 9])].set(jnp.array([10.0, -8.0]))
    payload, _ = c.encode(g, c.init_state((32,), jnp.float32))
    assert int(payload["length"]) == 2
    # corrupt the garbage tail on the wire; decode must not change
    bad = dict(payload)
    bad["values"] = payload["values"].at[3:].set(999.0)
    bad["indices"] = payload["indices"].at[3:].set(7)
    out = c.decode(bad, (32,), jnp.float32)
    expected = np.zeros(32); expected[5] = 10.0; expected[9] = -8.0
    np.testing.assert_allclose(np.asarray(out), expected)


def test_threshold_decode_sum_masks_per_worker():
    from pytorch_ps_mpi_tpu.codecs import ThresholdCodec

    c = ThresholdCodec(tau=2.0, max_fraction=0.5)
    g1 = jnp.zeros(32).at[2].set(50.0)            # 1 survivor
    g2 = jnp.zeros(32).at[jnp.array([2, 30])].set(jnp.array([7.0, -7.0]))
    p1, _ = c.encode(g1, c.init_state((32,), jnp.float32))
    p2, _ = c.encode(g2, c.init_state((32,), jnp.float32))
    assert int(p1["length"]) != int(p2["length"])  # ragged across workers
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), p1, p2)
    out = np.asarray(c.decode_sum(stacked, (32,), jnp.float32))
    expected = np.zeros(32); expected[2] = 57.0; expected[30] = -7.0
    np.testing.assert_allclose(out, expected)


def test_threshold_cap_overflow_drops_tail():
    from pytorch_ps_mpi_tpu.codecs import ThresholdCodec

    c = ThresholdCodec(tau=0.0, max_fraction=0.25)  # everything survives
    g = jnp.arange(1.0, 17.0)
    payload, _ = c.encode(g, c.init_state((16,), jnp.float32))
    assert payload["values"].shape == (4,)          # static cap
    assert int(payload["length"]) == 4              # clamped
    out = np.asarray(c.decode(payload, (16,), jnp.float32))
    np.testing.assert_allclose(out[:4], np.arange(1.0, 5.0))
    np.testing.assert_allclose(out[4:], 0.0)


def test_threshold_adaptive_tau_tracks_target():
    """With target_fraction set, tau rises when too much survives and the
    kept fraction converges toward the target."""
    from pytorch_ps_mpi_tpu.codecs import ThresholdCodec

    c = ThresholdCodec(tau=0.01, max_fraction=1.0, target_fraction=0.1)
    state = c.init_state((512,), jnp.float32)
    kept = []
    for i in range(30):
        g = jax.random.normal(jax.random.key(i), (512,))
        payload, state = c.encode(g, state)
        kept.append(int(payload["length"]))
    assert kept[0] > 400            # tau=0.01 keeps nearly everything
    assert 20 <= np.mean(kept[-5:]) <= 120   # ~10% of 512 at steady state


def test_threshold_validation():
    from pytorch_ps_mpi_tpu.codecs import ThresholdCodec

    with pytest.raises(ValueError):
        ThresholdCodec(max_fraction=0.0)
    with pytest.raises(ValueError):
        ThresholdCodec(max_fraction=0.1, target_fraction=0.2)
    with pytest.raises(ValueError):
        ThresholdCodec(compaction="bogus")


def test_threshold_sort_and_scatter_compaction_agree():
    """The sort compaction (TPU-vectorized bitonic) and the nonzero
    scatter compaction produce the SAME survivor set: identical lengths,
    identical valid-region indices/values, identical decoded gradients —
    including under cap overflow (both drop the tail in index order)."""
    from pytorch_ps_mpi_tpu.codecs import ThresholdCodec

    for tau, max_fraction in [(2.0, 0.25), (0.1, 0.05)]:  # normal, overflow
        sort_c = ThresholdCodec(tau=tau, max_fraction=max_fraction,
                                compaction="sort")
        scat_c = ThresholdCodec(tau=tau, max_fraction=max_fraction,
                                compaction="scatter")
        g = jax.random.normal(jax.random.key(7), (64, 32))
        p_sort, _ = sort_c.encode(g, sort_c.init_state(g.shape, g.dtype))
        p_scat, _ = scat_c.encode(g, scat_c.init_state(g.shape, g.dtype))
        k = int(p_sort["length"])
        assert k == int(p_scat["length"])
        np.testing.assert_array_equal(
            np.asarray(p_sort["indices"][:k]), np.asarray(p_scat["indices"][:k])
        )
        np.testing.assert_array_equal(
            np.asarray(p_sort["values"][:k]), np.asarray(p_scat["values"][:k])
        )
        np.testing.assert_array_equal(
            np.asarray(sort_c.decode(p_sort, g.shape, g.dtype)),
            np.asarray(scat_c.decode(p_scat, g.shape, g.dtype)),
        )


def test_cast_codecs_roundtrip_and_wire_size():
    """bf16/f16 wires: half the bytes, values within the narrow format's
    precision, f32 accumulation in decode_sum."""
    from pytorch_ps_mpi_tpu.codecs import get_codec

    g = jax.random.normal(jax.random.key(0), (64, 32))
    for name, rtol in [("bf16", 1e-2), ("f16", 1e-3)]:
        c = get_codec(name)
        payload, _ = c.encode(g, c.init_state(g.shape, g.dtype))
        assert payload.dtype == (jnp.bfloat16 if name == "bf16" else jnp.float16)
        out = c.decode(payload, g.shape, g.dtype)
        assert out.dtype == g.dtype
        np.testing.assert_allclose(np.asarray(out), np.asarray(g), rtol=rtol,
                                   atol=1e-3)
        assert c.payload_bits(g.shape, g.dtype) == g.size * 16  # half of f32
        # stacked sum accumulates in f32 (cast-up BEFORE the sum)
        stacked = jnp.stack([payload] * 8)
        s = c.decode_sum(stacked, g.shape, g.dtype)
        np.testing.assert_allclose(np.asarray(s), 8 * np.asarray(out),
                                   rtol=1e-5)


def test_bf16_codec_through_distributed_step(mesh8):
    """The bf16 wire through the fused MPI_PS step (psum fast path):
    training matches the identity-codec run to bf16 precision."""
    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.codecs import get_codec

    def run(codec_name):
        params = {"w": jnp.zeros((6, 3))}

        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((x @ p["w"] - y) ** 2)

        opt = SGD(params, lr=0.05, average=True,
                  code=get_codec(codec_name) if codec_name else None)
        k1, k2 = jax.random.split(jax.random.key(5))
        batch = (jax.random.normal(k1, (16, 6)), jax.random.normal(k2, (16, 3)))
        for _ in range(5):
            loss, _ = opt.step(loss_fn=loss_fn, batch=batch)
        return float(loss), opt.params

    loss_id, p_id = run(None)
    loss_bf, p_bf = run("bf16")
    assert abs(loss_bf - loss_id) < 0.05 * max(abs(loss_id), 1e-3)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-3
        ),
        p_id, p_bf,
    )
    # ...and the narrowing REALLY happened: bf16 rounding on the wire
    # must leave a trace (bit-identical params would mean the fused path
    # silently skipped the cast — the regression this guards against)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(p_id), jax.tree.leaves(p_bf))
    )


def test_bf16_codec_halves_async_wire():
    """On the async host wire (CodecWire) the bf16 codec halves payload
    bytes — the DCN-bandwidth configuration."""
    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.dcn import CodecWire

    template = {"w": np.zeros((128, 4), np.float32), "b": np.zeros(8, np.float32)}
    wire = CodecWire(get_codec("bf16"), template)
    assert wire.raw_bytes == (128 * 4 + 8) * 4
    assert wire.wire_bytes == wire.raw_bytes // 2
    grads = {"w": np.random.RandomState(0).randn(128, 4).astype(np.float32),
             "b": np.random.RandomState(1).randn(8).astype(np.float32)}
    blob = wire.encode_to_bytes(grads)
    assert len(blob) == wire.wire_bytes
    out = wire.decode_from_bytes(blob)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-2, atol=1e-2
        ),
        grads, out,
    )


def test_qsgd_levels_bounded():
    with pytest.raises(ValueError):
        QSGDCodec(levels=200)  # would overflow the int8 payload


# -- blocktopk: selection without a global sort -----

def test_blocktopk_keeps_each_blocks_largest():
    from pytorch_ps_mpi_tpu.codecs import BlockTopKCodec

    code = BlockTopKCodec(fraction=1 / 128, block_size=128)
    g = grad((512,), seed=3)
    out = roundtrip(code, g)
    # per 128-block, exactly the largest-|.| entry survives
    gb = np.asarray(g).reshape(4, 128)
    ob = np.asarray(out).reshape(4, 128)
    for b in range(4):
        j = np.abs(gb[b]).argmax()
        assert ob[b][j] == gb[b][j]
        assert (ob[b] != 0).sum() == 1


def test_blocktopk_wire_matches_topk_format_and_bits():
    from pytorch_ps_mpi_tpu.codecs import BlockTopKCodec, TopKCodec

    n = 4096
    bt = BlockTopKCodec(fraction=0.01, block_size=1024)
    tk = TopKCodec(fraction=0.01)
    g = grad((n,), seed=4)
    pb, _ = bt.encode(g, bt.init_state(g.shape, g.dtype))
    pt, _ = tk.encode(g, tk.init_state(g.shape, g.dtype))
    # same payload keys/dtypes; blockwise k = nb * round(B*f) ≈ global k
    assert set(pb) == set(pt) == {"values", "indices"}
    assert pb["indices"].dtype == jnp.int32
    assert pb["values"].shape == (4 * 10,)
    assert bt.payload_bits(g.shape, g.dtype) == 40 * (32 + 32)


def test_blocktopk_selects_most_of_global_topk_mass():
    """Gradient noise spreads large entries across blocks: block-local
    selection must recover most of the global top-k L2 mass."""
    from pytorch_ps_mpi_tpu.codecs import BlockTopKCodec, TopKCodec

    n = 1 << 16
    g = grad((n,), seed=5)
    f = 0.01
    bt = roundtrip(BlockTopKCodec(fraction=f, block_size=1024), g)
    tk = roundtrip(TopKCodec(fraction=f), g)
    mass = lambda x: float(jnp.sum(x * x))
    assert mass(bt) > 0.75 * mass(tk)


def test_blocktopk_ragged_tail_pads_and_drops():
    """n not a multiple of block_size: the padded tail must neither be
    selected over real entries nor corrupt the scatter (mode='drop')."""
    from pytorch_ps_mpi_tpu.codecs import BlockTopKCodec

    code = BlockTopKCodec(fraction=2 / 128, block_size=128)
    n = 300  # blocks of 128,128,44(+84 pad)
    g = jnp.ones((n,)) * 0.01
    g = g.at[290].set(5.0).at[299].set(-4.0)  # tail block's largest
    out = roundtrip(code, g)
    assert float(out[290]) == 5.0
    assert float(out[299]) == -4.0
    assert out.shape == (n,)
    # decode_sum over 2 stacked workers: same drop discipline
    st = code.init_state(g.shape, g.dtype)
    p, _ = code.encode(g, st)
    stacked = jax.tree.map(lambda x: jnp.stack([x, x]), p)
    s = code.decode_sum(stacked, g.shape, g.dtype)
    assert float(s[290]) == 10.0


def test_blocktopk_single_block_falls_back_to_topk():
    from pytorch_ps_mpi_tpu.codecs import BlockTopKCodec, TopKCodec

    g = grad((128,), seed=6)
    bt = roundtrip(BlockTopKCodec(fraction=0.1, block_size=1024), g)
    tk = roundtrip(TopKCodec(fraction=0.1), g)
    np.testing.assert_array_equal(np.asarray(bt), np.asarray(tk))


def test_blocktopk_validation():
    from pytorch_ps_mpi_tpu.codecs import BlockTopKCodec

    with pytest.raises(ValueError):
        BlockTopKCodec(fraction=0.01, block_size=100)  # not lane-aligned
    with pytest.raises(ValueError):
        BlockTopKCodec(fraction=0.0)


def test_blocktopk_payload_bits_counts_emitted_pairs():
    """Ragged tail + high fraction: encode emits nb*block_k pairs (pad
    picks included, dropped at scatter) and payload_bits must count ALL
    of them — under-reporting would skew every wire-size metric."""
    from pytorch_ps_mpi_tpu.codecs import BlockTopKCodec

    code = BlockTopKCodec(fraction=0.9, block_size=128)
    g = grad((300,), seed=7)
    p, _ = code.encode(g, code.init_state(g.shape, g.dtype))
    emitted = int(p["values"].shape[0])
    assert emitted == 3 * round(128 * 0.9)  # > n=300
    assert code._k_for(g.shape) == emitted
    assert code.payload_bits(g.shape, g.dtype) == emitted * 64
    # and the decode still reconstructs only real coordinates
    out = code.decode(p, g.shape, g.dtype)
    assert out.shape == g.shape


def test_blocktopk8_quantized_sparse_roundtrip_and_wire():
    """Compressed-sparse: survivors match blocktopk's selection with
    int8 precision (error <= scale/2 per block), at 40 bits/survivor."""
    from pytorch_ps_mpi_tpu.codecs import BlockTopK8Codec, BlockTopKCodec

    n = 4096
    g = grad((n,), seed=8)
    c8 = BlockTopK8Codec(fraction=0.01, block_size=1024)
    cf = BlockTopKCodec(fraction=0.01, block_size=1024)
    out8 = roundtrip(c8, g)
    outf = roundtrip(cf, g)
    # same support
    np.testing.assert_array_equal(np.asarray(out8 != 0), np.asarray(outf != 0))
    # values within the per-block quantization step
    p, _ = c8.encode(g, c8.init_state(g.shape, g.dtype))
    max_step = float(p["scale"].max())
    err = np.abs(np.asarray(out8) - np.asarray(outf)).max()
    assert err <= max_step / 2 + 1e-7
    # wire: 4 blocks x 10 survivors x 40 bits + 4 scales
    assert c8.payload_bits(g.shape, g.dtype) == 40 * 40 + 4 * 32
    assert c8.payload_bits(g.shape, g.dtype) < cf.payload_bits(g.shape, g.dtype)


def test_blocktopk8_decode_sum_and_single_block():
    from pytorch_ps_mpi_tpu.codecs import BlockTopK8Codec

    c8 = BlockTopK8Codec(fraction=0.1, block_size=128)
    # single block (n <= block_size): quantized plain top-k
    g = grad((96,), seed=9)
    out = roundtrip(c8, g)
    assert int(np.count_nonzero(np.asarray(out))) == round(96 * 0.1)
    assert c8.payload_bits(g.shape, g.dtype) == round(96 * 0.1) * 40 + 32
    # stacked decode_sum == sum of decodes
    g2 = grad((512,), seed=10)
    st = c8.init_state(g2.shape, g2.dtype)
    p1, _ = c8.encode(g2, st)
    p2, _ = c8.encode(-g2, st)
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), p1, p2)
    s = c8.decode_sum(stacked, g2.shape, g2.dtype)
    ref = c8.decode(p1, g2.shape, g2.dtype) + c8.decode(p2, g2.shape, g2.dtype)
    np.testing.assert_allclose(np.asarray(s), np.asarray(ref), rtol=1e-6)


def test_every_codec_handles_local_shard_shapes():
    """Model-parallel contract: under MPI_PS(param_specs=...) codecs
    encode LOCAL shard gradients whose shapes carry the leading
    [1]-shard axis ([1, d, f/tp] for TP leaves, [e_loc, d, f] for EP) —
    every registered codec must init/encode/decode_sum at such shapes
    without assuming 2-D or flat inputs, and identity-class codecs must
    stay exact."""
    from pytorch_ps_mpi_tpu.codecs.base import _REGISTRY

    shapes = [(1, 8, 16), (2, 8, 16)]
    kw = {
        "ef": {"inner_name": "topk", "fraction": 0.5},
        "powersgd": {"rank": 2, "min_compression_elems": 4},
        "sign": {"use_pallas": False},
        "topk": {"fraction": 0.5},
        "blocktopk": {"fraction": 0.5, "block_size": 128},
        "blocktopk8": {"fraction": 0.5, "block_size": 128},
        "randomk": {"fraction": 0.5},
        "qsgd": {"levels": 16},
        "threshold": {"tau": 0.5, "max_fraction": 0.9},
    }
    for name in sorted(_REGISTRY):
        code = get_codec(name, **kw.get(name, {}))
        for shape in shapes:
            g = jax.random.normal(jax.random.key(7), shape, jnp.float32)
            st = code.init_state(shape, jnp.float32)
            rng = jax.random.key(1) if code.needs_rng else None
            payload, _ = code.encode(g, st, rng)
            stacked = jax.tree.map(lambda x: jnp.stack([x, x]), payload)
            out = code.decode_sum(stacked, shape, jnp.float32)
            assert out.shape == shape, (name, shape, out.shape)
            assert bool(jnp.all(jnp.isfinite(out))), (name, shape)
            if name in ("identity", "bf16", "f16"):
                np.testing.assert_allclose(
                    np.asarray(out), 2 * np.asarray(g, np.float32),
                    rtol=1e-2, atol=1e-3, err_msg=f"{name}@{shape}",
                )
            assert int(code.payload_bits(shape, jnp.float32)) > 0
