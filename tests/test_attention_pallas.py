"""Flash-attention Pallas kernel: oracle equality
for forward, gradients, logsumexp, dynamic offsets, and the ring
integration — all in interpret mode on the CPU mesh (the same kernel
lowers through Mosaic on TPU; bench captures the perf side)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_ps_mpi_tpu.ops.attention_pallas import (
    _attention_jnp,
    flash_attention,
    flash_supported,
)


def qkv(b=2, l=64, h=2, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (b, l, h, d)) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense_oracle(causal):
    q, k, v = qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref, _ = _attention_jnp(q, k, v, 0, 0, causal, q.shape[-1] ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gradients_match_dense_oracle():
    q, k, v = qkv(l=32, d=8)
    sc = q.shape[-1] ** -0.5

    def lf(q, k, v):
        o, lse = flash_attention(q, k, v, causal=True, return_lse=True,
                                 block_q=8, block_k=8)
        # the lse term exercises the lse-cotangent path ring needs
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def lr(q, k, v):
        o, lse = _attention_jnp(q, k, v, 0, 0, True, sc)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    np.testing.assert_allclose(float(lf(q, k, v)), float(lr(q, k, v)),
                               rtol=1e-6)
    gf = jax.grad(lf, (0, 1, 2))(q, k, v)
    gr = jax.grad(lr, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_lse_is_logsumexp():
    q, k, v = qkv(l=32, d=8)
    sc = q.shape[-1] ** -0.5
    _, lse = flash_attention(q, k, v, return_lse=True, block_q=8, block_k=8)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sc
    ref = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_global_offsets_and_fully_masked_block():
    b, h, d = 1, 2, 8
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (b, 16, h, d))
    k = jax.random.normal(ks[1], (b, 32, h, d))
    v = jax.random.normal(ks[2], (b, 32, h, d))
    out = flash_attention(q, k, v, causal=True, q_offset=jnp.int32(16),
                          k_offset=jnp.int32(0), block_q=8, block_k=8)
    ref, _ = _attention_jnp(q, k, v, 16, 0, True, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # block entirely in the masked future: zero output, floor lse
    o, lse = flash_attention(q, k, v, causal=True, q_offset=jnp.int32(0),
                             k_offset=jnp.int32(100), return_lse=True,
                             block_q=8, block_k=8)
    assert float(jnp.abs(o).max()) == 0.0
    assert float(lse.max()) < -1e29


def test_untileable_shapes_fall_back_to_jnp():
    q, k, v = qkv(l=37)  # 37 has no power-of-two tiling >= 8
    assert not flash_supported(37, 37)
    out = flash_attention(q, k, v, causal=True)
    ref, _ = _attention_jnp(q, k, v, 0, 0, True, q.shape[-1] ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_blocks_match_dense(mesh8, causal):
    """Ring attention with flash per-block compute == dense attention
    over the gathered sequence (the existing ring oracle, now through
    the kernel + lse combine)."""
    from pytorch_ps_mpi_tpu.parallel.ring import ring_attention

    b, l, h, d = 2, 64, 2, 8  # 8 shards of 8 query rows
    ks = jax.random.split(jax.random.key(5), 3)
    q, k, v = (jax.random.normal(kk, (b, l, h, d)) for kk in ks)
    ref, _ = _attention_jnp(q, k, v, 0, 0, causal, d ** -0.5)

    def spmd(q, k, v):
        return ring_attention(q, k, v, "data", causal=causal,
                              use_flash=True)

    out = jax.jit(
        jax.shard_map(
            spmd, mesh=mesh8,
            in_specs=(P(None, "data"), P(None, "data"), P(None, "data")),
            out_specs=P(None, "data"), check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_ring_flash_gradients_flow(mesh8):
    """Training through flash-block ring attention: gradients exist and
    match the jnp-block ring path."""
    from pytorch_ps_mpi_tpu.parallel.ring import ring_attention

    b, l, h, d = 1, 32, 2, 8
    ks = jax.random.split(jax.random.key(6), 3)
    q, k, v = (jax.random.normal(kk, (b, l, h, d)) for kk in ks)

    def make_loss(use_flash):
        def spmd(q, k, v):
            o = ring_attention(q, k, v, "data", causal=True,
                               use_flash=use_flash)
            return jax.lax.psum(jnp.sum(o ** 2), "data")

        return jax.shard_map(
            spmd, mesh=mesh8,
            in_specs=(P(None, "data"),) * 3, out_specs=P(),
            check_vma=False,
        )

    lf, lj = make_loss(True), make_loss(False)
    gf = jax.grad(lambda *a: jnp.sum(lf(*a)), (0, 1, 2))(q, k, v)
    gj = jax.grad(lambda *a: jnp.sum(lj(*a)), (0, 1, 2))(q, k, v)
    for a, bb in zip(gf, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-5)


def test_bert_flash_mode_matches_full(mesh8):
    """BertMLM(attention='flash') == attention='full' logits."""
    from pytorch_ps_mpi_tpu.models import BertConfig, BertMLM

    cfg_full = BertConfig.tiny()
    cfg_flash = BertConfig.tiny(attention="flash")
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 1024)
    params = BertMLM(cfg_full).init(jax.random.key(1), tokens)
    a = BertMLM(cfg_full).apply(params, tokens)
    b = BertMLM(cfg_flash).apply(params, tokens)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_flash_local_attention_matches_dense(mesh8, causal):
    """Ulysses with the flash kernel as its post-exchange local
    attention == dense attention over the gathered sequence, gradients
    included (Ulysses' whole pitch is reusing the fused kernel)."""
    from pytorch_ps_mpi_tpu.parallel.ulysses import ulysses_attention

    b, l, h, d = 2, 64, 8, 8  # heads divide the 8-way axis
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(kk, (b, l, h, d)) for kk in ks)
    ref, _ = _attention_jnp(q, k, v, 0, 0, causal, d ** -0.5)

    def spmd(q, k, v):
        return ulysses_attention(q, k, v, "data", causal=causal,
                                 use_flash=True)

    mapped = jax.jit(
        jax.shard_map(
            spmd, mesh=mesh8,
            in_specs=(P(None, "data"),) * 3, out_specs=P(None, "data"),
            check_vma=False,
        )
    )
    out = mapped(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    # gradients through the kernel + both all_to_alls
    gf = jax.grad(lambda *a: jnp.sum(mapped(*a) ** 2), (0, 1, 2))(q, k, v)
    gj = jax.grad(
        lambda q, k, v: jnp.sum(
            _attention_jnp(q, k, v, 0, 0, causal, d ** -0.5)[0] ** 2
        ),
        (0, 1, 2),
    )(q, k, v)
    for a, bb in zip(gf, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-5)


def test_flash_auto_gate_requires_min_seq(monkeypatch):
    """'full'-attention auto-dispatch on TPU: below FLASH_MIN_SEQ the
    gate refuses (XLA's dense attention suits short sequences); from it
    up the gate passes iff shapes tile. Nothing is compiled to decide."""
    from pytorch_ps_mpi_tpu.ops import attention_pallas as ap

    monkeypatch.setattr(ap.jax, "default_backend", lambda: "tpu")
    # pin the floor: the env knob (FLASH_MIN_SEQ) may hold an untileable
    # value in a tuning run, which would break the tiling asserts below
    monkeypatch.setattr(ap, "FLASH_MIN_SEQ", 512)
    floor = ap.FLASH_MIN_SEQ
    assert not ap.flash_auto_ok(floor // 2, floor // 2, jnp.bfloat16)
    assert ap.flash_auto_ok(floor, floor, jnp.bfloat16)
    # the floor tests the LONGER side (ring blocks can be asymmetric)
    assert ap.flash_auto_ok(floor, floor // 4, jnp.bfloat16)
    # an untileable length is still refused above the floor
    assert not ap.flash_auto_ok(floor + 1, floor + 1, jnp.bfloat16)


@pytest.mark.parametrize("causal", [False, True])
def test_multi_tile_backward_both_masks_odd_heads(causal):
    """Multi-tile (4x4 grid) BACKWARD at causal=False and with a
    non-power-of-two head count — the two cells the other tests leave
    open: test_gradients_match_dense_oracle sweeps the multi-tile
    backward only causally, and every test uses power-of-two heads
    (the flattened batch*heads dim here is 6)."""
    q, k, v = qkv(b=2, l=64, h=3, d=16, seed=5)
    sc = q.shape[-1] ** -0.5

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal,
                              block_q=16, block_k=16)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def ref_loss(q, k, v):
        out, _ = _attention_jnp(q, k, v, 0, 0, causal, sc)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    gf = jax.grad(flash_loss, (0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_default_block_targets_tiers():
    """Tile policy as measured on the chip (PRs 30 and 32). Grid tiles:
    512x1024 unmasked, 1024x1024 causal or block-diffusion, at every
    length: under 1024 ``_pick_block`` clamps them to the largest power
    of two that divides the length, so a head of 512 is one grid step.
    Sub-tiles: 512x512 under a mask, the tile itself without one."""
    from pytorch_ps_mpi_tpu.ops.attention_pallas import (
        _bd_block_targets, _default_block_targets, _min_block_for,
        _pick_block, _sub_tile_targets, _window_block_targets)

    assert _default_block_targets(False) == (512, 1024)
    assert _default_block_targets(True) == (1024, 1024)
    assert _bd_block_targets() == (1024, 1024)
    assert _window_block_targets() == (512, 512)
    assert _sub_tile_targets(("causal",), 1024, 1024) == (512, 512)
    assert _sub_tile_targets(("bd", 4, 4096), 1024, 1024) == (512, 512)
    assert _sub_tile_targets(("none",), 512, 1024) == (512, 1024)
    assert _sub_tile_targets(("none",), 512, 512) == (512, 512)

    # divisibility degradation: targets cap, never break tiling
    mb = _min_block_for(jnp.float32)
    assert _pick_block(1536, 512, mb) == 512   # 1536 = 3*512
    assert _pick_block(1536, 1024, mb) == 512  # largest pow2 divisor
    assert _pick_block(1280, 512, mb) == 256   # 1280 = 5*256
    assert _pick_block(96, 128, mb) == 32
    assert _pick_block(512, 1024, mb) == 512   # a short head: the whole of it


# -- the grid tile under sequence 1024 (PR 32) ---------------------------------

def _spec_id(spec):
    return "-".join(str(x) for x in spec)


@pytest.mark.parametrize("spec", [("none",), ("causal",), ("window", 128),
                                  ("bd", 4, 512)], ids=_spec_id)
def test_a_head_of_512_is_one_grid_step(spec):
    """At 512 positions (a half of 512 under the block-diffusion mask) the
    grid tile is the whole head, its own 512 x 512 sub-tile: one grid step
    a head a kernel where 128 x 128 paid sixteen. Unmasked, the cell
    ``bert-base.mlm512``'s shape, that one sub-tile is FULL: a visit is
    straight-line code with no mask."""
    from pytorch_ps_mpi_tpu.ops.attention_pallas import flash_tiles

    length = 1024 if spec[0] == "bd" else 512
    plan = flash_tiles(spec, length, length, jnp.bfloat16)
    assert {k: plan[k] for k in ("block_q", "block_k", "sub_q", "sub_k")} == {
        "block_q": 512, "block_k": 512, "sub_q": 512, "sub_k": 512}
    assert plan["dead"] + plan["cut"] + plan["full"] == (length // 512) ** 2
    if spec[0] == "none":
        assert plan == {"mask": "none", "block_q": 512, "block_k": 512,
                        "sub_q": 512, "sub_k": 512, "dead": 0, "cut": 0,
                        "full": 1}
    if spec[0] == "causal":         # the diagonal passes through the one tile
        assert (plan["dead"], plan["cut"], plan["full"]) == (0, 1, 0)


def test_the_recorder_row_at_mlm512s_shape():
    """The counter of the mechanism: traced at ``bert-base.mlm512``'s head
    (512 x 64, bf16, unmasked) with the recorder on, ``flash_attention``
    writes one ``attn.flash_tiles`` row that reads 512 / 512 / 512 / 512
    and ``full`` 1, and since PR 40 which backward runs: ``fused``, with
    the 262,144 bytes of one head's float32 dk and dv resident."""
    from pytorch_ps_mpi_tpu import telemetry

    q = jnp.zeros((1, 512, 1, 64), jnp.bfloat16)
    rec = telemetry.configure()
    try:
        jax.eval_shape(lambda q: flash_attention(q, q, q), q)
        rows = [e["attrs"] for e in rec.events()
                if e["name"] == "attn.flash_tiles"]
    finally:
        telemetry.disable()
    assert rows == [{"mask": "none", "block_q": 512, "block_k": 512,
                     "sub_q": 512, "sub_k": 512, "dead": 0, "cut": 0,
                     "full": 1, "backward": "fused",
                     "resident_bytes": 512 * (64 + 64) * 4}]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("lq, lk, want", [
    (256, 256, (256, 256)), (384, 384, (128, 128)), (768, 768, (256, 256)),
    (512, 256, (512, 256)),     # ring's and ulysses' blocks may differ
    (640, 640, (128, 128)), (96, 96, (32, 32)),
])
def test_short_lengths_take_tiles_that_divide_them(lq, lk, want, causal):
    """Under 1024 the targets are clamped to the largest power of two
    that divides each length, whatever the mask."""
    from pytorch_ps_mpi_tpu.ops.attention_pallas import flash_tiles

    plan = flash_tiles(("causal",) if causal else ("none",), lq, lk,
                       jnp.bfloat16)
    assert (plan["block_q"], plan["block_k"]) == want
    assert lq % plan["block_q"] == 0 and lk % plan["block_k"] == 0
    assert plan["block_q"] % plan["sub_q"] == 0
    assert plan["block_k"] % plan["sub_k"] == 0


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("length", [256, 512])
def test_default_tile_matches_dense_at_short_lengths(length, causal):
    """Forward, logsumexp and both gradients at the DEFAULT tile of the
    tier under 1024 (the whole head at 256 and 512: one visit where the
    accumulation over k took two or four) against the dense oracle, in
    bf16 as the cell runs it."""
    ks = jax.random.split(jax.random.key(length + causal), 4)
    q, k, v, w = (jax.random.normal(kk, (1, length, 2, 16), jnp.bfloat16)
                  for kk in ks)

    def run(attend):
        def total(q, k, v):
            o, lse = attend(q, k, v)
            return (jnp.sum(w.astype(jnp.float32) * o)
                    + jnp.sum(jnp.sin(lse))), (o, lse)

        return jax.jit(jax.value_and_grad(total, (0, 1, 2),
                                          has_aux=True))(q, k, v)

    (_, (o, lse)), got = run(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, return_lse=True))
    (_, (o_ref, lse_ref)), want = run(lambda q, k, v: _attention_jnp(
        q, k, v, 0, 0, causal, q.shape[-1] ** -0.5))
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    np.testing.assert_allclose(f32(o), f32(o_ref), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=1e-4, atol=1e-4)
    for g, wnt in zip(got, want):
        np.testing.assert_allclose(f32(g), f32(wnt), rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("spec, length, dtype, want", [
    # gpt2-small.lm1024
    (("causal",), 1024, jnp.bfloat16, dict(
        block_q=1024, block_k=1024, sub_q=512, sub_k=512, dead=1, cut=2,
        full=1)),
    # sdar-30b-a3b.bd4k
    (("bd", 4, 4096), 8192, jnp.bfloat16, dict(
        block_q=1024, block_k=1024, sub_q=512, sub_k=512, dead=176, cut=24,
        full=56)),
    # phi4-mini-flash.lm8k: the two T x T layers, the window layer
    (("causal",), 8192, jnp.bfloat16, dict(
        block_q=1024, block_k=1024, sub_q=512, sub_k=512, dead=120, cut=16,
        full=120)),
    (("window", 512), 8192, jnp.bfloat16, dict(
        block_q=512, block_k=512, sub_q=512, sub_k=512, dead=225, cut=31,
        full=0)),
    # xing4-29b-a4b.lm4k (PR 33): latent attention's 192-wide q and k over
    # a 128-wide value take the causal plan as it is (the widths are not
    # the plan's matter)
    (("causal",), 4096, jnp.bfloat16, dict(
        block_q=1024, block_k=1024, sub_q=512, sub_k=512, dead=28, cut=8,
        full=28)),
    # unmasked from 1024 up (no cell): the tile is its own sub-tile
    (("none",), 1024, jnp.bfloat16, dict(
        block_q=512, block_k=1024, sub_q=512, sub_k=1024, dead=0, cut=0,
        full=2)),
], ids=lambda x: _spec_id(x) if isinstance(x, tuple) else None)
def test_the_other_cells_plans_are_the_parents(spec, length, dtype, want):
    """From 1024 up nothing moved with PR 32: the plans at the shapes of
    ``gpt2-small.lm1024``, ``sdar-30b-a3b.bd4k`` and
    ``phi4-mini-flash.lm8k`` are what the parent commit computed."""
    from pytorch_ps_mpi_tpu.ops.attention_pallas import flash_tiles

    assert flash_tiles(spec, length, length, dtype) == dict(
        mask=spec[0], **want)


def test_flash_auto_ok_false_off_tpu():
    """Off-TPU the kernel would run interpreted: the auto gate returns
    False at every tier (dense path everywhere)."""
    from pytorch_ps_mpi_tpu.ops.attention_pallas import flash_auto_ok

    assert jax.default_backend() != "tpu"
    assert not flash_auto_ok(512, 512, jnp.bfloat16)
    assert not flash_auto_ok(2048, 2048, jnp.bfloat16)
    assert not flash_auto_ok(8192, 8192, jnp.float32)


# -- masks beyond causal, and grouped heads (PR 27) ---------------------------

def grouped_qkv(l, heads, kv_heads, d=16, b=2, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (b, l, heads, d)),
            jax.random.normal(ks[1], (b, l, kv_heads, d)),
            jax.random.normal(ks[2], (b, l, kv_heads, d)),
            jax.random.normal(ks[3], (b, l, heads, d)))


def dense_block_diffusion(q, k, v, half, block):
    """The dense mask written out position by position, softmax and
    all: nothing of the kernel module but its layout."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    ok = np.zeros((2 * half, 2 * half), bool)
    for i in range(2 * half):
        for j in range(2 * half):
            bi, bj = (i % half) // block, (j % half) // block
            if i < half:
                ok[i, j] = (bi == bj) if j < half else (bj < bi)
            else:
                ok[i, j] = j >= half and bj <= bi
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("half, block, heads, kv_heads, bq, bk", [
    (32, 4, 8, 1, 8, 16),      # 32-over-4-style grouping: 8 query heads a kv head
    (32, 4, 4, 2, 16, 8),
    (64, 8, 2, 2, 16, 64),     # equal head counts, one k tile a half
    (64, 4, 4, 2, 32, 16),
])
def test_block_diffusion_kernels_match_dense(half, block, heads, kv_heads,
                                             bq, bk):
    """Forward, dq and dk/dv kernels under the block-diffusion mask, with
    grouped heads, tile skipping and the dead tiles' index clamp, against
    a dense mask built position by position."""
    q, k, v, w = grouped_qkv(2 * half, heads, kv_heads)

    def kernel(q, k, v):
        return jnp.sum(w * flash_attention(
            q, k, v, mask="block_diffusion", block=block, half=half,
            block_q=bq, block_k=bk))

    def dense(q, k, v):
        return jnp.sum(w * dense_block_diffusion(q, k, v, half, block))

    got, got_grads = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
    want, want_grads = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, wnt in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wnt),
                                   rtol=2e-4, atol=2e-5)


def test_block_diffusion_allows_a_quarter_of_the_pairs():
    from pytorch_ps_mpi_tpu.ops.attention_pallas import allowed_pairs

    half, block = 64, 4
    pos = jnp.arange(2 * half)
    ok = allowed_pairs(("bd", block, half), pos, pos)
    assert int(ok.sum()) == half * half + half * block
    assert not bool(ok[half:, :half].any())      # clean never sees noised


@pytest.mark.parametrize("causal", [False, True])
def test_grouped_heads_under_the_old_masks(causal):
    q, k, v, w = grouped_qkv(64, 6, 2)
    sc = q.shape[-1] ** -0.5

    def kernel(q, k, v):
        return jnp.sum(w * flash_attention(q, k, v, causal=causal,
                                           block_q=16, block_k=32))

    def dense(q, k, v):
        return jnp.sum(w * _attention_jnp(q, k, v, 0, 0, causal, sc)[0])

    got, want = (jax.grad(f, (0, 1, 2))(q, k, v) for f in (kernel, dense))
    for g, wnt in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wnt),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("kw, match", [
    (dict(mask="block_diffusion", block=3, half=32), "power of"),
    (dict(mask="block_diffusion", block=4, half=16), "whole doubled"),
    (dict(mask="band"), "unknown mask"),
    (dict(mask="block_diffusion", block=4, half=32, causal=True), "contradicts"),
])
def test_mask_arguments_are_checked(kw, match):
    q, k, v, _ = grouped_qkv(64, 2, 2)
    with pytest.raises(ValueError, match=match):
        flash_attention(q, k, v, **kw)


# -- the sub-tile sweep inside a grid step (PR 30) -----------------------------

@pytest.mark.parametrize("mask, lq, lk, offsets", [
    (("none",), 64, 64, [(0, 0)]),
    (("causal",), 64, 96, [(0, 0), (16, 0), (24, 8), (0, 200), (40, 0)]),
    (("bd", 4, 32), 64, 64, [(0, 0)]),
    (("bd", 16, 64), 128, 128, [(0, 0)]),   # sub-tiles smaller than a block
])
def test_tile_classes_match_the_dense_mask(mask, lq, lk, offsets):
    """``_tile_full`` iff every pair of the sub-tile is allowed and
    ``_tile_live`` iff any is, at every sub-tile position and size, in
    global coordinates; ``tile_census`` counts the same classes."""
    from pytorch_ps_mpi_tpu.ops.attention_pallas import (
        _tile_full, _tile_live, allowed_pairs, tile_census)

    for q_off, k_off in offsets:
        ok = np.asarray(allowed_pairs(mask, q_off + jnp.arange(lq),
                                      k_off + jnp.arange(lk)))
        for sq, sk in [(8, 8), (8, 16), (16, 8), (32, 32), (16, 32)]:
            seen = {"dead": 0, "cut": 0, "full": 0}
            for i in range(0, lq, sq):
                for j in range(0, lk, sk):
                    sub = ok[i:i + sq, j:j + sk]
                    live = bool(_tile_live(mask, q_off + i, k_off + j, sq, sk))
                    full = bool(_tile_full(mask, q_off + i, k_off + j, sq, sk))
                    assert live == sub.any(), (q_off, k_off, sq, sk, i, j)
                    assert full == sub.all(), (q_off, k_off, sq, sk, i, j)
                    seen["full" if full else "cut" if live else "dead"] += 1
            if (q_off, k_off) == (0, 0):
                assert tile_census(mask, lq, lk, 32, 32, sq, sk) == seen


def _sweep_case(mask, heads, kv_heads, q_off=None, k_off=None):
    return pytest.param(mask, heads, kv_heads, q_off, k_off,
                        id=f"{mask[0]}-{heads}over{kv_heads}-{q_off}-{k_off}")


@pytest.mark.parametrize("mask, heads, kv_heads, q_off, k_off", [
    _sweep_case(("none",), 4, 4), _sweep_case(("none",), 4, 2),
    _sweep_case(("causal",), 4, 4), _sweep_case(("causal",), 4, 2),
    _sweep_case(("causal",), 4, 4, 24, 8), _sweep_case(("causal",), 4, 2, 24, 8),
    # a block wholly in the future: nothing allowed, zero output, floor lse
    _sweep_case(("causal",), 4, 4, 0, 100), _sweep_case(("causal",), 4, 2, 0, 100),
    _sweep_case(("bd", 4, 32), 4, 4), _sweep_case(("bd", 4, 32), 4, 2),
])
def test_sub_tile_sweep_matches_dense(monkeypatch, mask, heads, kv_heads,
                                      q_off, k_off):
    """Forward, logsumexp and the three gradients (with a cotangent on the
    logsumexp) of the three kernels sweeping 32 x 32 grid tiles in 8 x 8
    sub-tiles, so that one grid tile holds dead, cut and full sub-tiles,
    against the dense oracle; the offsets arrive traced."""
    from pytorch_ps_mpi_tpu.ops import attention_pallas as ap

    monkeypatch.setattr(ap, "_sub_tile_targets", lambda *a: (8, 8))
    census = ap.tile_census(mask, 64, 64, 32, 32, 8, 8)
    if mask[0] != "none":
        assert min(census.values()) > 0, census
    q, k, v, w = grouped_qkv(64, heads, kv_heads)
    kw = dict(block_q=32, block_k=32, return_lse=True)
    if mask[0] == "bd":
        kw.update(mask="block_diffusion", block=mask[1], half=mask[2])
    else:
        kw.update(causal=mask[0] == "causal")

    def kernel(q, k, v, q_off, k_off):
        off = {} if q_off is None else dict(q_offset=q_off, k_offset=k_off)
        o, lse = flash_attention(q, k, v, **kw, **off)
        return jnp.sum(w * o) + jnp.sum(jnp.sin(lse)), (o, lse)

    def dense(q, k, v, q_off, k_off):
        o, lse = _attention_jnp(q, k, v, 0 if q_off is None else q_off,
                                0 if k_off is None else k_off, mask,
                                q.shape[-1] ** -0.5)
        return jnp.sum(w * o) + jnp.sum(jnp.sin(lse)), (o, lse)

    offs = (None, None) if q_off is None else (jnp.int32(q_off),
                                               jnp.int32(k_off))
    (_, (o, lse)), got = jax.jit(jax.value_and_grad(
        kernel, (0, 1, 2), has_aux=True))(q, k, v, *offs)
    (_, (o_ref, lse_ref)), want = jax.value_and_grad(
        dense, (0, 1, 2), has_aux=True)(q, k, v, *offs)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=1e-5, atol=1e-5)
    for g, wnt in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wnt),
                                   rtol=2e-4, atol=2e-5)
    if k_off == 100:
        assert float(jnp.abs(o).max()) == 0.0 and float(lse.max()) < -1e29


def test_tile_census_of_the_cells_and_its_recorder_row():
    """The census of the two cells that run the kernels, at 256 x 256
    sub-tiles (a head; before the sweep every sub-tile of a live grid
    tile was computed and masked: 16 and 384), and one ``attn.flash_tiles``
    row each time ``flash_attention`` is traced: in the set-up log while
    the recorder is off, which a recorder takes over as it is installed."""
    from pytorch_ps_mpi_tpu import telemetry
    from pytorch_ps_mpi_tpu.ops.attention_pallas import tile_census

    assert tile_census(("causal",), 1024, 1024, 512, 1024, 256, 256) == {
        "dead": 6, "cut": 4, "full": 6}
    assert tile_census(("bd", 4, 4096), 8192, 8192, 1024, 1024, 256, 256) == {
        "dead": 736, "cut": 48, "full": 240}
    assert tile_census(("none",), 1024, 1024, 512, 1024, 256, 256) == {
        "dead": 0, "cut": 0, "full": 16}
    with pytest.raises(ValueError, match="do not tile"):
        tile_census(("causal",), 1024, 1024, 512, 1024, 384, 256)

    q, k, v = qkv(l=64)
    fn = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32))
    fn(q, k, v)                       # traced with the recorder off
    assert [e["attrs"]["block_k"] for e in telemetry.setup_rows()
            if e["name"] == "attn.flash_tiles"] == [32]
    rec = telemetry.configure()
    try:
        fn2 = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=32, block_k=64))
        fn2(q, k, v)
        fn2(q, k, v)                  # the second call traces nothing
        rows = [e for e in rec.events() if e["name"] == "attn.flash_tiles"]
    finally:
        telemetry.disable()
    assert len(rows) == 2 and rows[0]["attrs"]["block_k"] == 32
    assert rows[1]["attrs"] == {"mask": "causal", "block_q": 32,
                                "block_k": 64, "sub_q": 32, "sub_k": 64,
                                "dead": 0, "cut": 2, "full": 0,
                                "backward": "fused",
                                "resident_bytes": 64 * (16 + 16) * 4}


# -- one backward kernel a layer (PR 40) ----------------------------------------

def _flash_kw(mask, **kw):
    """``flash_attention``'s arguments for a mask spec."""
    if mask[0] == "bd":
        return dict(kw, mask="block_diffusion", block=mask[1], half=mask[2])
    if mask[0] == "window":
        return dict(kw, mask="window", window=mask[1])
    return dict(kw, causal=mask[0] == "causal")


def _backward_and_oracle(monkeypatch, mask, q, k, v, w, offsets=(None, None),
                         **kw):
    """The gradients of ``sum(w * out) + sum(sin(lse))`` (so the logsumexp
    carries a cotangent that is not zero) by the kernels and by the dense
    oracle; the offsets arrive traced. Sub-tiles of 8 x 8: a grid tile
    holds dead, cut and full ones."""
    from pytorch_ps_mpi_tpu.ops import attention_pallas as ap

    monkeypatch.setattr(ap, "_sub_tile_targets", lambda *a: (8, 8))
    kw = _flash_kw(mask, return_lse=True, **kw)
    given = {} if offsets[0] is None else dict(
        zip(("q_offset", "k_offset"), map(jnp.int32, offsets)))

    def total(attend):
        def f(q, k, v, given):
            o, lse = attend(q, k, v, given)
            return jnp.sum(w * o) + jnp.sum(jnp.sin(lse))
        return jax.jit(jax.grad(f, (0, 1, 2)))(q, k, v, given)

    got = total(lambda q, k, v, given: flash_attention(q, k, v, **kw, **given))
    want = total(lambda q, k, v, given: _attention_jnp(
        q, k, v, given.get("q_offset", 0), given.get("k_offset", 0), mask,
        q.shape[-1] ** -0.5))
    for name, g, wnt in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wnt),
                                   rtol=2e-4, atol=3e-5, err_msg=name)
    return got


@pytest.mark.parametrize("dv", [16, 32, 8], ids=["equal", "wider", "narrower"])
@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("mask", [("none",), ("causal",), ("window", 24),
                                  ("bd", 4, 32)], ids=_spec_id)
def test_the_one_backward_kernel_matches_the_oracle(monkeypatch, mask, group,
                                                    dv):
    """dq, dk and dv of the ONE backward kernel against the dense oracle's,
    over the masks, grouped heads (dk and dv stay resident across the
    group's sweeps) and a value as wide as, wider and narrower than the
    keys. (Before the dq and dk/dv kernels were deleted, every case here,
    the offset cases below and the window cases of ``test_flash_window.py``
    also read bit-equal to that pair, interpreted: a row receives its
    terms in the order the pair gave them.)"""
    ks = jax.random.split(jax.random.key(group + dv), 4)
    q = jax.random.normal(ks[0], (1, 64, 8, 16))
    k = jax.random.normal(ks[1], (1, 64, 8 // group, 16))
    v = jax.random.normal(ks[2], (1, 64, 8 // group, dv))
    w = jax.random.normal(ks[3], (1, 64, 8, dv))
    _backward_and_oracle(monkeypatch, mask, q, k, v, w, block_q=32,
                         block_k=32)


@pytest.mark.parametrize("lq, lk, bq, bk, q_off, k_off", [
    (32, 64, 16, 32, 24, 8),      # ring's call: a block against a longer one
    (64, 32, 32, 16, 40, 0),
    (32, 64, 32, 32, 16, 0),      # one q tile: nothing of dq is carried
    (32, 64, 16, 32, 0, 100),     # wholly in the future: every gradient zero
])
@pytest.mark.parametrize("group", [1, 4])
def test_the_one_backward_kernel_under_traced_offsets(
        monkeypatch, group, lq, lk, bq, bk, q_off, k_off):
    """Ring attention's call: traced offsets, q and k blocks of unequal
    length (the resident dk and dv are ``lk`` rows, whatever ``lq``)."""
    ks = jax.random.split(jax.random.key(lq + k_off), 4)
    q = jax.random.normal(ks[0], (2, lq, 4, 16))
    k = jax.random.normal(ks[1], (2, lk, 4 // group, 16))
    v = jax.random.normal(ks[2], (2, lk, 4 // group, 32))
    w = jax.random.normal(ks[3], (2, lq, 4, 32))
    got = _backward_and_oracle(monkeypatch, ("causal",), q, k, v, w,
                               (q_off, k_off), block_q=bq, block_k=bk)
    if k_off == 100:
        assert all(float(jnp.abs(g).max()) == 0.0 for g in got)


def test_the_resident_head_its_row_and_its_limit(monkeypatch):
    """The gradient's jaxpr holds ONE backward kernel with three outputs;
    the ``attn.flash_tiles`` row says so and with how many bytes of dk and
    dv resident; every cell's head fits the VMEM the call asks for, and a
    head that does not is refused when the backward is traced, not by
    Mosaic and not in the forward pass."""
    from pytorch_ps_mpi_tpu import telemetry
    from pytorch_ps_mpi_tpu.ops import attention_pallas as ap

    # the cells (lk, d, dv; bf16, tiles of 1024 swept in 512): resident
    # bytes as the row gives them, and the whole call's count under the limit
    for lk, d, dv, want in [(8192, 192, 128, 10_485_760),     # joyai
                            (8192, 128, 128, 8_388_608),      # bd4k
                            (4096, 192, 128, 5_242_880),      # lm4k
                            (8192, 64, 64, 4_194_304),        # lm8kx2
                            (8192, 64, 128, 6_291_456),       # lm8k
                            (1024, 64, 64, 524_288), (512, 64, 64, 262_144)]:
        plan = ap.flash_tiles(("causal",), lk, lk, jnp.bfloat16, d=d, dv=dv)
        assert (plan["backward"], plan["resident_bytes"]) == ("fused", want)
        held = ap._backward_vmem(lk, d, dv, jnp.bfloat16, 1024, 1024, 512, 512)
        assert want < held < 40 << 20 < ap._VMEM_BYTES
    # ring's long blocks are what can pass it
    assert ap._backward_vmem(65536, 128, 128, jnp.bfloat16, 1024, 1024, 512,
                             512) > ap._VMEM_BYTES
    # a caller that gives no widths (the benchmark's window reader) gets
    # the plan it always got
    assert "backward" not in ap.flash_tiles(("causal",), 1024, 1024,
                                            jnp.bfloat16)

    q, k, v = qkv(l=64)
    fn = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32) ** 2), (0, 1, 2))
    calls = [e for e in jax.make_jaxpr(fn)(q, k, v).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert [len(e.outvars) for e in calls] == [2, 3]    # forward; dq, dk, dv
    (row,) = [e["attrs"] for e in telemetry.setup_rows()
              if e["name"] == "attn.flash_tiles"]
    assert (row["backward"], row["resident_bytes"]) == (
        "fused", 64 * (16 + 16) * 4)

    monkeypatch.setattr(ap, "_VMEM_BYTES", 60_000)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert out.shape == q.shape                         # no backward, no limit
    with pytest.raises(ValueError, match="dk and dv of one key-value head"):
        fn(q, k, v)
