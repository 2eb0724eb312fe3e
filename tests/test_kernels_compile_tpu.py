"""Every ``ops/`` Pallas kernel compiles for a TPU v5e — without a chip.

jax + libtpu can compile for a *described* topology:
``get_topology_desc("v5e:2x2")`` yields TPU v5 lite devices, and lowering
against a ``ShapeDtypeStruct`` placed on one of them runs the real TPU
compiler, Mosaic included. The CPU suite otherwise only ever runs the
kernels in interpret mode, which is how ``tern_pack`` carried a cast
Mosaic rejects for as long as it existed. Each kernel module's
``_interpret`` is patched to ``False`` and every compiled program must
contain a ``tpu_custom_call`` — a shape-dispatch to the jnp path cannot
pass as the kernel. Execution and numerics on the chip are
``chip_smoke.py`` phase (c).
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pytorch_ps_mpi_tpu.ops import (
    attention_pallas,
    hyper_connection,
    moe_rows_pallas,
    quant_pallas,
    sign_pallas,
    tern_pallas,
    topk_pallas,
)

M1 = 1 << 20
RAGGED = 1000 * 1024  # rows that no kernel's block size divides


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert len(topo.devices) == 4, topo.devices
    return topo.devices


@pytest.fixture(scope="module")
def v5e(v5e_2x2):
    dev = v5e_2x2[0]
    assert dev.device_kind == "TPU v5 lite", dev.device_kind
    return dev


@pytest.fixture(autouse=True)
def mosaic_not_interpret(monkeypatch):
    for mod in (attention_pallas, hyper_connection, moe_rows_pallas,
                quant_pallas, sign_pallas, tern_pallas, topk_pallas):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    # the hyper-connections' passes are jitted: no trace made in interpret
    # mode may answer here, and none made here may answer a later test
    jax.clear_caches()
    yield
    jax.clear_caches()


def compile_for(dev, fn, *avals):
    """AOT-compile ``fn`` for ``dev``; avals are (shape, dtype) pairs."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=SingleDeviceSharding(dev))
            for s, d in avals]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


@pytest.mark.parametrize("n", [M1, RAGGED])
def test_sign_kernels_compile(v5e, n):
    compile_for(v5e, sign_pallas.pack_signs, ((n,), jnp.float32))
    compile_for(v5e, sign_pallas.encode_signs, ((n,), jnp.float32))
    compile_for(v5e, sign_pallas.unpack_signs, ((n // 8,), jnp.uint8))


@pytest.mark.parametrize("n", [M1, RAGGED])
def test_quant_kernels_compile(v5e, n):
    # the undecorated functions: the module-level jit would hand back a
    # trace an earlier test made in interpret mode
    compile_for(v5e, quant_pallas.quantize_int8.__wrapped__,
                ((n,), jnp.float32))
    compile_for(v5e, quant_pallas.dequantize_int8.__wrapped__,
                ((n,), jnp.int8), ((), jnp.float32))


def test_tern_kernels_compile(v5e):
    compile_for(v5e, tern_pallas.tern_pack, ((M1,), jnp.float32),
                ((M1,), jnp.uint32), ((), jnp.float32))
    compile_for(v5e, tern_pallas.tern_unpack, ((M1 // 4,), jnp.uint8),
                ((), jnp.float32))


def test_exact_topk_compiles(v5e):
    fn = functools.partial(topk_pallas.exact_topk.__wrapped__,
                           k=M1 // 100, chunk=2048)
    compile_for(v5e, fn, ((M1,), jnp.float32))


@pytest.mark.parametrize("seq,dtype,d,causal", [
    (512, jnp.bfloat16, 64, True),     # 512x512: a head is one grid step
    (512, jnp.bfloat16, 64, False),    # bert-base.mlm512's kernels
    (512, jnp.float32, 128, True),     # the same tile at 4 bytes, 128 wide
    (768, jnp.bfloat16, 64, False),    # 256x256: the largest that divides
    (1024, jnp.bfloat16, 64, True),    # 1024x1024 tiles swept in 512x512
    (1024, jnp.float32, 128, True),
    (1536, jnp.bfloat16, 64, False),   # 512x512: the k target degrades
    (96, jnp.float32, 64, True),       # 32x32: a sub-tile under 128 lanes
])
def test_flash_forward_and_backward_compile(v5e, seq, dtype, d, causal):
    def loss(q, k, v):
        out = attention_pallas.flash_attention(q, k, v, causal=causal)
        return jnp.sum(out.astype(jnp.float32))

    qkv = ((2, seq, 4, d), dtype)
    text = compile_for(v5e, jax.grad(loss, (0, 1, 2)), qkv, qkv, qkv)
    # the forward kernel and the ONE backward kernel (dq, dk, dv)
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("half, heads, kv_heads, d", [
    (4096, 32, 4, 128),    # sdar-30b-a3b.bd4k: 8,192 positions, 1024x1024 tiles
    (512, 4, 2, 128),      # one q tile and one k tile a half
])
def test_block_diffusion_kernels_compile(v5e, half, heads, kv_heads, d):
    """The masked forward and backward kernels with grouped heads (dk and
    dv of a key-value head resident across its eight query heads' sweeps)
    at the cell's widths, each under its own name."""
    def loss(q, k, v):
        out = attention_pallas.flash_attention(
            q, k, v, mask="block_diffusion", block=4, half=half)
        return jnp.sum(out.astype(jnp.float32))

    text = compile_for(v5e, jax.grad(loss, (0, 1, 2)),
                       ((1, 2 * half, heads, d), jnp.bfloat16),
                       ((1, 2 * half, kv_heads, d), jnp.bfloat16),
                       ((1, 2 * half, kv_heads, d), jnp.bfloat16))
    for name in ("flash_bd_fwd", "flash_bd_dqkv"):
        assert name in text, name
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("mask, names", [
    (dict(mask="window", window=512), "flash_win"),   # 512x512 tiles, band of 2
    (dict(causal=True), "flash_wide"),                # 1024x1024 swept in 512x512
    (dict(mask="window", window=512, block_q=1024, block_k=1024), "flash_win"),
])
def test_differential_attention_kernels_compile(v5e, mask, names):
    """phi4-mini-flash.lm8k: 40 softmax maps over 20 of 8,192 x 64 with the
    128-wide joined value, under the window and the causal mask, each
    kernel under its own name."""
    def loss(q, k, v):
        out = attention_pallas.flash_attention(q, k, v, **mask)
        return jnp.sum(out.astype(jnp.float32))

    text = compile_for(v5e, jax.grad(loss, (0, 1, 2)),
                       ((1, 8192, 40, 64), jnp.bfloat16),
                       ((1, 8192, 20, 64), jnp.bfloat16),
                       ((1, 8192, 20, 128), jnp.bfloat16))
    for which in ("fwd", "dqkv"):
        assert f"{names}_{which}" in text, which


@pytest.mark.parametrize("pad", [0, 64], ids=["192", "padded_to_256"])
def test_latent_attention_kernels_compile(v5e, pad):
    """xing4-29b-a4b.lm4k: 32 heads of 4,096 positions, causal, q and k
    192 wide (128 without and 64 with the rotary embedding: one and a
    half lane tiles) over a 128-wide value; and the same with q and k
    zero-padded to 256, the K run's second candidate."""
    def loss(q, k, v):
        if pad:
            q, k = (jnp.pad(x, ((0, 0),) * 3 + ((0, pad),)) for x in (q, k))
        out = attention_pallas.flash_attention(q, k, v, causal=True,
                                               scale=192 ** -0.5)
        return jnp.sum(out.astype(jnp.float32))

    text = compile_for(v5e, jax.grad(loss, (0, 1, 2)),
                       ((1, 4096, 32, 192), jnp.bfloat16),
                       ((1, 4096, 32, 192), jnp.bfloat16),
                       ((1, 4096, 32, 128), jnp.bfloat16))
    for which in ("fwd", "dqkv"):
        assert f"flash_wide_{which}" in text, which


def test_latent_attention_kernels_compile_at_8k(v5e):
    """joyai-llm-flash.lm8k: the causal ``flash_wide_*`` kernels at 32 heads
    of 8,192 positions (twice ``lm4k``'s rows), q and k 192 wide over a
    128-wide value, bf16: the forward and the one backward kernel, and the
    gradients come out at the inputs' shapes. The largest cell: 10.5 MB of
    float32 dk and dv resident a head, over Mosaic's default of 16 MiB
    with their output blocks and the tile's, so the call states its own
    ``vmem_limit_bytes`` (and the forward, which needs none, states
    none)."""
    def loss(q, k, v):
        out = attention_pallas.flash_attention(q, k, v, causal=True,
                                               scale=192 ** -0.5)
        return jnp.sum(out.astype(jnp.float32))

    dev = SingleDeviceSharding(v5e)
    shapes = [(1, 8192, 32, 192), (1, 8192, 32, 192), (1, 8192, 32, 128)]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=dev)
            for s in shapes]
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(*args).compile()
    text = compiled.as_text()
    for which in ("fwd", "dqkv"):
        assert f"flash_wide_{which}" in text, which
    assert [a.shape for a in compiled.out_info] == shapes
    # (the scoped memory a call may use, what Mosaic used of it)
    vmem = {name: (int(limit), int(used)) for name, limit, used in re.findall(
        r'%\S*flash_wide_(fwd|dqkv)\S* = .*?"scoped_memory_configs":\[\{[^}]*'
        r'"size":"(\d+)".*?"used_scoped_memory_configs":\[\{[^}]*'
        r'"size":"(\d+)"', text)}
    assert vmem["fwd"][0] == 16 << 20 and vmem["fwd"][1] < 16 << 20
    assert vmem["dqkv"][0] == attention_pallas._VMEM_BYTES
    assert 16 << 20 < vmem["dqkv"][1] < attention_pallas._VMEM_BYTES
    # the shape rule counts more than Mosaic uses: it errs towards the pair
    assert vmem["dqkv"][1] < 8192 * (256 + 128) * 8 + (12 << 20)


def test_causal_grouped_kernels_compile_at_the_cells_shape(v5e):
    """lfm2-24b-a2b.lm8kx2: 2 rows of 8,192 positions, 32 query over 8
    key-value heads of 64, causal. Every part was there (grouped heads
    under the block-diffusion mask at 128 wide, the causal sweep ungrouped
    or with unequal widths); the combination is compiled here first: the
    forward and the one backward kernel, and dk/dv come out over the 8
    key-value heads."""
    def loss(q, k, v):
        out = attention_pallas.flash_attention(q, k, v, mask="causal")
        return jnp.sum(out.astype(jnp.float32))

    dev = SingleDeviceSharding(v5e)
    args = [jax.ShapeDtypeStruct((2, 8192, h, 64), jnp.bfloat16, sharding=dev)
            for h in (32, 8, 8)]
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(*args).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2
    assert [a.shape for a in compiled.out_info] == [
        (2, 8192, 32, 64), (2, 8192, 8, 64), (2, 8192, 8, 64)]


def _kernel_events(text):
    """The Pallas custom calls of an optimized program as a device trace
    names them: ``chipbench.trace_reduce.short`` of the instruction."""
    from chipbench.trace_reduce import PALLAS_TAG, short

    names = [short(ln.strip().removeprefix("ROOT ")) for ln in
             text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert names and all(n.endswith(PALLAS_TAG) for n in names), names
    return names


@pytest.mark.parametrize("kw, widths, reader, names", [
    (dict(mask="block_diffusion", block=4, half=1024), (128, 128),
     "scope_time.BD_KERNELS", ["flash_bd_fwd", "flash_bd_dqkv"]),
    (dict(mask="window", window=512), (64, 128),
     "sambay_trace.DIFF_KERNELS", ["flash_win_fwd", "flash_win_dqkv"]),
    (dict(causal=True), (64, 128),
     "sambay_trace.DIFF_KERNELS", ["flash_wide_fwd", "flash_wide_dqkv"]),
    (dict(causal=True, scale=192 ** -0.5), (192, 128),
     "xing_trace.MLA_KERNELS", ["flash_wide_fwd", "flash_wide_dqkv"]),
], ids=["bd", "win", "wide", "mla"])
def test_the_benchmarks_readers_find_the_one_backward_kernel(v5e, kw, widths,
                                                             reader, names):
    """``attn.bd_kernel_ms``, ``attn.diff_kernel_ms`` and
    ``attn.mla_kernel_ms`` sum the events their regular expressions match:
    ``flash_<kind>_dqkv`` matches as ``dq`` + ``kv``. Under a layer's
    checkpoint, as the four ``remat`` cells run the kernels. A kernel the
    readers missed would make a roofline read over 100 %."""
    import importlib

    from pytorch_ps_mpi_tpu.ops._common import checkpoint_layer

    module, _, pattern = reader.partition(".")
    rx = re.compile(getattr(importlib.import_module(f"chipbench.{module}"),
                            pattern))

    @checkpoint_layer
    def layer(q, k, v):
        return attention_pallas.flash_attention(q * 2, k, v, **kw)

    def loss(q, k, v):
        return jnp.sum(layer(q, k, v).astype(jnp.float32))

    d, dv = widths
    text = compile_for(v5e, jax.grad(loss, (0, 1, 2)),
                       ((1, 2048, 4, d), jnp.bfloat16),
                       ((1, 2048, 2, d), jnp.bfloat16),
                       ((1, 2048, 2, dv), jnp.bfloat16))
    events = _kernel_events(text)
    assert len(events) == 2 and all(rx.match(e) for e in events), events
    assert [n in e.split(" = ")[0] for n, e in zip(names, events)] == [
        True, True], events


def test_the_unnamed_kernels_keep_the_names_their_readers_match(v5e):
    """The causal and unmasked kernels of one width carry no name of their
    own: under ``models/bert.py``'s attention module XLA calls both
    ``%SelfAttention_0.<n>`` (``trace_reduce.ATTENTION_KERNEL``:
    ``attn.kernel_ms`` and ``attn.roofline_pct`` in ``lm1024`` and
    ``mlm512``), and under the scope ``attn.gqa`` their ``op_name`` carries
    it (``lfm2_trace.kernel_seconds`` goes by the scope table)."""
    from chipbench.trace_reduce import ATTENTION_KERNEL
    from pytorch_ps_mpi_tpu.models.bert import BertConfig, EncoderLayer

    cfg = BertConfig.tiny(attention="flash", hidden_size=256, num_heads=4,
                          dtype=jnp.bfloat16)
    layer = EncoderLayer(cfg)
    x = jax.ShapeDtypeStruct((2, 512, 256), jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=SingleDeviceSharding(v5e)),
        jax.eval_shape(layer.init, jax.random.key(0), x))
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(layer.apply(p, x).astype(
        jnp.float32)))).lower(params, x).compile().as_text()
    events = _kernel_events(text)
    assert len(events) == 2, events
    assert all(re.match(ATTENTION_KERNEL, e) for e in events), events

    def gqa(q, k, v):
        with jax.named_scope("attn.gqa"):
            out = attention_pallas.flash_attention(q, k, v, mask="causal")
        return jnp.sum(out.astype(jnp.float32))

    text = compile_for(v5e, jax.grad(gqa, (0, 1, 2)),
                       ((1, 2048, 8, 64), jnp.bfloat16),
                       ((1, 2048, 2, 64), jnp.bfloat16),
                       ((1, 2048, 2, 64), jnp.bfloat16))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 2
    for ln in calls:
        (op_name,) = re.findall(r'op_name="([^"]*)"', ln)
        assert re.search(r"(^|[/(])attn\.gqa([/)]|$)", op_name), op_name


def test_selective_scan_compiles_at_the_cells_size(v5e):
    """Forward and backward of the chunked scan at T 8,192, E 5,120, N 16
    (XLA's loops, no kernel): the state of every step never exists at
    once — the program's temporaries stay far under the 2.7 GB of
    ``[T, E, N]``."""
    from pytorch_ps_mpi_tpu.ops.selective_scan import selective_scan

    steps, width, n = 8192, 5120, 16
    dev = SingleDeviceSharding(v5e)
    sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=dev)
    compiled = jax.jit(jax.grad(
        lambda *z: jnp.sum(selective_scan(*z)), range(6))).lower(
        sds((1, steps, width), jnp.bfloat16), sds((1, steps, width)),
        sds((width, n)), sds((1, steps, n)), sds((1, steps, n)),
        sds((width,))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def test_grouped_products_compile_to_kernels(v5e):
    """``jax.lax.ragged_dot`` forward and both transposes at the cell's
    expert widths: XLA:TPU's own Mosaic kernels, no dense fallback."""
    from pytorch_ps_mpi_tpu.parallel import dropless

    def loss(x, gate, up, down, sizes):
        return jnp.sum(dropless.swiglu_experts(
            x, sizes, gate, up, down).astype(jnp.float32))

    text = compile_for(v5e, jax.grad(loss, (0, 1, 2, 3)),
                       ((4096, 2048), jnp.bfloat16),
                       ((16, 2048, 768), jnp.bfloat16),
                       ((16, 2048, 768), jnp.bfloat16),
                       ((16, 768, 2048), jnp.bfloat16), ((16,), jnp.int32))
    assert text.count("%ragged-dot") >= 9      # 3 matrices x 3 passes


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("positions, rows, width, experts", [
    (16384, 65536, 2048, 16),       # sdar-30b-a3b.bd4k
    (4096, 16384, 3584, 8),         # xing4-29b-a4b.lm4k
], ids=["bd4k", "lm4k"])
def test_the_sum_back_compiles_at_the_cells_shapes(v5e, positions, rows,
                                                   width, experts, dtype):
    """``sum_rows`` out of both expert cells' buffers: one Mosaic kernel
    (2-byte rows as halves of 32-bit words, 4-byte rows as they are), and
    no copy of the buffer in front of it: it reads the tiles where they
    are."""
    block = moe_rows_pallas.block_rows(width)

    def back(y, to, runs):
        return moe_rows_pallas.sum_rows(y, to, runs, positions, block)

    text = compile_for(v5e, back, ((rows, width), dtype),
                       ((rows,), jnp.int32),
                       ((positions // block + 1, experts), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "moe_sum_rows" in text
    assert f"[{rows},{width}]" not in "".join(
        ln.split("(")[0] for ln in text.splitlines() if " copy(" in ln)


HC_CELL = (4, 4096, 3584)       # xing4-29b-a4b.lm4k: n, b s, d
HC_CFG = (20, 1e-6, (-30.0, 30.0), 1e-6)


def hc_avals(dtype):
    """(the parameters' and the streams' avals, the tile) of one
    hyper-connection at the cell's shape."""
    n, positions, d = HC_CELL
    p = jax.eval_shape(lambda k: hyper_connection.init(k, n, d),
                       jax.random.key(0))
    tile = hyper_connection.tile(
        jax.ShapeDtypeStruct((n, 1, positions, d), dtype))
    return p, ((n, positions, d), dtype), tile


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kernel", ["hc_pre_fwd", "hc_post_fwd",
                                    "hc_post_bwd", "hc_pre_bwd"])
def test_hyper_connection_kernels_compile_at_the_cells_shape(v5e, kernel,
                                                             dtype):
    """Each of the four passes over ``[4, 1, 4096, 3584]`` alone: one
    Mosaic kernel under its own name, its tile's streams, double-buffered,
    and its scratch inside the VMEM it asks for (the compiler says so
    where they are not)."""
    hc = hyper_connection
    p, x, tile = hc_avals(dtype)
    assert tile == (hc.TILE if dtype == jnp.bfloat16 else 128)
    y, small = ((x[0][1:]), dtype), ((x[0][1], 128), jnp.float32)
    sds = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=SingleDeviceSharding(v5e)), tree)
    fn, avals = {
        "hc_pre_fwd": (lambda p, x: hc._pre_fwd(x, p, tile, HC_CFG), [x]),
        "hc_post_fwd": (lambda p, x, y, c: hc._post_fwd(x, y, c, tile),
                        [x, y, small]),
        "hc_post_bwd": (lambda p, x, y, c, g: hc._post_bwd(
            tile, (x, y, c), g)[1:], [x, y, small, x]),
        "hc_pre_bwd": (lambda p, x, c, z, du, dc, g: hc._pre_bwd(
            x, p, c, z, tile, HC_CFG, du, dc, g),
            [x, small, small, y, small, x]),
    }[kernel]
    text = jax.jit(fn).lower(sds(p), *(jax.ShapeDtypeStruct(
        s, d, sharding=SingleDeviceSharding(v5e)) for s, d in avals)
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert kernel in text


def test_every_hyper_connection_kernel_sits_under_its_scope(v5e):
    """``jax.grad`` of a layer of two hyper-connected sub-layers under
    ``remat`` at the cell's shape: seven kernels (the gradient of a sum
    needs no forward value, so what is left of the forward passes is the
    recomputation: the first pair and the second's first half; then the
    two backward pairs), and the ``op_name`` of each carries ``hc.mix``
    — what ``hc.mix_ms`` and ``hc.mix_roofline_pct`` find their time
    by."""
    hc = hyper_connection
    p, _, _ = hc_avals(jnp.bfloat16)
    n, positions, d = HC_CELL

    @jax.checkpoint
    def layer(x, p):
        for _ in range(2):
            x, _ = hc.connect(x, p, jnp.tanh, iters=20, eps=1e-6,
                              clamp=(-30.0, 30.0), norm_eps=1e-6, tag="mtp.")
        return x

    def loss(x, p):
        return jnp.sum(layer(x, p).astype(jnp.float32))

    on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=SingleDeviceSharding(v5e))
    text = jax.jit(jax.grad(loss, (0, 1))).lower(
        on(jax.ShapeDtypeStruct((n, 1, positions, d), jnp.bfloat16)),
        jax.tree.map(on, p)).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    for ln in calls:
        (op_name,) = re.findall(r'op_name="([^"]*)"', ln)
        assert re.search(r"(^|[/(])mtp\.hc\.(mix|sinkhorn)([/)]|$)", op_name), \
            op_name
    names = [re.match(r"\s*(?:ROOT )?%([a-z_]+)", ln)[1] for ln in calls]
    assert {name: names.count(name) for name in set(names)} == {
        "hc_pre_fwd": 2, "hc_post_fwd": 1, "hc_post_bwd": 2,
        "hc_pre_bwd": 2}, names


def test_vmem_overflow_is_a_compile_error(v5e):
    """Negative control: the compiler really runs — tiles that cannot
    fit VMEM fail here instead of compiling to something else. Since the
    kernels sweep a tile in sub-tiles the scores no longer count (2048 x
    4096 fits); the resident q, k, v and o blocks alone must overflow."""
    def fwd(q, k, v):
        return attention_pallas.flash_attention(
            q, k, v, causal=True, block_q=8192, block_k=8192)

    qkv = ((1, 8192, 2, 128), jnp.float32)
    with pytest.raises(Exception, match="(?i)vmem|RESOURCE_EXHAUSTED"):
        compile_for(v5e, fwd, qkv, qkv, qkv)


@pytest.mark.parametrize("chips,asynchronous", [(1, False), (4, True)])
def test_fused_step_compiles_with_the_overlapping_schedule(v5e_2x2, chips,
                                                           asynchronous):
    """The fused step of ``MPI_PS`` for described chips: on four, the
    TPU compiler accepts every name ``comms.async_allreduce_options``
    passes (an unknown one is a compile error) and each matrix's
    all-reduce becomes an asynchronous pair; on one, no option is passed
    and no collective is left. The optimizer is built on this backend's
    devices (its state has to live somewhere) and handed the described
    mesh before its step is traced."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_ps_mpi_tpu import MPI_PS, comms
    from pytorch_ps_mpi_tpu.mesh import make_mesh

    def loss_fn(p, batch):
        return jnp.mean((jnp.tanh(batch["x"] @ p["w1"]) @ p["w2"] + p["b"]
                         - batch["y"]) ** 2)

    params = {"w1": jnp.zeros((512, 1024)), "w2": jnp.zeros((1024, 512)),
              "b": jnp.zeros((512,))}
    opt = MPI_PS(params, optim="adam", average=True, lr=1e-3,
                 mesh=make_mesh(devices=jax.devices()[:chips]))
    opt.mesh = mesh = make_mesh(devices=v5e_2x2[:chips])
    assert (comms.async_allreduce_options(mesh, ("data",)) is not None
            ) == asynchronous

    def on(tree, spec):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)), tree)

    batch = {"x": jnp.zeros((32, 512)), "y": jnp.zeros((32, 512))}
    text = opt._build_grad_step(loss_fn).lower(
        on(opt.params, P()), on(opt.opt_state, P()),
        on(opt.codec_state, P("data")), on(batch, P("data")),
        on(jax.random.key(0), P())).compile().as_text()
    counts = comms.count_scheduled_collectives(text)
    if asynchronous:
        assert counts["async_collectives"] == 2, counts
        assert counts["collectives"] == 3, counts
    else:
        assert counts == {"collectives": 0, "async_collectives": 0}
