"""The hyper-connections' kernel mover against the ``jax.numpy`` one
(``ops/hyper_connection.py``): the same weights, ``u`` and new streams,
and the same gradient with respect to the streams, the sub-layer's other
input and all nine parameters, at widths that tile (interpreted here);
shapes that do not tile take ``jax.numpy`` and the ``hc.plan`` row says
which mover ran."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu import telemetry
from pytorch_ps_mpi_tpu.ops import hyper_connection as hc

N = 4
KW = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)
CFG = tuple(KW.values())        # the passes' static argument, in this order
LEAVES = sorted(hc.init(jax.random.key(0), N, 128))
# float32 to rounding in another order of sums; bf16 to the dtype
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def case(dtype, d, rows=1):
    """Two position tiles of streams, a second input of the sub-layer,
    parameters with open gates, and a weight for a scalar of the new
    streams."""
    positions = 2 * hc.TILE // rows
    k = jax.random.split(jax.random.key(0), 4)
    p = hc.init(k[0], N, d, gate=0.3, bias=1.0)
    x = jax.random.normal(k[1], (N, rows, positions, d)).astype(dtype)
    y = jax.random.normal(k[2], (rows, positions, d)).astype(dtype)
    return p, x, y, jax.random.normal(k[3], x.shape)


def sub_layer(u, y):
    return jnp.tanh(u.astype(jnp.float32)).astype(u.dtype) * y


def by_jnp(x, y, p, weigh, tag=""):
    h_pre, h_post, h_res = hc.mixing_weights(x, p, tag=tag, **KW)
    u = hc.width_mix(x, h_pre, tag)
    out = hc.depth_mix(x, sub_layer(u, y), h_res, h_post, tag)
    return jnp.sum(out.astype(jnp.float32) * weigh)


def by_connect(x, y, p, weigh, tag=""):
    out, more = hc.connect(x, p, lambda u: sub_layer(u, y), tag=tag, **KW)
    assert more is None
    return jnp.sum(out.astype(jnp.float32) * weigh)


def off(a, b):
    """The largest difference, as a share of the largest entry."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30))


@pytest.mark.parametrize("d", [256, 384])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_kernels_forward_is_the_jnp_functions(dtype, d):
    p, x, y, _ = case(dtype, d)
    positions = hc.tile(x)
    assert positions == hc.TILE and x.shape[2] == 2 * positions
    flat = x.reshape(N, -1, d)
    u, c, z = hc._pre_fwd(flat, p, positions, CFG)
    out = hc._post_fwd(flat, y.reshape(-1, d), c, positions)
    h_pre, h_post, h_res = hc.mixing_weights(x, p, **KW)
    mine = hc.weights_of(c, x.shape)
    for got, want in zip(mine, (h_pre, h_post, h_res)):
        assert got.shape == want.shape and got.dtype == jnp.float32
        # the weights are float32 under either mover; bf16 streams round
        # the product's inputs the same way
        assert off(got, want) < 2e-5
    assert off(u.reshape(y.shape), hc.width_mix(x, h_pre)) < TOL[dtype]
    assert off(out.reshape(x.shape),
               hc.depth_mix(x, y, h_res, h_post)) < TOL[dtype]
    assert u.dtype == out.dtype == dtype
    assert np.abs(np.sum(mine[2], axis=0) - 1).max() < 1e-4


@pytest.mark.parametrize("leaf", ["streams", "y"] + LEAVES)
@pytest.mark.parametrize("dtype, d", [(jnp.float32, 256), (jnp.bfloat16, 384)],
                         ids=["f32", "bf16"])
def test_the_kernels_gradient_is_jax_own_of_the_jnp_functions(dtype, d, leaf,
                                                              gradients):
    mine, theirs = gradients(dtype, d)
    assert mine[leaf].shape == theirs[leaf].shape
    assert mine[leaf].dtype == theirs[leaf].dtype
    assert np.isfinite(np.asarray(mine[leaf], np.float32)).all()
    assert off(mine[leaf], theirs[leaf]) < TOL[dtype], leaf


@pytest.fixture(scope="module")
def gradients():
    """(value and gradients by ``connect``, by the ``jax.numpy``
    functions) a (dtype, d), computed once for all of a case's leaves."""
    done = {}

    def both(dtype, d):
        if (dtype, d) not in done:
            p, x, y, weigh = case(dtype, d)
            assert hc.tile(x)
            out = []
            for fn in (by_connect, by_jnp):
                value, (dx, dy, dp) = jax.jit(jax.value_and_grad(
                    fn, (0, 1, 2)))(x, y, p, weigh)
                out.append(dict(dp, streams=dx, y=dy, value=value))
            assert off(out[0]["value"], out[1]["value"]) < TOL[dtype]
            done[dtype, d] = out
        return done[dtype, d]

    return both


@pytest.mark.parametrize("how", ["checkpoint", "mtp", "two_rows"])
def test_connect_under_remat_with_the_modules_tag_and_over_rows(how):
    """``jax.checkpoint`` runs the forward kernels again in the backward
    pass; ``tag='mtp.'`` is the prediction module's path; two rows of
    positions are one run of ``b s`` positions to the kernels."""
    p, x, y, weigh = case(jnp.float32, 256, rows=2 if how == "two_rows" else 1)
    tag = "mtp." if how == "mtp" else ""
    wrap = jax.checkpoint if how == "checkpoint" else (lambda f: f)
    grads = [jax.jit(jax.grad(wrap(lambda x, y, p: fn(x, y, p, weigh, tag)),
                              (0, 1, 2)))(x, y, p)
             for fn in (by_connect, by_jnp)]
    for mine, theirs in zip(*map(jax.tree.leaves, grads)):
        assert off(mine, theirs) < TOL[jnp.float32]
    text = jax.jit(jax.grad(lambda x: by_connect(x, y, p, weigh, tag))
                   ).lower(x).as_text(debug_info=True)
    assert tag + "hc.mix" in text


def plan_of(x):
    hc.record_plan(x, 20, 10)     # conftest starts every test with no rows
    (row,) = [r for r in telemetry.setup_rows() if r["name"] == "hc.plan"]
    return row["attrs"]


@pytest.mark.parametrize("shape, dtype, why", [
    ((N, 2, 16, 32), jnp.float32, "a width under a lane tile"),
    ((N, 1, 2 * hc.TILE, 192), jnp.bfloat16, "a width of one and a half"),
    ((N, 1, 100, 256), jnp.float32, "positions no tile divides"),
    ((9, 1, 2 * hc.TILE, 256), jnp.float32, "more streams than a group"),
    ((N, 1, 2 * hc.TILE, 256), jnp.float16, "a dtype the chip does not add"),
    ((N, 1, 3 * 128, 256), jnp.bfloat16, None),
])
def test_a_shape_that_does_not_tile_takes_jnp_and_the_plan_says_so(shape,
                                                                   dtype, why):
    x = jnp.zeros(shape, dtype)
    row = plan_of(x)
    if why is None:       # these tile: 128 positions where 256 do not divide
        assert row["mover"] == "kernel"
        assert row["tile"] == hc.tile(x) and shape[1] * shape[2] % row["tile"] == 0
        return
    assert hc.tile(x) is None, why
    assert (row["mover"], row["tile"]) == ("jnp", 0)
    if shape[0] == N:
        # and connect runs there: no kernel in the program
        p = hc.init(jax.random.key(0), N, shape[-1])
        text = jax.jit(lambda x: hc.connect(x, p, lambda u: u, **KW)[0]
                       ).lower(x).as_text()
        assert "hc_pre_fwd" not in text and "pallas" not in text


def test_the_plan_row_of_the_cells_shape_names_the_kernel():
    row = plan_of(jax.ShapeDtypeStruct((N, 1, 4096, 3584), jnp.bfloat16))
    assert row == {"streams": 4, "iterations": 20, "sub_layers": 10,
                   "stream_bytes": 4 * 4096 * 3584 * 2, "mover": "kernel",
                   "tile": hc.TILE}


def test_pack_puts_every_weight_in_its_lane_and_unpacks_by_its_transpose():
    p = hc.init(jax.random.key(1), N, 128, gate=0.5)
    w, ab = hc._pack(p, N)
    base = hc._base(N)
    assert w.shape == (N, 128, 128) and ab.shape == (8, 128)
    assert np.array_equal(w[..., :N], p["w_pre"])
    assert np.array_equal(w[..., N:2 * N], p["w_post"])
    for i in range(N):
        lanes = slice(base + 8 * i, base + 8 * i + N)
        assert np.array_equal(w[..., lanes], p["w_res"][..., N * i:N * (i + 1)])
        assert np.array_equal(ab[1, lanes], p["b_res"][i])
    used = np.zeros(128, bool)
    used[:2 * N] = True
    for i in range(N):
        used[base + 8 * i:base + 8 * i + N] = True
    assert not np.asarray(w)[..., ~used].any()
    assert np.array_equal(np.asarray(ab[0]) != 0, used)
    # linear: the transpose of packing ones counts each entry once
    _, unpack = jax.vjp(lambda p: hc._pack(p, N), p)
    (back,) = unpack((jnp.ones_like(w), jnp.ones_like(ab)))
    assert float(back["a_res"]) == N * N and float(back["a_pre"]) == N
    assert np.array_equal(back["w_res"], np.ones_like(p["w_res"]))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_a_model_whose_width_tiles_trains_through_the_kernels(remat,
                                                              monkeypatch):
    """``models/xing.py`` at a hidden size of one lane tile and 128
    positions, its prediction module (``tag='mtp.'``) included: the loss
    and every leaf's gradient by the kernels are those by the
    ``jax.numpy`` functions (the same model with ``tile`` answering
    None)."""
    from pytorch_ps_mpi_tpu.models import xing

    cfg = xing.XingConfig.tiny(hidden_size=128, hc_init_gate=0.3,
                               hc_init_bias=1.0, remat=remat,
                               layer_index=(0, 2))
    params = xing.init(jax.random.key(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (1, 128), 0,
                                          cfg.vocab_size)}
    step = lambda: jax.jit(jax.value_and_grad(
        lambda p: xing.causal_lm_loss(p, batch, cfg)))(params)
    assert hc.tile(jnp.zeros((4, 1, 128, 128))) == 128
    text = jax.jit(jax.grad(lambda p: xing.causal_lm_loss(p, batch, cfg))
                   ).lower(params).as_text(debug_info=True)
    assert "mtp.hc.mix" in text and "hc_pre_bwd" in text
    loss, grads = step()
    monkeypatch.setattr(hc, "tile", lambda streams: None)
    want, want_grads = step()
    assert off(loss, want) < 1e-5
    for (path, mine), theirs in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree.leaves(want_grads)):
        assert off(mine, theirs) < 1e-3 or float(
            jnp.abs(theirs).max()) < 1e-6, jax.tree_util.keystr(path)
