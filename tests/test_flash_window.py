"""The window mask and a value wider than the keys in the two flash
kernels (interpreted), against the dense masked softmax; the census of
sub-tiles under the window; the band of grid steps; the kernels' names."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.ops import attention_pallas as ap


def qkv(t, heads=4, kv_heads=2, d=16, dv=16, rows=2, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(1), 4)
    return (jax.random.normal(k[0], (rows, t, heads, d), dtype),
            jax.random.normal(k[1], (rows, t, kv_heads, d), dtype),
            jax.random.normal(k[2], (rows, t, kv_heads, dv), dtype),
            jax.random.normal(k[3], (rows, t, heads, dv)))


def both(q, k, v, w, spec, **kw):
    """(value, dq, dk, dv) of sum(w * attention) by the kernels and by
    the dense oracle."""
    kernel = lambda q, k, v: jnp.sum(w * ap.flash_attention(q, k, v, **kw))
    dense = lambda q, k, v: jnp.sum(w * ap._attention_jnp(
        q, k, v, 0, 0, spec, q.shape[-1] ** -0.5)[0])
    return [jax.tree.leaves(jax.value_and_grad(f, (0, 1, 2))(q, k, v))
            for f in (kernel, dense)]


def assert_close(got, want):
    for g, wnt in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wnt),
                                   rtol=2e-4, atol=3e-5)


# sub-tiles of 16: a window under, at and over one, over the whole
# sequence, and of one position; grid tiles of several sub-tiles
@pytest.mark.parametrize("t, window, bq, bk", [
    (64, 8, 16, 16), (64, 16, 16, 16), (64, 40, 16, 16), (64, 100, 16, 16),
    (64, 1, 16, 16), (64, 16, 32, 16), (64, 16, 16, 32), (128, 24, 64, 32),
    (96, 17, 32, 32)])
def test_window_mask_forward_and_backward(t, window, bq, bk):
    q, k, v, w = qkv(t)
    assert_close(*both(q, k, v, w, ("window", window), mask="window",
                       window=window, block_q=bq, block_k=bk))


@pytest.mark.parametrize("kw, spec", [
    (dict(causal=True), ("causal",)), (dict(), ("none",)),
    (dict(mask="window", window=24), ("window", 24))])
def test_a_value_wider_than_the_keys(kw, spec):
    q, k, v, w = qkv(64, d=16, dv=32)
    got, want = both(q, k, v, w, spec, block_q=16, block_k=32, **kw)
    assert got[3].shape == v.shape and got[1].shape == q.shape
    assert_close(got, want)
    out = ap.flash_attention(q, k, v, block_q=16, block_k=32, **kw)
    assert out.shape == (2, 64, 4, 32)


def test_window_in_bf16_matches_the_oracle_on_the_same_inputs():
    q, k, v, _ = qkv(64, dtype=jnp.bfloat16, d=16, dv=32)
    out = ap.flash_attention(q, k, v, mask="window", window=16,
                             block_q=16, block_k=16)
    want, _ = ap._attention_jnp(q, k, v, 0, 0, ("window", 16), 0.25)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)


@pytest.mark.parametrize("window, sub", [(8, 16), (16, 16), (40, 16), (16, 8),
                                         (512, 512)])
def test_census_under_the_window(window, sub):
    """Each sub-tile's class from its corners equals its class from the
    dense mask."""
    length = 16 * sub if sub > 16 else 64
    ids = jnp.arange(length)
    dense = np.asarray(ap.allowed_pairs(("window", window), ids, ids))
    tiles = dense.reshape(length // sub, sub, length // sub, sub)
    dead = int((~tiles.any((1, 3))).sum())
    full = int(tiles.all((1, 3)).sum())
    total = (length // sub) ** 2
    assert ap.tile_census(("window", window), length, length, sub, sub, sub,
                          sub) == {"dead": dead, "cut": total - dead - full,
                                   "full": full}


def test_the_cells_window_layer():
    """8,192 positions, window 512, sub-tiles of 512: 2 live sub-tiles a
    q sub-tile (1 in the first), none full; and the grid's inner axis is
    2 k tiles a q tile, not 16 or 8 (both kernels walk the q tiles' bands
    since PR 40: the transposed band went with the dk/dv kernel)."""
    plan = ap.flash_tiles(("window", 512), 8192, 8192, jnp.bfloat16)
    assert plan == {"mask": "window", "block_q": 512, "block_k": 512,
                    "sub_q": 512, "sub_k": 512, "dead": 225, "cut": 31,
                    "full": 0}
    for bq, bk in ((512, 512), (1024, 1024)):
        n = 8192 // bq
        steps, tile, fetched = ap._band(("window", 512), n, n, bq, bk)
        assert steps == 2
        assert [int(tile(j, 0)) for j in (0, 1, 5)] == [0, 0, 4]
        assert int(fetched(n - 1, 1)) == n - 1
    # any other mask: every tile, step kk is tile kk
    steps, tile, fetched = ap._band(("causal",), 8, 8, 1024, 1024)
    assert steps == 8 and tile(3, 5) == 5 and fetched(3, 5) == 5


def test_flash_tiles_says_where_the_dense_path_runs():
    assert ap.flash_tiles(("causal",), 100, 100, jnp.float32) is None
    plan = ap.flash_tiles(("causal",), 1024, 1024, jnp.bfloat16)
    assert (plan["block_q"], plan["sub_q"], plan["dead"], plan["cut"],
            plan["full"]) == (1024, 512, 1, 2, 1)


def test_kernel_names():
    assert ap._kernel_name(("window", 512), "fwd") == "flash_win_fwd"
    # the one backward kernel: "dq" + "kv" to the benchmark's readers
    assert ap._kernel_name(("bd", 4, 64), "dqkv") == "flash_bd_dqkv"
    assert ap._kernel_name(("window", 512), "dqkv", True) == "flash_win_dqkv"
    assert ap._kernel_name(("causal",), "dqkv", True) == "flash_wide_dqkv"
    assert ap._kernel_name(("causal",), "dqkv") is None
    # the accepted readers match the name XLA gives these
    assert ap._kernel_name(("causal",), "fwd") is None
    assert ap._kernel_name(("none",), "fwd") is None


def test_window_arguments_are_checked():
    q, k, v, _ = qkv(64)
    with pytest.raises(ValueError, match="window >= 1"):
        ap.flash_attention(q, k, v, mask="window")
    with pytest.raises(ValueError, match="no offsets"):
        ap.flash_attention(q, k, v, mask="window", window=8, q_offset=8)
    with pytest.raises(ValueError, match="one length"):
        ap.flash_attention(q, k[:, :32], v[:, :32], mask="window", window=8)
    with pytest.raises(ValueError, match="unknown mask"):
        ap.flash_attention(q, k, v, mask="band")
