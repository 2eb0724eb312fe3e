"""``ops/moe_rows_pallas.sum_rows`` (interpreted) against the ``jnp.take``
form it replaces, and ``parallel/dropless.py`` with the kernel under its
sum back against the same layer with ``take`` under it.

float32 is bit-equal where the slots list a position's rows in the order
they lie in the buffer (both add in that order, and a slot that holds
nothing adds +0). bfloat16 is within one ulp of the output: the kernel
adds in float32 and rounds once, the ``take`` form rounds after every
addition, so the kernel is the more exact of the two (checked against
the float32 sum of the same rows).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.ops import moe_rows_pallas
from pytorch_ps_mpi_tpu.parallel import dropless


def take_form(y, slots):
    out = None
    for s in range(slots.shape[1]):
        rows = jnp.take(y, slots[:, s], axis=0, mode="fill", fill_value=0)
        out = rows if out is None else out + rows
    return out


def sorted_case(kind, rng, n, groups, spare=5):
    """A buffer as the plan sorts it: group after group, output rows
    ascending within one, ``spare`` rows that hold nothing at the end.
    -> (keys, to, slots): the group and the output row of each buffer
    row, and for each output row its buffer rows in buffer order."""
    share = {"eighth": 0.125, "none": 0.0, "all": 1.0, "last_group": 1.0}[kind]
    held = rng.random((groups, n)) < share if 0 < share < 1 else (
        np.full((groups, n), bool(share)))
    if kind == "last_group":    # every other group's runs are empty
        held[:-1] = False
    keys, to = np.nonzero(held)
    rows = len(keys) + spare
    slots = np.full((n, groups), rows, np.int32)
    fill = np.zeros(n, np.int64)
    for r, i in enumerate(to):
        slots[i, fill[i]] = r
        fill[i] += 1
    keys = np.concatenate([keys, np.full(spare, groups)]).astype(np.int32)
    to = np.concatenate([to, np.full(spare, n)]).astype(np.int32)
    return keys, to, slots


@pytest.mark.parametrize("width", [1, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["eighth", "none", "all", "last_group"])
def test_sum_rows_is_the_take_form(kind, dtype, width):
    # 70 output rows in blocks of 32: the last block is ragged; the rows
    # end inside a tile of 8; "all" holds more tiles a block than DEPTH
    rng = np.random.default_rng(width)
    n, d, block = 70, 256, 32
    keys, to, slots = sorted_case(kind, rng, n, width)
    y = jnp.asarray(rng.normal(size=(len(to), d)), dtype)
    runs = moe_rows_pallas.block_runs(jnp.asarray(keys), jnp.asarray(to),
                                      width, n, block)
    assert runs.shape == (4, width)
    got = jax.jit(lambda y, to, runs: moe_rows_pallas.sum_rows(
        y, to, runs, n, block))(y, jnp.asarray(to), runs)
    assert got.shape == (n, d) and got.dtype == dtype
    slots = jnp.asarray(slots)
    if dtype == jnp.float32:
        assert np.array_equal(np.asarray(got), np.asarray(take_form(y, slots)))
        return
    exact = np.asarray(take_form(y.astype(jnp.float32), slots))
    got = np.asarray(got.astype(jnp.float32))
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(exact), 1e-30))) - 7)
    assert np.all(np.abs(got - exact) <= ulp)
    # and the one rounding is no worse than the chain's
    chain = np.asarray(take_form(y, slots).astype(jnp.float32))
    assert np.abs(got - exact).max() <= np.abs(chain - exact).max() + 1e-12


def test_the_runs_of_a_sorted_buffer():
    """Two groups over 6 output rows in blocks of 4: group 0 holds rows
    for outputs 1, 3, 4, group 1 for 0, 5; one spare row."""
    keys = jnp.asarray([0, 0, 0, 1, 1, 2], jnp.int32)
    to = jnp.asarray([1, 3, 4, 0, 5, 6], jnp.int32)
    runs = moe_rows_pallas.block_runs(keys, to, 2, 6, 4)
    assert runs.tolist() == [[0, 3], [2, 4], [3, 5]]
    with pytest.raises(ValueError, match="int32"):
        moe_rows_pallas.block_runs(keys, to, 2 ** 20, 2 ** 12, 4)


def test_the_default_block_follows_the_width():
    assert moe_rows_pallas.block_rows(2048) == 512
    assert moe_rows_pallas.block_rows(3584) == 256
    assert moe_rows_pallas.block_rows(128) == 8192


@pytest.mark.parametrize("shape, dtype, want", [
    ((64, 2048), jnp.bfloat16, True), ((64, 3584), jnp.float32, True),
    ((64, 1), jnp.float32, False),          # the gate weights
    ((64, 64), jnp.float32, False),         # the tests' narrow presets
    ((64, 128), jnp.int32, False), ((64, 128), jnp.int8, False),
    ((256 * 1024, 128), jnp.bfloat16, False),   # SMEM holds 131,072 rows
])
def test_what_the_kernel_moves(shape, dtype, want):
    assert moe_rows_pallas.movable(
        jax.ShapeDtypeStruct(shape, dtype)) is want
    if not want:
        with pytest.raises(ValueError, match="lane tiles"):
            moe_rows_pallas.sum_rows(
                jnp.zeros(shape, dtype), jnp.zeros(shape[:1], jnp.int32),
                jnp.zeros((2, 1), jnp.int32), 4, 4)


# -- the layer: 32 positions of 128 (a lane tile: the kernel moves them),
# 8 experts top-2, 4 held --------------------------------------------------

def layer_case(dtype=jnp.float32, capacity_factor=2.0):
    k = jax.random.split(jax.random.key(7), 5)
    p, d, f, n, count = 32, 128, 32, 8, 4
    args = (jax.random.normal(k[0], (p, d), jnp.float32).astype(dtype),
            jax.random.normal(k[1], (d, n), jnp.float32) * 0.3,
            *(jax.random.normal(k[2 + i], shape, jnp.float32).astype(dtype)
              * 0.1 for i, shape in enumerate(
                  [(count, d, f), (count, d, f), (count, f, d)])))
    kw = dict(top_k=2, experts_held=(2, count),
              capacity_factor=capacity_factor)
    return args, kw


def layer_grads(args, kw, remat):
    def loss(*a):
        layer = lambda *a: dropless.dropless_moe(*a, **kw)[0]
        y = (jax.checkpoint(layer) if remat else layer)(*a)
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("leaf", ["x", "router", "gate", "up", "down"])
def test_the_layers_gradients_with_the_kernel_are_the_take_paths(
        monkeypatch, leaf, remat):
    args, kw = layer_case()
    assert dropless._mover(args[0]) == "kernel"
    loss, grads = layer_grads(args, kw, remat)
    monkeypatch.setattr(moe_rows_pallas, "movable", lambda y: False)
    assert dropless._mover(args[0]) == "take"
    want_loss, want = layer_grads(args, kw, remat)
    i = ["x", "router", "gate", "up", "down"].index(leaf)
    assert float(jnp.max(jnp.abs(want[i]))) > 0
    # float32, the same additions in the same order
    assert np.array_equal(np.asarray(grads[i]), np.asarray(want[i]))
    assert float(loss) == float(want_loss)


@pytest.mark.parametrize("capacity_factor, overflows", [(0.25, True),
                                                        (2.0, False)])
def test_an_overflow_is_still_nan(capacity_factor, overflows):
    args, kw = layer_case(jnp.bfloat16, capacity_factor)
    y, loads = jax.jit(lambda *a: dropless.dropless_moe(*a, **kw))(*args)
    held = int(jnp.sum(loads))
    assert (held > dropless.capacity_rows(32, 2, 8, 4, capacity_factor)) \
        is overflows
    assert bool(jnp.all(jnp.isnan(y))) is overflows
    assert bool(jnp.all(jnp.isfinite(y))) is not overflows


@pytest.mark.parametrize("d, dtype, mover", [
    (128, jnp.bfloat16, "kernel"), (64, jnp.float32, "take")])
def test_the_recorder_row_says_who_moved_what(d, dtype, mover):
    """One ``moe.row_moves`` row each time the layer is traced with the
    recorder on: what is moved, the buffer, the pairs expected here, and
    the mover of each of the four moves (rows and gate weights, into the
    buffer and back): only the rows' sum back has a kernel."""
    from pytorch_ps_mpi_tpu import telemetry

    shapes = [(32, d), (d, 8), (4, d, 16), (4, d, 16), (4, 16, d)]
    args = [jax.ShapeDtypeStruct(s, dtype) for s in shapes]
    rec = telemetry.configure()
    try:
        jax.eval_shape(lambda *a: dropless.dropless_moe(
            *a, top_k=2, experts_held=(0, 4), capacity_factor=2.0), *args)
        rows = [e["attrs"] for e in rec.events()
                if e["name"] == "moe.row_moves"]
    finally:
        telemetry.disable()
    assert rows == [{"rows": 32, "slots": 2, "width": d,
                     "dtype": jnp.dtype(dtype).name, "buffer_rows": 64,
                     "expected_held": 32, "gather_rows": "take",
                     "sum_rows": mover, "gather_gates": "take",
                     "sum_gates": "take"}]
