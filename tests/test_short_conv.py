"""``ops/short_conv.py`` against a loop over positions in float32
(``by_position``: one position, one tap, one channel vector at a time):
lengths that nothing divides, rows shorter than the taps, rows that must
not see each other, the gradients of all four inputs, and the ``conv.plan``
row."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.ops.short_conv import gated_short_conv


def by_position(b_gate, c_gate, u, taps):
    """``out[r, t] = C[r, t] * sum_j taps[:, j] * (B * u)[r, t - (K - 1) +
    j]``, nothing before a row's first position."""
    rows, steps, _ = u.shape
    k = taps.shape[1]
    out = []
    for r in range(rows):
        row = []
        for t in range(steps):
            c = jnp.zeros_like(u[r, t])
            for j in range(k):
                at = t - (k - 1) + j
                if at >= 0:
                    c = c + taps[:, j] * b_gate[r, at] * u[r, at]
            row.append(c_gate[r, t] * c)
        out.append(jnp.stack(row))
    return jnp.stack(out)


def inputs(rows, steps, d, k, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    parts = [jax.random.normal(kk, (rows, steps, d)) for kk in keys[:3]]
    return (*parts, jax.random.normal(keys[3], (d, k)))


def close(a, b, tol=1e-6):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


@pytest.mark.parametrize("steps, k", [
    (13, 3),    # a length that nothing divides
    (2, 3),     # shorter than the taps
    (1, 3),     # one position: the last tap alone
    (7, 1),     # one tap: the two gates and a scale
    (11, 4),    # another tap count
])
def test_against_the_loop_over_positions(steps, k):
    b_gate, c_gate, u, taps = inputs(2, steps, 5, k)
    got = gated_short_conv(jnp.concatenate([b_gate, c_gate, u], -1), taps)
    assert got.shape == (2, steps, 5)
    assert close(got, by_position(b_gate, c_gate, u, taps))


def test_two_rows_do_not_see_each_other():
    b_gate, c_gate, u, taps = inputs(2, 6, 4, 3)
    bcu = jnp.concatenate([b_gate, c_gate, u], -1)
    both = gated_short_conv(bcu, taps)
    for r in range(2):
        alone = gated_short_conv(bcu[r:r + 1], taps)
        assert np.array_equal(both[r:r + 1], alone)
    # and another first row leaves the second row what it was
    other = gated_short_conv(bcu.at[0].set(7.0), taps)
    assert np.array_equal(other[1], both[1])
    assert not np.array_equal(other[0], both[0])


def test_it_is_causal():
    b_gate, c_gate, u, taps = inputs(1, 9, 4, 3)
    bcu = jnp.concatenate([b_gate, c_gate, u], -1)
    out = gated_short_conv(bcu, taps)
    later = gated_short_conv(bcu.at[:, 5:].set(3.0), taps)
    assert np.array_equal(later[:, :5], out[:, :5])


@pytest.mark.parametrize("which", ["B", "C", "u", "taps"])
def test_the_gradient_of(which):
    b_gate, c_gate, u, taps = inputs(2, 10, 6, 3, seed=1)
    weight = jax.random.normal(jax.random.key(9), (2, 10, 6))
    at = "BCu".find(which) if which != "taps" else 3

    def mine(*z):
        return jnp.sum(weight * gated_short_conv(
            jnp.concatenate(z[:3], -1), z[3]))

    def loop(*z):
        return jnp.sum(weight * by_position(*z))

    args = (b_gate, c_gate, u, taps)
    got, want = jax.grad(mine, at)(*args), jax.grad(loop, at)(*args)
    assert np.any(np.asarray(want))
    assert close(got, want, 1e-5)


def test_the_gates_and_the_sum_are_float32_and_rounded_once():
    b_gate, c_gate, u, taps = inputs(2, 16, 8, 3, seed=2)
    bcu = jnp.concatenate([b_gate, c_gate, u], -1).astype(jnp.bfloat16)
    got = gated_short_conv(bcu, taps)
    assert got.dtype == jnp.bfloat16
    parts = [p.astype(jnp.float32) for p in jnp.split(bcu, 3, -1)]
    want = by_position(*parts, taps).astype(jnp.bfloat16)
    assert np.array_equal(got.astype(jnp.float32), want.astype(jnp.float32))


def test_a_width_that_is_not_three_parts_is_refused():
    with pytest.raises(ValueError, match="side by side"):
        gated_short_conv(jnp.zeros((1, 4, 10)), jnp.zeros((3, 3)))


def test_one_conv_plan_row_a_trace():
    from pytorch_ps_mpi_tpu import telemetry

    b_gate, c_gate, u, taps = inputs(2, 12, 8, 3)
    bcu = jnp.concatenate([b_gate, c_gate, u], -1).astype(jnp.bfloat16)
    rec = telemetry.configure()
    try:
        jax.jit(gated_short_conv)(bcu, taps)
        rows = [e for e in rec.events() if e["name"] == "conv.plan"]
    finally:
        telemetry.disable()
    assert len(rows) == 1
    assert rows[0]["attrs"] == dict(
        rows=2, T=12, channels=8, taps=3, bytes_read=2 * 12 * 24 * 2,
        bytes_written=2 * 12 * 8 * 2, mover="jnp")
