"""``ops/delta_rule.py``'s chunked form against the recurrence run token by
token (``chipbench/reference/solar.py::delta_rule_steps``), forward and
gradients, in float32: betas near 0 and near 2, the strongest decays the
family's initialisation draws and decays far past them, lengths no chunk
divides, rows that must not see each other, chunks of 16 and 64; and what
the form is: one pass over the chunks, no per-token loop, one ``kda.plan``
row a trace.

Tolerance: 2e-5 of the largest entry (float32 on both sides; the chunked
form sums in other orders and solves a triangular system)."""

import math

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference.solar import delta_rule_steps
from pytorch_ps_mpi_tpu.ops.delta_rule import decayed_products, delta_rule

ROWS, HEADS, D = 2, 3, 8
# the family's strongest decay a step: exp(A_log) at its largest (16) times
# softplus(dt_bias) at dt's largest (0.1), the projection's share aside
STRONGEST = -16 * 0.1


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


def token_by_token(q, k, v, g, beta):
    return jax.vmap(delta_rule_steps)(q, k, v, g, beta)


def inputs(steps, seed=0, beta=None, decay=None):
    """Unit q and k a head, values, log-decays of about ``decay`` a step
    (default: the family's range), betas in (0, 2) or about ``beta``."""
    keys = jax.random.split(jax.random.key(seed), 5)
    shape = (ROWS, steps, HEADS, D)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], shape)) * D ** -0.5
    k = unit(jax.random.normal(keys[1], shape))
    v = jax.random.normal(keys[2], shape)
    spread = jax.random.uniform(keys[3], shape, minval=0.5, maxval=1.5)
    g = (decay * spread if decay is not None
         else -jnp.exp(jax.random.uniform(keys[3], shape, minval=-5.0,
                                          maxval=math.log(1.6))))
    b = jax.random.uniform(keys[4], shape[:3])
    b = 2 * b if beta is None else beta + 0.01 * (b - 0.5)
    return q, k, v, g, b


CASES = {
    "family range": dict(),
    "beta near 0": dict(beta=0.01),
    "beta near 2": dict(beta=1.99),
    "strongest initial decay": dict(decay=STRONGEST),
    "decay far past it": dict(decay=-40.0),
    "no decay": dict(decay=-1e-6),
}


@pytest.mark.parametrize("chunk, steps", [(16, 16), (16, 50), (64, 64),
                                          (64, 130), (16, 5)])
@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_is_the_recurrence(case, chunk, steps):
    q, k, v, g, b = inputs(steps, **CASES[case])
    got = jax.jit(lambda *a: delta_rule(*a, chunk=chunk))(q, k, v, g, b)
    assert got.shape == (ROWS, steps, HEADS, D) and got.dtype == jnp.float32
    assert np.all(np.isfinite(got))
    assert close(got, token_by_token(q, k, v, g, b))


@pytest.mark.parametrize("chunk, steps", [(16, 50), (64, 130)])
@pytest.mark.parametrize("case", ["family range", "beta near 2",
                                  "strongest initial decay",
                                  "decay far past it"])
def test_gradients_are_the_recurrences(case, chunk, steps):
    args = inputs(steps, seed=1, **CASES[case])
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    got = jax.jit(jax.grad(loss(lambda *a: delta_rule(*a, chunk=chunk)),
                           argnums=range(5)))(*args)
    want = jax.jit(jax.grad(loss(token_by_token), argnums=range(5)))(*args)
    # where the decay leaves a gradient at a billionth of the others (g's,
    # at 40 a step), its error is held to the others' scale
    scale = max(float(np.max(np.abs(b))) for b in want)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert np.all(np.isfinite(a)), name
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.max(np.abs(a - b)) <= 1e-4 * max(np.max(np.abs(b)),
                                                   1e-3 * scale), name


def test_rows_never_see_each_other():
    q, k, v, g, b = inputs(40)
    whole = delta_rule(q, k, v, g, b, chunk=16)
    for r in range(ROWS):
        one = delta_rule(*(a[r:r + 1] for a in (q, k, v, g, b)), chunk=16)
        assert close(one[0], whole[r], 1e-6)
    # a row changed from its first position on moves no other row
    moved = delta_rule(q, k.at[1].set(-k[1]), v.at[1].set(2 * v[1]), g, b,
                       chunk=16)
    assert np.array_equal(moved[0], whole[0])
    assert not np.allclose(moved[1], whole[1])


def test_bf16_inputs_keep_a_float32_state():
    q, k, v, g, b = inputs(50)
    low = [a.astype(jnp.bfloat16) for a in (q, k, v)]
    got = delta_rule(*low, g, b, chunk=16)
    want = token_by_token(*(a.astype(jnp.float32) for a in low), g, b)
    assert got.dtype == jnp.float32 and close(got, want)


def test_the_decayed_products_are_exact_where_a_naive_factoring_overflows():
    """The pairs a chunk meets, built by halves, against the sum written
    out; at 40 a step a chunk of 16 spans exp(640), far past float32."""
    keys = jax.random.split(jax.random.key(3), 3)
    c = 16
    x = jax.random.normal(keys[0], (2, 1, c, D))
    k = jax.random.normal(keys[1], (1, c, D))
    gamma = jnp.cumsum(-40.0 * jax.random.uniform(keys[2], (1, c, D)), 1)
    assert not np.all(np.isfinite(jnp.exp(-gamma)))
    got = decayed_products(x, k, gamma)
    diff = gamma[:, :, None, :] - gamma[:, None, :, :]
    lower = jnp.tril(jnp.ones((c, c), bool), -1)[None, :, :, None]
    want = jnp.sum(x[:, :, :, None, :] * k[None, :, None, :, :]
                   * jnp.exp(jnp.where(lower, diff, -jnp.inf)), -1)
    assert close(got, want, 1e-6)
    assert not np.any(np.triu(np.asarray(got)[0, 0]))


def scans(jaxpr):
    """The lengths of every scan in a jaxpr, nested ones too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)   # a ClosedJaxpr's own
                if isinstance(sub, jax.extend.core.Jaxpr):
                    out += scans(sub)
    return out


@pytest.mark.parametrize("chunk, steps", [(16, 50), (64, 8192)])
def test_one_pass_over_the_chunks_and_no_per_token_loop(chunk, steps):
    shape = jax.ShapeDtypeStruct((1, steps, 2, 128), jnp.bfloat16)
    f32 = jax.ShapeDtypeStruct((1, steps, 2, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, steps, 2), jnp.float32)
    fwd = jax.make_jaxpr(lambda *a: delta_rule(*a, chunk=chunk))(
        shape, shape, shape, f32, beta)
    assert scans(fwd.jaxpr) == [-(-steps // chunk)]
    bwd = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(delta_rule(*a, chunk=chunk)), argnums=range(5)))(
        f32, f32, f32, f32, beta)
    assert set(scans(bwd.jaxpr)) == {-(-steps // chunk)}


def test_the_plan_row_and_what_is_refused():
    from pytorch_ps_mpi_tpu import telemetry

    q, k, v, g, b = inputs(50)
    rec = telemetry.configure()
    try:
        delta_rule(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                   v.astype(jnp.bfloat16), g, b, chunk=16)
        plans = [e["attrs"] for e in rec.events() if e["name"] == "kda.plan"]
    finally:
        telemetry.disable()
    per = ROWS * 50 * HEADS
    assert plans == [dict(rows=ROWS, heads=HEADS, T=50, chunk=16, dk=D, dv=D,
                          bytes_read=per * (3 * D * 2 + D * 4 + 4),
                          bytes_written=per * D * 4)]
    with pytest.raises(ValueError, match="power of two"):
        delta_rule(q, k, v, g, b, chunk=48)
