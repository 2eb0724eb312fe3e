"""AsySG-InCon async trainer tests (reference README.md:56-81; the
algorithmic target of BASELINE.md). The reference never tested its async
machinery (SURVEY §4); here staleness semantics are asserted directly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.codecs import get_codec
from pytorch_ps_mpi_tpu.parallel import AsyncPS


def quad_loss(params, batch):
    x, y = batch
    pred = x @ params["w"]
    return jnp.mean((pred - y) ** 2)


def make_setup(num_workers=4, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    params = {"w": jax.random.normal(k1, (6, 2))}
    w_true = jax.random.normal(k3, (6, 2))
    x = jax.random.normal(k2, (num_workers, 8, 6))
    y = jnp.einsum("wbi,ij->wbj", x, w_true)
    return params, (x, y), w_true


def test_async_converges_with_staleness():
    params, batches, w_true = make_setup()
    ps = AsyncPS(params, quad_loss, num_workers=4, max_staleness=2, lr=0.02)
    losses = []
    for _ in range(60):
        ps.step(batches)
        losses.append(float(quad_loss(ps.params, (batches[0][0], batches[1][0]))))
    assert losses[-1] < losses[0] * 0.2


def test_zero_staleness_equals_sequential_sgd():
    """With staleness 0 for all workers, a round must equal applying the
    workers' fresh gradients sequentially (pure inconsistent-read-free PS)."""
    params, batches, _ = make_setup()
    ps = AsyncPS(
        params, quad_loss, num_workers=4, max_staleness=0,
        staleness=[0, 0, 0, 0], lr=0.05,
    )
    ps.step(batches)

    # oracle: all grads computed at the SAME params (vmap semantics),
    # then applied one at a time
    from pytorch_ps_mpi_tpu.optim import SGDHyper, init_sgd_state, sgd_update
    grads = jax.vmap(jax.grad(quad_loss), in_axes=(None, 0))(params, batches)
    p, s = params, init_sgd_state(params)
    for i in range(4):
        g = jax.tree.map(lambda x: x[i], grads)
        p, s = sgd_update(p, g, s, SGDHyper(lr=0.05))
    np.testing.assert_allclose(
        np.asarray(ps.params["w"]), np.asarray(p["w"]), rtol=1e-5, atol=1e-6
    )


def test_history_tracks_versions():
    params, batches, _ = make_setup()
    ps = AsyncPS(params, quad_loss, num_workers=4, max_staleness=2, lr=0.02)
    ps.step(batches)
    # newest history entry == current params; older entries still initial
    np.testing.assert_allclose(
        np.asarray(ps.history["w"][0]), np.asarray(ps.params["w"])
    )
    np.testing.assert_allclose(
        np.asarray(ps.history["w"][2]), np.asarray(params["w"])
    )


def test_async_with_codec():
    params, batches, _ = make_setup()
    ps = AsyncPS(
        params, quad_loss, num_workers=4, max_staleness=1,
        code=get_codec("int8", use_pallas=False), lr=0.02,
    )
    first = float(quad_loss(ps.params, (batches[0][0], batches[1][0])))
    for _ in range(40):
        ps.step(batches)
    last = float(quad_loss(ps.params, (batches[0][0], batches[1][0])))
    assert last < first * 0.5


def test_staleness_validation():
    params, _, _ = make_setup()
    with pytest.raises(ValueError):
        AsyncPS(params, quad_loss, num_workers=4, max_staleness=1,
                staleness=[0, 0, 2, 0])


# -- arrival-driven staleness -----------------------

def test_sampled_staleness_matches_given_distribution():
    """Default mode samples lags per round; over many rounds the used-lag
    histogram must track the requested distribution (not a schedule)."""
    from pytorch_ps_mpi_tpu.parallel.async_ps import (
        staleness_probs_from_histogram,
    )

    params, batches, _ = make_setup()
    probs = staleness_probs_from_histogram({0: 60, 1: 30, 2: 10}, 2)
    np.testing.assert_allclose(probs, [0.6, 0.3, 0.1])
    ps = AsyncPS(params, quad_loss, num_workers=4, max_staleness=2,
                 staleness_probs=probs, lr=0.01, seed=7)
    rounds = 150
    for _ in range(rounds):
        ps.step(batches)
    total = sum(ps.staleness_hist.values())
    assert total == rounds * 4
    emp = np.array([ps.staleness_hist.get(i, 0) / total for i in range(3)])
    # total-variation distance small (600 samples; 3 bins)
    assert 0.5 * np.abs(emp - probs).sum() < 0.08, (emp, probs)
    # and it is genuinely stochastic: both of the non-fresh lags occur
    assert ps.staleness_hist.get(1, 0) > 0 and ps.staleness_hist.get(2, 0) > 0


def test_fixed_schedule_still_available_and_recorded():
    params, batches, _ = make_setup()
    ps = AsyncPS(params, quad_loss, num_workers=4, max_staleness=2,
                 staleness=[0, 1, 2, 2], lr=0.01)
    for _ in range(5):
        ps.step(batches)
    assert ps.staleness_hist == {0: 5, 1: 5, 2: 10}


def test_staleness_probs_validation():
    params, batches, _ = make_setup()
    with pytest.raises(ValueError):
        AsyncPS(params, quad_loss, num_workers=4, max_staleness=2,
                staleness=[0, 1, 2, 0], staleness_probs=[1, 1, 1], lr=0.01)
    with pytest.raises(ValueError):
        AsyncPS(params, quad_loss, num_workers=4, max_staleness=2,
                staleness_probs=[1.0, 1.0], lr=0.01)  # wrong length
    from pytorch_ps_mpi_tpu.parallel.async_ps import (
        staleness_probs_from_histogram,
    )
    with pytest.raises(ValueError):
        staleness_probs_from_histogram({7: 10}, 2)  # all mass was dropped


def test_negative_fixed_staleness_rejected():
    params, batches, _ = make_setup()
    with pytest.raises(ValueError):
        AsyncPS(params, quad_loss, num_workers=4, max_staleness=2,
                staleness=[-1, 0, 0, 0], lr=0.01)
