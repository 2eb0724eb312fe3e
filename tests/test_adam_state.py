"""Adam's state without AMSGrad holds no running maximum (a fourth
float32 copy of the parameters that nothing read); a state or checkpoint
of the old shape still loads and steps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu import MPI_PS
from pytorch_ps_mpi_tpu.optim import AdamHyper, adam_update, init_adam_state
from pytorch_ps_mpi_tpu.trainer import Trainer


def problem():
    params = {"w": jnp.arange(12.0).reshape(3, 4) / 10, "b": jnp.ones((4,))}
    grads = {"w": jnp.sin(jnp.arange(12.0)).reshape(3, 4), "b": -jnp.ones((4,))}
    return params, grads


def loss_fn(p, batch):
    return jnp.mean((batch["x"] @ p["w"] + p["b"] - batch["y"]) ** 2)


def batches():
    k = jax.random.split(jax.random.key(1), 2)
    b = {"x": jax.random.normal(k[0], (8, 3)), "y": jax.random.normal(k[1], (8, 4))}
    while True:
        yield b


def test_the_maximum_is_empty_without_amsgrad():
    params, _ = problem()
    assert init_adam_state(params, amsgrad=False).max_exp_avg_sq == ()
    full = init_adam_state(params)
    assert jax.tree.structure(full.max_exp_avg_sq) == jax.tree.structure(params)


@pytest.mark.parametrize("old_shape", [False, True])
def test_the_update_is_the_same_without_the_tree(old_shape):
    params, grads = problem()
    h = AdamHyper(lr=1e-2)
    want_p, want_s = params, init_adam_state(params)
    got_p, got_s = params, init_adam_state(params, amsgrad=old_shape)
    for _ in range(3):
        want_p, want_s = adam_update(want_p, grads, want_s, h)
        got_p, got_s = adam_update(got_p, grads, got_s, h)
    for a, b in zip(jax.tree.leaves(want_p), jax.tree.leaves(got_p)):
        assert np.array_equal(a, b)
    # the tree is passed on as it came: empty, or the old shape's zeros
    assert jax.tree.structure(got_s.max_exp_avg_sq) == jax.tree.structure(
        init_adam_state(params, amsgrad=old_shape).max_exp_avg_sq)


def test_amsgrad_without_its_tree_is_an_error():
    params, grads = problem()
    with pytest.raises(ValueError):
        adam_update(params, grads, init_adam_state(params, amsgrad=False),
                    AdamHyper(amsgrad=True))


@pytest.mark.parametrize("mode", ["allgather", "leader"])
@pytest.mark.parametrize("amsgrad", [False, True])
def test_the_optimizer_holds_the_tree_only_with_amsgrad(mode, amsgrad):
    params, _ = problem()
    opt = MPI_PS(params, optim="adam", lr=1e-2, mode=mode, amsgrad=amsgrad,
                 average=True)
    state = opt.opt_state.inner if mode == "leader" else opt.opt_state
    assert (state.max_exp_avg_sq == ()) == (not amsgrad)
    loss = Trainer(opt, loss_fn).fit(batches(), 2)["final_loss"]
    assert np.isfinite(loss)


def test_a_checkpoint_of_the_old_shape_still_loads(tmp_path):
    params, _ = problem()
    old = MPI_PS(params, optim="adam", lr=1e-2, average=True)
    trainer = Trainer(old, loss_fn, checkpoint_dir=str(tmp_path))
    trainer.fit(batches(), 2)
    # as written before the maximum became optional: a params-shaped tree
    old.opt_state = old.opt_state._replace(
        max_exp_avg_sq=jax.tree.map(jnp.zeros_like, old.opt_state.exp_avg_sq))
    trainer.save()

    new = MPI_PS(params, optim="adam", lr=1e-2, average=True)
    resumed = Trainer(new, loss_fn, checkpoint_dir=str(tmp_path))
    assert resumed.maybe_restore() is True
    assert resumed.step_count == 2
    assert new.opt_state.max_exp_avg_sq == ()
    assert int(new.opt_state.step) == 2
    for a, b in zip(jax.tree.leaves(new.params), jax.tree.leaves(old.params)):
        assert np.array_equal(a, b)
    assert np.isfinite(resumed.fit(batches(), 1)["final_loss"])
