"""The plain references against the package's models at tiny presets on
seeded weights: loss and gradients, float32 on both sides."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import resnet as ref_resnet
from chipbench.reference import transformer as ref_tf


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


def transformer_case(kind):
    from pytorch_ps_mpi_tpu.models import GPTLM, BertMLM, gpt_tiny
    from pytorch_ps_mpi_tpu.models.bert import BertConfig, mlm_loss
    from pytorch_ps_mpi_tpu.models.gpt import causal_lm_loss

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, 512, (6, 32)), jnp.int32)
    if kind == "bert":
        model = BertMLM(BertConfig.tiny(vocab_size=512))
        batch = {"tokens": tokens, "targets": tokens,
                 "mask": jnp.asarray(rng.random((6, 32)) < 0.3)}
        system = lambda p: mlm_loss(model.apply(p, batch["tokens"]),
                                    batch["targets"], batch["mask"])
        terms = ref_tf.mlm_terms
    else:
        model = GPTLM(gpt_tiny(vocab_size=512, attention="einsum"))
        batch = {"tokens": tokens}
        system = lambda p: causal_lm_loss(model.apply(p, tokens), tokens)
        terms = ref_tf.lm_terms
    params = jax.jit(model.init)(jax.random.key(1), tokens[:1])
    return params, batch, system, functools.partial(terms, num_layers=2)


@pytest.mark.parametrize("kind", ["bert", "gpt"])
@pytest.mark.parametrize("block_rows", [6, 4])
def test_transformer_reference_matches_model(kind, block_rows):
    params, batch, system, terms = transformer_case(kind)
    want_loss, want_grads = jax.value_and_grad(system)(params)
    loss, grads = ref_tf.BlockedLoss(terms, block_rows)(params, batch)
    assert close(loss, want_loss, 1e-5)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert close(got, want)


def test_reference_adam_is_the_programs_adam():
    from pytorch_ps_mpi_tpu.optim import AdamHyper, adam_update, init_adam_state

    params, batch, system, _ = transformer_case("bert")
    grads = jax.grad(system)(params)
    state, p_sys = init_adam_state(params), params
    adam, p_ref = ref_tf.Adam(params, 1e-3), params
    for _ in range(3):
        p_sys, state = adam_update(p_sys, grads, state, AdamHyper(lr=1e-3))
        p_ref = adam.update(p_ref, grads)
    for got, want in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sys)):
        assert close(got, want, 2e-5)  # float32 rounding of the step size


def test_resnet_reference_matches_model():
    from pytorch_ps_mpi_tpu.parallel.async_train import make_problem

    cfg = {"model": "resnet18", "model_kw": {"num_classes": 10},
           "in_shape": [8, 8, 3], "batch": 4, "seed": 3}
    _, params, batch_fn, loss_fn = make_problem(cfg)
    batch = batch_fn(0, 0)
    want_loss, want_grads = jax.value_and_grad(loss_fn)(params, batch)
    loss, grads = jax.value_and_grad(ref_resnet.loss)(params, batch)
    assert close(loss, want_loss, 1e-5)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert close(got, want, 1e-3)


def test_int8_roundtrip_is_the_codecs():
    from pytorch_ps_mpi_tpu.codecs import get_codec

    code = get_codec("int8")
    g = jax.random.normal(jax.random.key(2), (4099,), jnp.float32) * 0.03
    payload, _ = code.encode(g, code.init_state(g.shape, g.dtype))
    want = code.decode(payload, g.shape, g.dtype)
    assert np.array_equal(ref_resnet.int8_roundtrip(np.asarray(g)),
                          np.asarray(want))


@pytest.mark.parametrize("gen, kw", [("mlm_uniform", {"mask_rate": 0.15}),
                                     ("lm_zipf", {"exponent": 1.0})])
def test_generators_are_seeded(gen, kw):
    import importlib

    batches = importlib.import_module(f"chipbench.gen.{gen}").batches
    a, b, c = (next(batches(s, 4, 16, 100, **kw)) for s in (5, 5, 6))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert any(not np.array_equal(a[k], c[k]) for k in a)
    assert a["tokens"].shape == (4, 16) and a["tokens"].dtype == np.int32
    assert 0 <= a["tokens"].min() and a["tokens"].max() < 100
