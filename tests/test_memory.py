"""HBM-management features: buffer donation in the fused step and
per-layer rematerialization (jax.checkpoint) in the transformer models.
Numerics must be IDENTICAL with the features on or off — they change
where memory goes, never the math."""

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_ps_mpi_tpu import SGD
from pytorch_ps_mpi_tpu.models import BertConfig, BertMLM, GPTLM, gpt_tiny


def test_donated_step_matches_undonated(mesh8):
    """donate_buffers=True reuses input buffers for outputs; the update
    itself is unchanged — identical params after several steps."""
    def run(donate):
        params = {"w": jnp.zeros((4, 3)), "b": jnp.zeros((3,))}

        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

        opt = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9,
                  donate_buffers=donate)
        k1, k2 = jax.random.split(jax.random.key(3))
        batch = (jax.random.normal(k1, (16, 4)), jax.random.normal(k2, (16, 3)))
        for _ in range(3):
            opt.step(loss_fn=loss_fn, batch=batch)
        return opt.params

    p_plain = run(False)
    p_donated = run(True)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        p_plain, p_donated,
    )


def test_donated_accumulate_matches_undonated(mesh8):
    def run(donate):
        params = {"w": jnp.zeros((4, 2))}

        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((x @ p["w"] - y) ** 2)

        opt = SGD(params, mesh=mesh8, lr=0.05, donate_buffers=donate)
        k1, k2 = jax.random.split(jax.random.key(5))
        batches = (jax.random.normal(k1, (2, 16, 4)),
                   jax.random.normal(k2, (2, 16, 2)))
        opt.step_accumulate(loss_fn, batches)
        return opt.params

    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        run(False), run(True),
    )


def test_remat_bert_same_outputs_and_grads():
    """remat=True recomputes activations in backward; forward AND
    gradients match the non-remat model bitwise-close, with the same
    parameter structure (checkpointing is invisible to the optimizer)."""
    cfg = BertConfig.tiny()
    cfg_r = BertConfig.tiny(remat=True)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    params = BertMLM(cfg).init(jax.random.key(0), tokens)
    params_r = BertMLM(cfg_r).init(jax.random.key(0), tokens)
    assert (jax.tree.structure(params) == jax.tree.structure(params_r))

    def loss(model_cfg):
        def f(p):
            return BertMLM(model_cfg).apply(p, tokens).sum()
        return f

    out, grads = jax.value_and_grad(loss(cfg))(params)
    out_r, grads_r = jax.value_and_grad(loss(cfg_r))(params)
    np.testing.assert_allclose(float(out), float(out_r), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        grads, grads_r,
    )


def test_remat_gpt_same_outputs_and_grads():
    cfg = gpt_tiny()
    cfg_r = gpt_tiny(remat=True)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    params = GPTLM(cfg).init(jax.random.key(0), tokens)

    def loss(model_cfg):
        def f(p):
            return GPTLM(model_cfg).apply(p, tokens).sum()
        return f

    out, grads = jax.value_and_grad(loss(cfg))(params)
    out_r, grads_r = jax.value_and_grad(loss(cfg_r))(params)
    np.testing.assert_allclose(float(out), float(out_r), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        grads, grads_r,
    )


def test_step_memory_analysis_reports_donation(mesh8):
    """XLA's buffer assignment is the runtime-stats-independent HBM
    probe (XLA:CPU returns no memory_stats()): donation must
    appear as nonzero alias bytes and a strictly smaller estimated
    peak than the undonated compile of the SAME step."""
    def analyze(donate):
        # params + momentum must DOMINATE activation temps, or temp-size
        # jitter between the two compiles can swamp the aliasing signal
        params = {"w": jnp.zeros((512, 512)), "b": jnp.zeros((512,))}

        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

        opt = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9,
                  donate_buffers=donate)
        k1, k2 = jax.random.split(jax.random.key(3))
        batch = (jax.random.normal(k1, (16, 512)),
                 jax.random.normal(k2, (16, 512)))
        return opt.step_memory_analysis(loss_fn, batch)

    plain = analyze(False)
    donated = analyze(True)
    assert plain.get("estimated_peak_bytes") is not None
    assert donated.get("alias_size_in_bytes", 0) > 0
    assert plain.get("alias_size_in_bytes", 0) == 0
    assert (donated["estimated_peak_bytes"]
            < plain["estimated_peak_bytes"])
