"""Model-zoo shape/grad sanity + an end-to-end distributed training run
for each BASELINE config family (BASELINE.json)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu import SGD
from pytorch_ps_mpi_tpu.data import cross_entropy_loss, synthetic_images, synthetic_mlm
from pytorch_ps_mpi_tpu.models import MLP, BertConfig, BertMLM, ResNet18, ResNet50
from pytorch_ps_mpi_tpu.models.bert import mlm_loss


def test_mlp_mnist_e2e(mesh8):
    """BASELINE config #1: MLP/MNIST sync SGD — loss must decrease."""
    model = MLP(features=(32, 10))
    data = synthetic_images("mnist", batch=32)
    x0, y0 = next(data)
    params = model.init(jax.random.key(0), x0)

    def loss_fn(p, batch):
        x, y = batch
        return cross_entropy_loss(model.apply(p, x), y)

    opt = SGD(params, mesh=mesh8, lr=0.01, momentum=0.9, average=True)
    losses = []
    for i, batch in zip(range(12), data):
        loss, _ = opt.step(loss_fn=loss_fn, batch=batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_resnet18_forward_and_grad():
    model = ResNet18(num_classes=10, small_inputs=True, num_filters=16)
    x = jnp.ones((2, 32, 32, 3))
    params = model.init(jax.random.key(0), x)
    out = model.apply(params, x)
    assert out.shape == (2, 10)
    g = jax.grad(lambda p: model.apply(p, x).sum())(params)
    assert np.isfinite(np.asarray(jax.tree.leaves(g)[0])).all()


def test_resnet50_forward():
    model = ResNet50(num_classes=10, small_inputs=True, num_filters=16)
    x = jnp.ones((1, 32, 32, 3))
    params = model.init(jax.random.key(0), x)
    assert model.apply(params, x).shape == (1, 10)


def test_resnet18_distributed_step(mesh8):
    """BASELINE config #2 shape: ResNet-18/CIFAR-10, sync allreduce."""
    model = ResNet18(num_classes=10, small_inputs=True, num_filters=8)
    data = synthetic_images("cifar10", batch=16)
    x0, y0 = next(data)
    params = model.init(jax.random.key(0), x0)

    def loss_fn(p, batch):
        x, y = batch
        return cross_entropy_loss(model.apply(p, x), y)

    opt = SGD(params, mesh=mesh8, lr=0.01, average=True)
    loss, data_dict = opt.step(loss_fn=loss_fn, batch=(x0, y0))
    assert np.isfinite(float(loss))
    assert data_dict["msg_bytes"] > 0


def test_bert_tiny_mlm(mesh8):
    """BASELINE config #5 shape: BERT MLM distributed step."""
    cfg = BertConfig.tiny()
    model = BertMLM(cfg)
    gen = synthetic_mlm(batch=8, seq_len=16, vocab_size=cfg.vocab_size)
    batch = next(gen)
    params = model.init(jax.random.key(0), batch["tokens"])

    def loss_fn(p, b):
        logits = model.apply(p, b["tokens"])
        return mlm_loss(logits, b["targets"], b["mask"])

    opt = SGD(params, mesh=mesh8, lr=0.05, average=True)
    first, _ = opt.step(loss_fn=loss_fn, batch=batch)
    for _ in range(5):
        last, _ = opt.step(loss_fn=loss_fn, batch=batch)
    assert float(last) < float(first)


def test_bert_ring_attention_matches_full():
    """Ring-attention BERT == full-attention BERT on the same params."""
    from jax.sharding import PartitionSpec as P
    from pytorch_ps_mpi_tpu.mesh import make_mesh

    mesh = make_mesh(axis_names=("seq",))
    cfg_full = BertConfig.tiny()
    cfg_ring = BertConfig.tiny(attention="ring")
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg_full.vocab_size)
    params = BertMLM(cfg_full).init(jax.random.key(0), tokens)
    ref = BertMLM(cfg_full).apply(params, tokens)

    l_local = 32 // 8

    def spmd(params, tokens):
        import jax.lax as lax
        offset = lax.axis_index("seq") * l_local
        return BertMLM(cfg_ring).apply(params, tokens, position_offset=offset)

    ring = jax.jit(
        jax.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), P(None, "seq")),
            out_specs=P(None, "seq"),
            check_vma=False,
        )
    )(params, tokens)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref), rtol=3e-4, atol=3e-4)


def test_resnet_batchnorm_aux_state_distributed(mesh8):
    """norm='batch' ResNet trains through the aux-state path with
    cross-replica synced batch_stats (torch needed SyncBatchNorm)."""
    from pytorch_ps_mpi_tpu.models import ResNet18

    model = ResNet18(num_classes=10, small_inputs=True, num_filters=8,
                     norm="batch")
    x0, y0 = next(synthetic_images("cifar10", batch=16))
    variables = model.init(jax.random.key(0), x0)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(p, aux, batch):
        x, y = batch
        logits, updates = model.apply(
            {"params": p, "batch_stats": aux}, x, train=True,
            mutable=["batch_stats"],
        )
        return cross_entropy_loss(logits, y), updates["batch_stats"]

    opt = SGD(params, mesh=mesh8, lr=0.01, average=True)
    first, _ = opt.step(loss_fn=loss_fn, batch=(x0, y0), aux_state=batch_stats)
    assert opt.aux_state is not None
    # running stats must have moved off their init
    mean0 = jax.tree.leaves(batch_stats)[0]
    mean1 = jax.tree.leaves(opt.aux_state)[0]
    assert float(jnp.abs(mean1 - mean0).sum()) > 0
    for _ in range(3):
        last, _ = opt.step(loss_fn=loss_fn, batch=(x0, y0),
                           aux_state=opt.aux_state)
    assert np.isfinite(float(last))


def test_syncbn_matches_global_batch_oracle(mesh8):
    """TRUE SyncBatchNorm: with ``bn_axis='data'``,
    a data-sharded forward inside shard_map must produce exactly the
    logits and updated running stats of one device seeing the global
    batch — torch DDP SyncBatchNorm semantics, realized as a psum in the
    flax BatchNorm instead of a separate wrapper module."""
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.models import ResNet18

    sync = ResNet18(num_classes=4, small_inputs=True, num_filters=8,
                    norm="batch", bn_axis="data")
    dense = ResNet18(num_classes=4, small_inputs=True, num_filters=8,
                     norm="batch")  # bn_axis=None: plain BN

    x = jax.random.normal(jax.random.key(1), (16, 8, 8, 3))
    # init under train=False: stats aren't computed, so no bound axis
    # is needed at init time
    variables = dense.init(jax.random.key(0), x[:1], train=False)
    params, stats = variables["params"], variables["batch_stats"]

    def fwd_sync(p, aux, x):
        return sync.apply(
            {"params": p, "batch_stats": aux}, x, train=True,
            mutable=["batch_stats"],
        )

    logits_sh, upd_sh = jax.jit(
        jax.shard_map(
            fwd_sync, mesh=mesh8,
            in_specs=(P(), P(), P("data")),
            out_specs=(P("data"), P()),
            check_vma=False,
        )
    )(params, stats, x)

    logits_ref, upd_ref = dense.apply(
        {"params": params, "batch_stats": stats}, x, train=True,
        mutable=["batch_stats"],
    )

    np.testing.assert_allclose(
        np.asarray(logits_sh), np.asarray(logits_ref), rtol=2e-5, atol=2e-5
    )
    for a, b in zip(
        jax.tree.leaves(upd_sh["batch_stats"]),
        jax.tree.leaves(upd_ref["batch_stats"]),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_bert_ulysses_attention_matches_full():
    """Ulysses-attention BERT == full-attention BERT on the same params
    (4 seq shards; tiny config's 4 heads give 1 head per device)."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    cfg_full = BertConfig.tiny()
    cfg_uly = BertConfig.tiny(attention="ulysses")
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0,
                                cfg_full.vocab_size)
    params = BertMLM(cfg_full).init(jax.random.key(0), tokens)
    ref = BertMLM(cfg_full).apply(params, tokens)

    l_local = 32 // 4

    def spmd(params, tokens):
        import jax.lax as lax
        offset = lax.axis_index("seq") * l_local
        return BertMLM(cfg_uly).apply(params, tokens, position_offset=offset)

    out = jax.jit(
        jax.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), P(None, "seq")),
            out_specs=P(None, "seq"),
            check_vma=False,
        )
    )(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_bert_unknown_attention_mode_raises():
    cfg = BertConfig.tiny(attention="ulises")  # typo must not run silently
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="unknown attention"):
        BertMLM(cfg).init(jax.random.key(0), tokens)


def test_scan_layers_matches_loop_layout():
    """scan_layers compiles ONE layer body instead of L unrolled copies
    (3x grad-compile cut measured at 12 layers); the math must be
    IDENTICAL, with stack_layer_params bridging the param layouts."""
    import dataclasses
    from pytorch_ps_mpi_tpu.models import stack_layer_params
    from pytorch_ps_mpi_tpu.models.gpt import GPTLM

    cfg = BertConfig.tiny(num_layers=4)
    toks = jax.random.randint(jax.random.key(0), (2, 64), 0, cfg.vocab_size)

    for make, c0 in [
        (BertMLM, cfg),
        (GPTLM, dataclasses.replace(cfg, causal=True)),
        # remat composes with the scanned body (nn.remat(_ScanBody))
        (BertMLM, dataclasses.replace(cfg, remat=True)),
    ]:
        cs = dataclasses.replace(c0, scan_layers=True)
        m, ms = make(c0), make(cs)
        p = m.init(jax.random.key(1), toks)
        ps = {"params": stack_layer_params(p["params"], c0.num_layers)}
        assert (jax.tree.structure(ps)
                == jax.tree.structure(ms.init(jax.random.key(1), toks)))
        o1, o2 = m.apply(p, toks), ms.apply(ps, toks)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=2e-5, rtol=2e-5)

    # gradients agree too (the trunk is under lax.scan in one layout)
    def loss(model, pr):
        return jnp.sum(model.apply(pr, toks).astype(jnp.float32) ** 2) * 1e-6

    cs = dataclasses.replace(cfg, scan_layers=True)
    m, ms = BertMLM(cfg), BertMLM(cs)
    p = m.init(jax.random.key(1), toks)
    ps = {"params": stack_layer_params(p["params"], cfg.num_layers)}
    g1 = jax.grad(lambda pr: loss(m, pr))(p)
    g2 = jax.grad(lambda pr: loss(ms, pr))(ps)
    g1s = {"params": stack_layer_params(g1["params"], cfg.num_layers)}
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5),
        g1s, g2,
    )


def test_bf16_logits_loss_matches_f32():
    """f32_logits=False keeps the [B,S,V] logits in compute dtype; the
    loss must do its reductions in f32 (fused upcast, no full-size f32
    array) and agree with the f32-logits twin to bf16 resolution."""
    import dataclasses
    from pytorch_ps_mpi_tpu.models.bert import target_log_likelihood
    from pytorch_ps_mpi_tpu.models.gpt import GPTLM, causal_lm_loss

    # the stable form IS log_softmax+gather for f32 inputs
    logits = jax.random.normal(jax.random.key(0), (4, 16, 64)) * 5.0
    tgt = jax.random.randint(jax.random.key(1), (4, 16), 0, 64)
    ref = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                              tgt[..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(np.asarray(target_log_likelihood(logits, tgt)),
                               np.asarray(ref), atol=1e-5, rtol=1e-5)

    # model-level: bf16 logits vs f32 logits, same params
    cfg = BertConfig.tiny(causal=True, dtype=jnp.bfloat16)
    cfg_bf = dataclasses.replace(cfg, f32_logits=False)
    toks = jax.random.randint(jax.random.key(2), (2, 32), 0, cfg.vocab_size)
    m32, mbf = GPTLM(cfg), GPTLM(cfg_bf)
    p = m32.init(jax.random.key(3), toks)
    out = mbf.apply(p, toks)
    assert out.dtype == jnp.bfloat16
    l32 = causal_lm_loss(m32.apply(p, toks), toks)
    lbf = causal_lm_loss(out, toks)
    np.testing.assert_allclose(float(l32), float(lbf), rtol=2e-2)

    # gradients flow and are finite through the bf16 head
    g = jax.grad(lambda pr: causal_lm_loss(mbf.apply(pr, toks), toks))(p)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))


def test_target_log_likelihood_gradient_matches_log_softmax():
    """The stop-gradient-max logsumexp must be GRADIENT-equivalent to
    plain log_softmax+gather for f32 inputs (the max term's gradient
    contribution cancels analytically; stop_gradient just prevents
    spurious max-index routing)."""
    from pytorch_ps_mpi_tpu.models.bert import target_log_likelihood

    logits = jax.random.normal(jax.random.key(0), (3, 8, 32)) * 4.0
    tgt = jax.random.randint(jax.random.key(1), (3, 8), 0, 32)

    def ours(lg):
        return jnp.sum(target_log_likelihood(lg, tgt))

    def ref(lg):
        lp = jax.nn.log_softmax(lg, axis=-1)
        return jnp.sum(jnp.take_along_axis(lp, tgt[..., None], -1))

    g1, g2 = jax.grad(ours)(logits), jax.grad(ref)(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=1e-6, rtol=1e-5)
