"""MPI_PS integration tests on the 8-device mesh — covering what the
reference left entirely untested (SURVEY §4: "ps.py entirely").

Key oracle: the distributed step must numerically equal a single-device
step on the summed gradient (the reference's semantics: sum over workers,
``ps.py:176``, then one fused update)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu import MPI_PS, Adam, SGD
from pytorch_ps_mpi_tpu.codecs import get_codec
from pytorch_ps_mpi_tpu.optim import SGDHyper, init_sgd_state, sgd_update


def make_params(seed=0):
    k = jax.random.key(seed)
    return {"w": jax.random.normal(k, (4, 3)), "b": jnp.zeros((3,))}


def quad_loss(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def batch_for(mesh, seed=1):
    k1, k2 = jax.random.split(jax.random.key(seed))
    n = 8 * 4
    return jax.random.normal(k1, (n, 4)), jax.random.normal(k2, (n, 3))


def test_step_returns_loss_and_schema(mesh8):
    opt = SGD(make_params(), mesh=mesh8, lr=0.1)
    loss, data = opt.step(loss_fn=quad_loss, batch=batch_for(mesh8))
    assert loss is not None and np.isfinite(float(loss))
    for key in [
        "code_wait", "iallgather_prepare_time", "isend_time", "comm_wait",
        "decode_time", "optim_step_time", "msg_bytes", "packaged_bytes",
    ]:
        assert key in data  # reference schema, ps.py:116-148


def test_distributed_equals_single_device_sum(mesh8):
    """Distributed sync step == local step on summed per-shard grads."""
    params = make_params()
    batch = batch_for(mesh8)
    opt = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9)
    opt.step(loss_fn=quad_loss, batch=batch)

    # oracle: per-worker grads on each 4-row shard, summed, one local step
    grads = [
        jax.grad(quad_loss)(params, (batch[0][i * 4:(i + 1) * 4], batch[1][i * 4:(i + 1) * 4]))
        for i in range(8)
    ]
    summed = jax.tree.map(lambda *g: sum(g), *grads)
    h = SGDHyper(lr=0.05, momentum=0.9)
    expected, _ = sgd_update(params, summed, init_sgd_state(params), h)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        opt.params,
        expected,
    )


def test_leader_mode_equals_allgather_mode(mesh8):
    params = make_params()
    batch = batch_for(mesh8)
    a = SGD(params, mesh=mesh8, lr=0.05, mode="allgather")
    b = SGD(params, mesh=mesh8, lr=0.05, mode="leader")
    a.step(loss_fn=quad_loss, batch=batch)
    b.step(loss_fn=quad_loss, batch=batch)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6),
        a.params,
        b.params,
    )


def test_grads_only_path(mesh8):
    params = make_params()
    opt = SGD(params, mesh=mesh8, lr=1.0)
    # worker r contributes grad = r for every element
    grads = jax.tree.map(
        lambda p: jnp.arange(8.0)[(...,) + (None,) * p.ndim] * jnp.ones((8,) + p.shape),
        params,
    )
    opt.step(grads=grads)
    total = sum(range(8))
    jax.tree.map(
        lambda new, old: np.testing.assert_allclose(
            np.asarray(new), np.asarray(old) - total, rtol=1e-6
        ),
        opt.params,
        params,
    )


def test_average_flag(mesh8):
    params = make_params()
    opt = SGD(params, mesh=mesh8, lr=1.0, average=True)
    grads = jax.tree.map(lambda p: jnp.ones((8,) + p.shape), params)
    opt.step(grads=grads)
    jax.tree.map(
        lambda new, old: np.testing.assert_allclose(
            np.asarray(new), np.asarray(old) - 1.0, rtol=1e-6
        ),
        opt.params,
        params,
    )


@pytest.mark.parametrize("codec_name,kw", [
    ("topk", {"fraction": 0.5}),
    ("blocktopk", {"fraction": 0.5, "block_size": 128}),
    ("blocktopk8", {"fraction": 0.5, "block_size": 128}),
    ("int8", {"use_pallas": False}),
    ("sign", {}),
    ("randomk", {"fraction": 0.5}),
    ("qsgd", {"levels": 16}),
    ("terngrad", {}),
    ("threshold", {"tau": 0.5, "max_fraction": 0.5}),
    ("threshold", {"tau": 1.0, "max_fraction": 0.5, "target_fraction": 0.25}),
])
def test_codec_training_converges(mesh8, codec_name, kw):
    """Loss decreases under every codec (convergence smoke; the reference's
    whole purpose — compressed training that still learns)."""
    params = make_params()
    opt = SGD(params, mesh=mesh8, lr=0.002, code=get_codec(codec_name, **kw))
    batch = batch_for(mesh8)
    first, _ = opt.step(loss_fn=quad_loss, batch=batch)
    for _ in range(20):
        last, _ = opt.step(loss_fn=quad_loss, batch=batch)
    assert float(last) < float(first)


def test_error_feedback_beats_plain_topk(mesh8):
    params = make_params()
    batch = batch_for(mesh8)

    def train(code):
        opt = SGD(make_params(), mesh=mesh8, lr=0.002, code=code)
        for _ in range(25):
            loss, _ = opt.step(loss_fn=quad_loss, batch=batch)
        return float(loss)

    plain = train(get_codec("topk", k=1))
    ef = train(get_codec("ef", inner_name="topk", k=1))
    assert ef <= plain * 1.05  # EF should not be worse


def test_adam_distributed_converges(mesh8):
    opt = Adam(make_params(), mesh=mesh8, lr=3e-2)
    batch = batch_for(mesh8)
    first, _ = opt.step(loss_fn=quad_loss, batch=batch)
    for _ in range(40):
        last, _ = opt.step(loss_fn=quad_loss, batch=batch)
    assert float(last) < float(first) * 0.75


def test_constructor_validation(mesh8):
    with pytest.raises(ValueError):
        MPI_PS(make_params(), optim="nope", mesh=mesh8)
    with pytest.raises(ValueError):
        MPI_PS(make_params(), mode="nope", mesh=mesh8)
    with pytest.raises(ValueError):
        SGD(make_params(), mesh=mesh8).step()


def test_instrumented_step_fills_schema(mesh8):
    """instrument=True must produce real per-stage wall times for the
    reference's timing keys (ps.py:116-148) and the same numerics."""
    params = make_params()
    batch = batch_for(mesh8)
    fused = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9)
    instr = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9, instrument=True)
    l1, _ = fused.step(loss_fn=quad_loss, batch=batch)
    l2, d = instr.step(loss_fn=quad_loss, batch=batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        fused.params, instr.params,
    )
    assert d["comm_wait"] > 0 and d["optim_step_time"] > 0 and d["grad_time"] > 0


def test_instrumented_step_with_codec(mesh8):
    params = make_params()
    batch = batch_for(mesh8)
    opt = SGD(params, mesh=mesh8, lr=0.01, instrument=True,
              code=get_codec("topk", fraction=0.5))
    first, d = opt.step(loss_fn=quad_loss, batch=batch)
    assert d["code_wait"] > 0 and d["decode_time"] > 0 and d["comm_wait"] > 0
    for _ in range(10):
        last, _ = opt.step(loss_fn=quad_loss, batch=batch)
    assert float(last) < float(first)


def test_run_steps_fused_scan_matches_loop(mesh8):
    """N steps under one lax.scan == N individual step() calls."""
    params = make_params()
    batch = batch_for(mesh8)
    n = 5
    batches = (
        jnp.broadcast_to(batch[0][None], (n,) + batch[0].shape),
        jnp.broadcast_to(batch[1][None], (n,) + batch[1].shape),
    )
    a = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9)
    losses, data = a.run_steps(quad_loss, batches)
    assert losses.shape == (n,) and data["n_steps"] == n

    b = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9)
    loop_losses = [float(b.step(loss_fn=quad_loss, batch=batch)[0]) for _ in range(n)]
    np.testing.assert_allclose(np.asarray(losses), loop_losses, rtol=1e-5)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6
        ),
        a.params, b.params,
    )


def test_powersgd_distributed_training(mesh8):
    params = make_params()
    batch = batch_for(mesh8)
    opt = SGD(params, mesh=mesh8, lr=0.002,
              code=get_codec("powersgd", rank=2, min_compression_elems=4))
    first, _ = opt.step(loss_fn=quad_loss, batch=batch)
    for _ in range(25):
        last, _ = opt.step(loss_fn=quad_loss, batch=batch)
    assert float(last) < float(first)


def test_instrumented_leader_mode_matches_fused(mesh8):
    """The instrumented update stage must include leader mode's broadcast
    (regression: it used to skip it)."""
    params = make_params()
    batch = batch_for(mesh8)
    fused = SGD(params, mesh=mesh8, lr=0.05, mode="leader")
    instr = SGD(params, mesh=mesh8, lr=0.05, mode="leader", instrument=True)
    fused.step(loss_fn=quad_loss, batch=batch)
    instr.step(loss_fn=quad_loss, batch=batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        fused.params, instr.params,
    )


def test_grads_only_with_aux_state_rejected(mesh8):
    opt = SGD(make_params(), mesh=mesh8, lr=0.1)
    grads = jax.tree.map(lambda p: jnp.ones((8,) + p.shape), make_params())
    with pytest.raises(NotImplementedError):
        opt.step(grads=grads, aux_state={"x": jnp.zeros(1)})
    # same contract under instrument: no forward pass, no new aux
    instr = SGD(make_params(), mesh=mesh8, lr=0.1, instrument=True)
    with pytest.raises(NotImplementedError):
        instr.step(grads=grads, aux_state={"x": jnp.zeros(1)})


def _aux_loss(p, aux, batch):
    """quad_loss with a running-mean aux channel (a minimal batch_stats
    stand-in: new aux must flow back per step)."""
    x, y = batch
    pred = x @ p["w"] + p["b"]
    new_aux = {"mean": 0.9 * aux["mean"] + 0.1 * jnp.mean(x)}
    return jnp.mean((pred - y) ** 2), new_aux


def test_instrumented_step_with_aux_state_matches_fused(mesh8):
    """instrument=True + aux_state works — staged aux
    pmean in the grad stage, same numerics as the fused path."""
    params = make_params()
    batch = batch_for(mesh8)
    aux0 = {"mean": jnp.zeros(())}

    fused = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9)
    instr = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9, instrument=True)
    l1, _ = fused.step(loss_fn=_aux_loss, batch=batch, aux_state=aux0)
    l2, d = instr.step(loss_fn=_aux_loss, batch=batch, aux_state=aux0)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    np.testing.assert_allclose(
        float(fused.aux_state["mean"]), float(instr.aux_state["mean"]), rtol=1e-6
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        fused.params, instr.params,
    )
    assert d["grad_time"] > 0 and d["comm_wait"] > 0 and d["optim_step_time"] > 0
    # second step continues from the returned aux
    l3, _ = instr.step(loss_fn=_aux_loss, batch=batch, aux_state=instr.aux_state)
    assert np.isfinite(float(l3))


def test_instrumented_step_accumulate_matches_plain(mesh8):
    """instrument=True + step_accumulate works — the
    accumulation scan is the grad stage (whole-wall + per-microbatch
    mean), encode/comm/update stages get real walls, numerics match."""
    params = make_params()
    k1, k2 = jax.random.split(jax.random.key(9))
    micro = (
        jax.random.normal(k1, (2, 32, 4)),
        jax.random.normal(k2, (2, 32, 3)),
    )

    plain = SGD(params, mesh=mesh8, lr=0.05, average=True)
    l1, _ = plain.step_accumulate(quad_loss, micro)

    instr = SGD(params, mesh=mesh8, lr=0.05, average=True, instrument=True)
    l2, d = instr.step_accumulate(quad_loss, micro)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        plain.params, instr.params,
    )
    assert d["accum_steps"] == 2
    assert d["grad_time"] > 0 and d["comm_wait"] > 0 and d["optim_step_time"] > 0
    assert d["grad_time_per_microbatch"] == pytest.approx(d["grad_time"] / 2)


def test_step_accumulate_matches_big_batch(mesh8):
    """k microbatches accumulated == one k-times-larger batch (with
    average=True both are mean gradients)."""
    params = make_params()
    k1, k2 = jax.random.split(jax.random.key(9))
    x = jax.random.normal(k1, (64, 4))
    y = jax.random.normal(k2, (64, 3))

    a = SGD(params, mesh=mesh8, lr=0.05, average=True)
    a.step(loss_fn=quad_loss, batch=(x, y))

    b = SGD(params, mesh=mesh8, lr=0.05, average=True)
    micro = (x.reshape(2, 32, 4), y.reshape(2, 32, 3))
    loss, data = b.step_accumulate(quad_loss, micro)
    assert data["accum_steps"] == 2
    jax.tree.map(
        lambda p, q: np.testing.assert_allclose(
            np.asarray(p), np.asarray(q), rtol=1e-5, atol=1e-6
        ),
        a.params, b.params,
    )


def test_leader_optimizer_state_is_sharded(mesh8):
    """ZeRO-1 property: leader mode partitions optimizer state (and the
    master parameter copy) 1/world per device instead of replicating
    it (a rank-0 PS lowering would redundantly update on every rank and
    broadcast identical values)."""
    params = make_params()
    opt = Adam(params, mesh=mesh8, lr=1e-3)
    assert opt.mode == "allgather"
    opt_leader = Adam(params, mesh=mesh8, lr=1e-3, mode="leader")

    def check_sharded(state):
        for p, m in zip(
            jax.tree.leaves(params), jax.tree.leaves(state.inner.exp_avg)
        ):
            n = int(np.prod(p.shape))
            shard_len = -(-n // 8)
            # moments cover the model once globally (vs once PER DEVICE
            # when replicated), partitioned over the mesh axis
            assert m.shape == (8, shard_len), (p.shape, m.shape)
            assert m.sharding.spec[0] == "data", m.sharding.spec
            assert len({s.device for s in m.addressable_shards}) == 8
            assert {
                int(np.prod(s.data.shape)) for s in m.addressable_shards
            } == {shard_len}
        # the master param copy is sharded the same way
        for sh in jax.tree.leaves(state.param_shards):
            assert sh.sharding.spec[0] == "data", sh.sharding.spec

    check_sharded(opt_leader.opt_state)
    # state stays sharded after a step
    opt_leader.step(loss_fn=quad_loss, batch=batch_for(mesh8))
    check_sharded(opt_leader.opt_state)


def test_leader_mode_adam_multi_step_equals_allgather(mesh8):
    """Sharded Adam (moments partitioned, bias correction, multi-step state
    carry) == replicated Adam."""
    params = make_params()
    batch = batch_for(mesh8)
    a = Adam(params, mesh=mesh8, lr=3e-2, mode="allgather")
    b = Adam(params, mesh=mesh8, lr=3e-2, mode="leader")
    for _ in range(5):
        la, _ = a.step(loss_fn=quad_loss, batch=batch)
        lb, _ = b.step(loss_fn=quad_loss, batch=batch)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-5)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-4, atol=1e-6
        ),
        a.params, b.params,
    )


def test_leader_mode_momentum_state_carry(mesh8):
    """SGD momentum buffers live sharded across steps in leader mode."""
    params = make_params()
    batch = batch_for(mesh8)
    a = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9, mode="allgather")
    b = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9, mode="leader")
    for _ in range(4):
        a.step(loss_fn=quad_loss, batch=batch)
        b.step(loss_fn=quad_loss, batch=batch)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-4, atol=1e-6
        ),
        a.params, b.params,
    )


def test_leader_mode_with_sparse_codec(mesh8):
    """Leader mode through the non-psum decode path (all_gather payloads →
    decode_sum → slice local shard → sharded update)."""
    params = make_params()
    batch = batch_for(mesh8)
    opt = SGD(params, mesh=mesh8, lr=0.002, mode="leader",
              code=get_codec("topk", fraction=0.5))
    first, _ = opt.step(loss_fn=quad_loss, batch=batch)
    for _ in range(20):
        last, _ = opt.step(loss_fn=quad_loss, batch=batch)
    assert float(last) < float(first)


def test_leader_mode_run_steps(mesh8):
    """Fused lax.scan multi-step works with sharded optimizer state."""
    params = make_params()
    batch = batch_for(mesh8)
    n = 4
    batches = (
        jnp.broadcast_to(batch[0][None], (n,) + batch[0].shape),
        jnp.broadcast_to(batch[1][None], (n,) + batch[1].shape),
    )
    a = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9, mode="leader")
    losses, _ = a.run_steps(quad_loss, batches)
    b = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9, mode="allgather")
    for _ in range(n):
        b.step(loss_fn=quad_loss, batch=batch)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-4, atol=1e-6
        ),
        a.params, b.params,
    )


def test_clip_norm_matches_manual_oracle(mesh8):
    """clip_norm clips the AGGREGATED gradient (torch clip_grad_norm_
    semantics): distributed step == local step on the manually clipped
    summed gradient."""
    params = make_params()
    batch = batch_for(mesh8)
    clip = 0.5  # far below the actual norm: clipping is active
    opt = SGD(params, mesh=mesh8, lr=0.05, clip_norm=clip)
    opt.step(loss_fn=quad_loss, batch=batch)

    grads = [
        jax.grad(quad_loss)(params, (batch[0][i * 4:(i + 1) * 4],
                                     batch[1][i * 4:(i + 1) * 4]))
        for i in range(8)
    ]
    summed = jax.tree.map(lambda *g: sum(g), *grads)
    gnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g))
                               for g in jax.tree.leaves(summed))))
    assert gnorm > clip  # the scenario is real
    clipped = jax.tree.map(lambda g: g * (clip / gnorm), summed)
    expected, _ = sgd_update(params, clipped, init_sgd_state(params),
                             SGDHyper(lr=0.05))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        opt.params, expected,
    )


def test_clip_norm_leader_equals_allgather(mesh8):
    """The ZeRO-1 fast path computes the clip norm from psum'd shard
    sum-squares; both topologies must clip identically (a shard-local
    norm would diverge silently)."""
    params = make_params()
    batch = batch_for(mesh8)

    def run(mode):
        opt = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9,
                  clip_norm=0.5, mode=mode)
        for _ in range(3):
            opt.step(loss_fn=quad_loss, batch=batch)
        return opt.params

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        run("allgather"), run("leader"),
    )


def test_clip_norm_inactive_when_above_gradient_norm(mesh8):
    """A clip threshold above the gradient norm is a no-op (scale
    min(1, c/norm) == 1)."""
    params = make_params()
    batch = batch_for(mesh8)

    def run(clip):
        opt = SGD(params, mesh=mesh8, lr=0.05, clip_norm=clip)
        opt.step(loss_fn=quad_loss, batch=batch)
        return opt.params

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        run(0.0), run(1e9),
    )


def test_clip_norm_negative_rejected():
    with pytest.raises(ValueError, match="clip_norm"):
        SGD(make_params(), lr=0.05, clip_norm=-1.0)


# -- leader-mode wire lowering + accounting ---------

def test_leader_dense_scatter_matches_allgather_numerics(mesh8):
    """int8 (wire ratio 4 < world 8) takes the dense_scatter lowering in
    leader mode: decode-own-payload + reduce_scatter. Numerics must
    equal the allgather topology (psum(decode(own)) == decode_sum of
    the gathered payloads, by decode_sum's definition)."""
    params = make_params()
    batch = batch_for(mesh8)
    a = SGD(params, mesh=mesh8, lr=0.05, code=get_codec("int8"))
    b = SGD(params, mesh=mesh8, lr=0.05, mode="leader",
            code=get_codec("int8"))
    la, da = a.step(loss_fn=quad_loss, batch=batch)
    lb, db = b.step(loss_fn=quad_loss, batch=batch)
    assert db["wire_lowering"] == "dense_scatter"
    assert da["wire_lowering"] == "allgather"
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-6)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6
        ),
        a.params, b.params,
    )


def test_leader_payload_gather_for_sparse_and_accounting(mesh8):
    """Strongly-compressing topk (ratio >= world) stays on
    payload_gather; the accounting makes the PS-topology trade visible:
    leader pays the param gather on top of the payload exchange
    (documented in _leader_lowering), while a weakly-compressing codec's
    dense_scatter receives less than its own payload_gather would.
    Params must be big enough that topk-1% actually compresses past 8x
    (on the 15-element make_params() the k>=1 floor makes topk WEAK and
    dense_scatter correctly wins — that regime is the int8 test)."""
    params = {"w": jax.random.normal(jax.random.key(0), (16, 8))}
    k1, k2 = jax.random.split(jax.random.key(1))
    batch = (jax.random.normal(k1, (64, 16)), jax.random.normal(k2, (64, 8)))

    def loss(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    a = SGD(params, mesh=mesh8, lr=0.05, code=get_codec("topk", fraction=0.01))
    b = SGD(params, mesh=mesh8, lr=0.05, mode="leader",
            code=get_codec("topk", fraction=0.01))
    la, da = a.step(loss_fn=loss, batch=batch)
    lb, db = b.step(loss_fn=loss, batch=batch)
    assert db["wire_lowering"] == "payload_gather"
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-6)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6
        ),
        a.params, b.params,
    )
    # analytic accounting: W=8, n = msg_bytes, p = packaged_bytes
    w, n, p = 8, da["msg_bytes"], da["packaged_bytes"]
    assert da["wire_bytes_per_worker"] == pytest.approx((w - 1) * p)
    assert db["wire_bytes_per_worker"] == pytest.approx(
        (w - 1) * p + (w - 1) / w * n
    )
    # the documented conclusion: for sparse codecs the leader topology
    # moves MORE than allgather (params must come back); the ZeRO-1 win
    # is update FLOPs + optimizer-state HBM, not wire
    assert db["wire_bytes_per_worker"] > da["wire_bytes_per_worker"]
    # weakly-compressing codec: dense_scatter receives less than its
    # payload_gather form would have
    c = SGD(params, mesh=mesh8, lr=0.05, mode="leader",
            code=get_codec("int8"))
    _, dc = c.step(loss_fn=loss, batch=batch)
    pg_equiv = (w - 1) * dc["packaged_bytes"] + (w - 1) / w * dc["msg_bytes"]
    assert dc["wire_bytes_per_worker"] < pg_equiv


def test_wire_accounting_psum_paths(mesh8):
    params = make_params()
    batch = batch_for(mesh8)
    w = 8
    a = SGD(params, mesh=mesh8, lr=0.05)  # identity: fused psum
    _, da = a.step(loss_fn=quad_loss, batch=batch)
    assert da["wire_lowering"] == "psum"
    assert da["wire_bytes_per_worker"] == pytest.approx(
        2 * (w - 1) / w * da["msg_bytes"]
    )
    b = SGD(params, mesh=mesh8, lr=0.05, mode="leader")
    _, db = b.step(loss_fn=quad_loss, batch=batch)
    assert db["wire_lowering"] == "psum_scatter"
    assert db["wire_bytes_per_worker"] == pytest.approx(
        (w - 1) / w * 2 * db["msg_bytes"]
    )
    # comm_dtype halves the collective's share of the bytes
    c = SGD(params, mesh=mesh8, lr=0.05, comm_dtype=jnp.bfloat16)
    _, dc = c.step(loss_fn=quad_loss, batch=batch)
    assert dc["wire_bytes_per_worker"] == pytest.approx(
        2 * (w - 1) / w * dc["msg_bytes"] / 2
    )


def test_wire_accounting_dtype_rules(mesh8):
    """The accounting must mirror the COMPILED collective's wire dtype
    rules: a non-psum codec's wire_dtype (f16) is excluded from on-chip
    collectives, so leader+f16 dense_scatter moves (and reports) full
    f32; comm_dtype=bf16 both narrows the dense scatter AND can flip the
    lowering decision in the ratio band where f32-dense loses to
    payloads but bf16-dense wins."""
    w = 8
    params = {"w": jax.random.normal(jax.random.key(0), (16, 8))}
    k1, k2 = jax.random.split(jax.random.key(1))
    batch = (jax.random.normal(k1, (64, 16)), jax.random.normal(k2, (64, 8)))

    def loss(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    # f16 codec (non-psum): scatter runs f32 (comm_dtype None) and the
    # report must say so — frac * (n + n), not frac * (n/2 + n)
    a = SGD(params, mesh=mesh8, lr=0.05, mode="leader",
            code=get_codec("f16"))
    _, da = a.step(loss_fn=loss, batch=batch)
    n = da["msg_bytes"]
    assert da["wire_lowering"] == "dense_scatter"
    assert da["wire_bytes_per_worker"] == pytest.approx((w - 1) / w * 2 * n)

    # topk with k=6 of 128 (p=48B, n=512B): f32 dense recv 448 == ...
    # payload recv 336 < 448 -> payload_gather without comm_dtype...
    b = SGD(params, mesh=mesh8, lr=0.05, mode="leader",
            code=get_codec("topk", k=6))
    _, db = b.step(loss_fn=loss, batch=batch)
    assert db["wire_lowering"] == "payload_gather"
    # ...but with a bf16 wire the dense scatter receives 224 < 336 and
    # the selector must flip
    c = SGD(params, mesh=mesh8, lr=0.05, mode="leader",
            code=get_codec("topk", k=6), comm_dtype=jnp.bfloat16)
    lc, dc = c.step(loss_fn=loss, batch=batch)
    assert dc["wire_lowering"] == "dense_scatter"
    assert dc["wire_bytes_per_worker"] == pytest.approx(
        (w - 1) / w * (n / 2 + n)
    )
    assert np.isfinite(float(lc))


def test_state_dict_checkpoint_resume_bit_exact(mesh8, tmp_path):
    """state_dict -> CheckpointManager -> load_state_dict on a FRESH
    optimizer resumes bit-exactly — including the EF codec's residual
    memory and the step rng (a stochastic codec diverges instantly if
    the rng doesn't survive)."""
    from pytorch_ps_mpi_tpu.utils.checkpoint import CheckpointManager

    params = make_params()
    batch = batch_for(mesh8)
    code = lambda: get_codec("ef", inner_name="randomk", fraction=0.3)
    a = SGD(params, mesh=mesh8, lr=0.02, code=code(), seed=3)
    for _ in range(4):
        a.step(loss_fn=quad_loss, batch=batch)

    ckpt = CheckpointManager(str(tmp_path / "ck"))
    ckpt.save(a._step_count, a.state_dict())

    # the uninterrupted run
    cont = [float(a.step(loss_fn=quad_loss, batch=batch)[0])
            for _ in range(3)]

    # fresh process stand-in: new optimizer, template from state_dict
    b = SGD(params, mesh=mesh8, lr=0.02, code=code(), seed=999)
    restored = ckpt.restore(b.state_dict())
    b.load_state_dict(restored)
    resumed = [float(b.step(loss_fn=quad_loss, batch=batch)[0])
               for _ in range(3)]

    np.testing.assert_array_equal(np.asarray(cont), np.asarray(resumed))
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)
        ),
        a.params, b.params,
    )
    # the EF residual itself round-tripped
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)
        ),
        a.codec_state, b.codec_state,
    )


def test_instrumented_wire_labels_match_staged_topology(mesh8):
    """instrument=True runs a staged pipeline whose collective topology
    differs from the fused lowering; the reported wire fields must
    describe what was MEASURED (a reader pairs them with comm_wait)."""
    params = make_params()
    batch = batch_for(mesh8)
    a = SGD(params, mesh=mesh8, lr=0.05, instrument=True,
            code=get_codec("int8"), mode="leader")
    _, da = a.step(loss_fn=quad_loss, batch=batch)
    w, n, p = 8, da["msg_bytes"], da["packaged_bytes"]
    assert da["wire_lowering"] == "payload_gather_staged"
    assert da["wire_bytes_per_worker"] == pytest.approx(
        (w - 1) * p + (w - 1) / w * n
    )
    b = SGD(params, mesh=mesh8, lr=0.05, instrument=True)
    _, db = b.step(loss_fn=quad_loss, batch=batch)
    assert db["wire_lowering"] == "psum_staged"
    assert db["wire_bytes_per_worker"] == pytest.approx(
        2 * (w - 1) / w * db["msg_bytes"]
    )


@pytest.mark.parametrize("mode,codec,kw,expect_lowering", [
    ("leader", "int8", {}, "dense_scatter"),
    ("leader", "blocktopk8", {"fraction": 0.05, "block_size": 128},
     "payload_gather"),
    ("allgather", "blocktopk8", {"fraction": 0.05, "block_size": 128},
     "allgather"),
])
def test_run_steps_composes_with_lowerings(mesh8, mode, codec, kw,
                                           expect_lowering):
    """The fused multi-step scan must equal the step loop under every
    aggregation lowering and the compressed-sparse codec."""
    params = {"w": jax.random.normal(jax.random.key(0), (16, 8))}

    def loss(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    k1, k2 = jax.random.split(jax.random.key(1))
    batch = (jax.random.normal(k1, (64, 16)), jax.random.normal(k2, (64, 8)))
    n = 4
    batches = (
        jnp.broadcast_to(batch[0][None], (n,) + batch[0].shape),
        jnp.broadcast_to(batch[1][None], (n,) + batch[1].shape),
    )
    a = SGD(params, mesh=mesh8, lr=0.05, mode=mode, code=get_codec(codec, **kw))
    a.run_steps(loss, batches)
    assert a._wire_accounting[0] == expect_lowering
    b = SGD(params, mesh=mesh8, lr=0.05, mode=mode, code=get_codec(codec, **kw))
    for _ in range(n):
        b.step(loss_fn=loss, batch=batch)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6
        ),
        a.params, b.params,
    )


# -- one step in flight --------------------------------------------------------

def batches_for(n, seed=7):
    return [batch_for(None, seed=seed + i) for i in range(n)]


def assert_bit_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


@pytest.fixture
def waits(monkeypatch):
    """What ``MPI_PS.step`` blocked on, in order: ``waits.seen`` holds
    every argument of ``jax.block_until_ready`` but None, beside whether
    it was ready already; the functions of ``waits.before`` are called
    with it ahead of the wait."""
    real = jax.block_until_ready

    def watched(x):
        if x is not None:
            watched.seen.append(
                (x, all(leaf.is_ready() for leaf in jax.tree.leaves(x))))
            for hook in watched.before:
                hook(x)
        return real(x)

    watched.seen, watched.before = [], []
    monkeypatch.setattr(jax, "block_until_ready", watched)
    return watched


@pytest.mark.parametrize("donate", [False, True])
def test_steps_in_flight_bit_identical_to_waiting_after_each(mesh8, donate):
    """The same program on the same inputs in the same order: N steps
    that each wait only for the step before give the losses and the
    state of N steps that each wait for themselves."""
    batches = batches_for(6)

    def run(wait_after_each):
        opt = Adam(make_params(), mesh=mesh8, lr=0.01, average=True,
                   code=get_codec("topk", fraction=0.5),
                   donate_buffers=donate)
        losses = []
        for b in batches:
            loss, _ = opt.step(loss_fn=quad_loss, batch=b)
            if wait_after_each:
                jax.block_until_ready((loss, opt.params, opt.opt_state))
            losses.append(loss)
        return ([np.asarray(l) for l in losses], opt.params,
                tuple(opt.opt_state), opt.codec_state,
                jax.random.key_data(opt._rng))

    assert_bit_equal(run(False), run(True))


def test_step_returns_while_it_runs_and_waits_for_the_step_before(waits):
    """The program of a step is held inside a host callback: ``step``
    returns all the same, and the NEXT ``step`` returns only once the
    held one has finished — it blocks on that step's loss, and the
    callback is let go the moment it does."""
    import threading

    from pytorch_ps_mpi_tpu.mesh import make_mesh

    gate = threading.Event()

    def hold(_):
        assert gate.wait(timeout=60), "nobody waited for the held step"
        return np.zeros((), np.float32)

    def held_loss(params, batch):
        loss = quad_loss(params, batch)
        return loss + jax.pure_callback(
            hold, jax.ShapeDtypeStruct((), jnp.float32),
            jax.lax.stop_gradient(loss))

    # two devices: each blocks one of the CPU client's threads in `hold`
    opt = SGD(make_params(), mesh=make_mesh(devices=jax.devices()[:2]),
              lr=0.1, average=True)
    b1, b2 = batches_for(2)
    waits.before.append(lambda x: gate.set())

    loss1, data1 = opt.step(loss_fn=held_loss, batch=b1)
    assert not loss1.is_ready() and not gate.is_set()
    assert waits.seen == [] and data1["host_ahead"] == 0.0

    loss2, data2 = opt.step(loss_fn=held_loss, batch=b2)
    assert loss1.is_ready()
    [(waited_for, was_ready)] = waits.seen
    assert waited_for is loss1 and not was_ready
    assert data2["host_ahead"] == 1.0  # step 1 still ran: the host led

    plain = SGD(make_params(), mesh=opt.mesh, lr=0.1, average=True)
    for b, loss in ((b1, loss1), (b2, loss2)):
        expected, _ = plain.step(loss_fn=quad_loss, batch=b)
        assert float(loss) == float(expected)
    assert_bit_equal(opt.params, plain.params)


@pytest.mark.parametrize("asks", ["numerics", "closure", "grads"])
def test_a_call_that_needs_its_own_step_waits_for_it(mesh8, waits, asks):
    """A numerics monitor and a ``closure`` read this
    step's values on the host, and the ``grads=`` path returns nothing to
    hold: each waits for its own outputs and leaves nothing in flight."""
    batch = batch_for(mesh8)
    opt = SGD(make_params(), mesh=mesh8, lr=0.1, average=True,
              numerics=asks == "numerics")
    twin = SGD(make_params(), mesh=mesh8, lr=0.1, average=True)
    twin.step(loss_fn=quad_loss, batch=batch)
    opt.step(loss_fn=quad_loss, batch=batch)  # leaves a step in flight
    expected, _ = twin.step(loss_fn=quad_loss, batch=batch)

    if asks == "grads":
        g = jax.tree.map(lambda p: jnp.ones((8,) + p.shape), opt.params)
        loss, data = opt.step(grads=g)
        assert loss is None
    elif asks == "closure":
        seen = []
        loss, data = opt.step(
            loss_fn=quad_loss, batch=batch,
            closure=lambda: seen.append(np.asarray(opt.params["w"])) or 7.0)
        assert loss == 7.0  # the closure's value, as before
        np.testing.assert_array_equal(seen[0], np.asarray(twin.params["w"]))
    else:
        loss, data = opt.step(loss_fn=quad_loss, batch=batch)
        assert float(loss) == float(expected)
        assert np.isfinite(data["grad_norm"]) and data["grad_norm"] > 0
    assert waits.seen[-1][0] is opt.params
    assert data["host_ahead"] == 0.0 and opt._in_flight is None
    if asks != "grads":
        assert_bit_equal(opt.params, twin.params)


# -- the one place a step program is handed to the compiler (PR 28) ----------

@pytest.mark.parametrize("bucket_mb, want", [
    (0.0, {"an_option": "true"}),
    (16.0, None),
], ids=["per-leaf", "flat-buckets"])
def test_jit_spmd_hands_jit_what_comms_decides(monkeypatch, mesh8, bucket_mb,
                                               want):
    """The decision is ``comms.async_allreduce_options``' alone, on this
    optimizer's mesh and aggregation axes (None on this backend), and
    ``jax.jit`` gets it as it is; a program whose exchange is in flat
    buckets is compiled without, whatever the mesh."""
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu import comms

    opt = Adam(make_params(), mesh=mesh8, lr=0.05, bucket_mb=bucket_mb)
    assert comms.async_allreduce_options(opt.mesh, opt._agg_axes) is None
    asked, seen, real = [], {}, jax.jit

    def decide(mesh, axes):
        asked.append((mesh, axes))
        return {"an_option": "true"}

    def spy(fn, **kw):
        seen.update(kw)
        return real(fn)

    monkeypatch.setattr(comms, "async_allreduce_options", decide)
    monkeypatch.setattr(jax, "jit", spy)
    opt._jit_spmd(lambda x: x, P(), P())
    assert asked == ([(opt.mesh, opt._agg_axes)] if want else [])
    assert seen["compiler_options"] == want


@pytest.mark.parametrize("n_devices, kw", [
    (1, dict(mode="allgather")),
    (4, dict(mode="allgather")),
    (4, dict(mode="allgather", code="int8")),
    (4, dict(mode="leader")),
], ids=["one-device", "identity", "int8", "leader"])
def test_the_first_step_is_the_program_of_every_later_one(n_devices, kw):
    """The state is placed as the step returns it (``_place_state``), so
    three steps trace and compile the step once: left on one device, or
    on the host, the first step was a program of its own."""
    from pytorch_ps_mpi_tpu.mesh import make_mesh

    mesh = make_mesh(devices=jax.devices()[:n_devices])
    code = get_codec(kw["code"]) if "code" in kw else None
    opt = Adam(jax.device_get(make_params()), mesh=mesh, lr=0.05,
               mode=kw["mode"], code=code, average=True)
    for leaf in jax.tree.leaves((opt.params, opt.opt_state, opt.codec_state)):
        assert leaf.sharding.mesh == mesh and leaf.committed
    for seed in (1, 2, 3):
        opt.step(loss_fn=quad_loss, batch=batch_for(mesh, seed=seed))
    assert [step._cache_size() for step in opt._compiled.values()] == [1]


def test_placing_the_state_copies_nothing_that_lies_there_already():
    from pytorch_ps_mpi_tpu.mesh import make_mesh

    params = make_params()
    opt = Adam(params, mesh=make_mesh(devices=jax.devices()[:1]), lr=0.05)
    for mine, theirs in zip(jax.tree.leaves(opt.params),
                            jax.tree.leaves(params)):
        assert (mine.unsafe_buffer_pointer()
                == theirs.unsafe_buffer_pointer())


def test_step_memory_analysis_counts_the_programs_collectives(mesh8):
    """Beside the memory analysis: the optimized program's collectives
    and how many of them are asynchronous (none off a TPU), with one
    recorder row each time they are read."""
    from pytorch_ps_mpi_tpu import telemetry

    rec = telemetry.configure()
    try:
        opt = Adam(make_params(), mesh=mesh8, lr=0.05)
        batch = batch_for(mesh8)
        out = opt.step_memory_analysis(quad_loss, batch)
        rows = [e for e in rec.events() if e["name"] == "ps.step_program"]
    finally:
        telemetry.disable()
    assert out["collectives"] >= 1 and out["async_collectives"] == 0
    assert len(rows) == 1
    assert rows[0]["attrs"] == {"collectives": out["collectives"],
                                "async_collectives": 0}
