"""MPI_PS driving model-parallel meshes.

The drop-in optimizer (reference role ``ps.py:54-59``) composed with
Megatron TP (``parallel/tp.py``) and GPipe PP (``parallel/pp.py``):
``param_specs`` keeps model-sharded leaves sharded through the whole
fused step while the codec pipeline aggregates each device's LOCAL
gradient over the data axis only. Every test here proves numerics
against either the dense single-device oracle or the pure-DP twin —
codec, leader/ZeRO-1, and clip modes included.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_ps_mpi_tpu.codecs import get_codec
from pytorch_ps_mpi_tpu.mesh import make_mesh
from pytorch_ps_mpi_tpu.parallel import tp
from pytorch_ps_mpi_tpu.parallel.pp import (
    init_stage_stack,
    pipeline_loss,
    stage_spec,
)
from pytorch_ps_mpi_tpu.ps import MPI_PS

D, F = 8, 32
TP = 4
DP = 2
GB = 8          # global batch
SEQ = 4


@pytest.fixture(scope="module")
def mesh_dp_tp():
    return make_mesh(shape=(DP, TP), axis_names=("data", "model"))


def _tp_setup():
    params = tp.init_tp_mlp(jax.random.key(0), D, F, tp=TP)
    x = jax.random.normal(jax.random.key(1), (GB, SEQ, D))
    y = jax.random.normal(jax.random.key(2), (GB, SEQ, D))
    return params, x, y


def _tp_loss_fn(p, batch):
    """Per-device LOCAL loss with a STATIC global normalizer: summing the
    local grads over 'data' (MPI_PS's sum semantics) then equals the
    dense global-mean-loss gradient."""
    xb, yb = batch
    pred = tp.tp_mlp(xb, p, "model", local_grads=True)
    return ((pred - yb) ** 2).sum() / (GB * SEQ * D)


def _dense_oracle_run(params, x, y, steps, lr, momentum=0.0, clip=0.0):
    """Single-device SGD on the dense-equivalent weights."""
    w = tp.dense_equivalent_mlp(params)

    def dense_loss(w):
        w1, b1, w2, b2 = w
        pred = jax.nn.gelu(x @ w1 + b1) @ w2 + b2
        return jnp.mean((pred - y) ** 2)

    buf = jax.tree.map(jnp.zeros_like, w)
    for i in range(steps):
        g = jax.grad(dense_loss)(w)
        if clip:
            norm = jnp.sqrt(sum(jnp.sum(l ** 2) for l in jax.tree.leaves(g)))
            g = jax.tree.map(
                lambda l: l * jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12)),
                g,
            )
        if momentum:
            buf = jax.tree.map(
                lambda b, l: l if i == 0 else momentum * b + l, buf, g
            )
            g = buf
        w = jax.tree.map(lambda p, l: p - lr * l, w, g)
    return w


def _assert_matches_dense(new_params, dense_w, rtol=1e-4, atol=1e-6):
    w1, b1, w2, b2 = dense_w
    got_w1 = jnp.concatenate([new_params["w1"][i] for i in range(TP)], axis=-1)
    np.testing.assert_allclose(np.asarray(got_w1), np.asarray(w1), rtol=rtol, atol=atol)
    got_b1 = jnp.concatenate([new_params["b1"][i] for i in range(TP)], axis=-1)
    np.testing.assert_allclose(np.asarray(got_b1), np.asarray(b1), rtol=rtol, atol=atol)
    got_w2 = jnp.concatenate([new_params["w2"][i] for i in range(TP)], axis=0)
    np.testing.assert_allclose(np.asarray(got_w2), np.asarray(w2), rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(new_params["b2"]), np.asarray(b2),
                               rtol=rtol, atol=atol)


def test_mpips_dp_tp_matches_dense_oracle(mesh_dp_tp):
    """3 momentum-SGD steps through the fused MPI_PS pipeline on a
    DP(2)xTP(4) mesh == 3 single-device steps on the dense weights."""
    params, x, y = _tp_setup()
    opt = MPI_PS(
        params, optim="sgd", lr=0.1, momentum=0.9,
        mesh=mesh_dp_tp, axis_name="data",
        param_specs=tp.tp_param_spec(params, "model"),
        batch_spec=P("data"),
    )
    for _ in range(3):
        loss, data = opt.step(loss_fn=_tp_loss_fn, batch=(x, y))
    dense_w = _dense_oracle_run(params, x, y, steps=3, lr=0.1, momentum=0.9)
    _assert_matches_dense(opt.params, dense_w)
    assert jnp.isfinite(loss)
    # reported loss is the SUM of local losses (static-global-normalizer
    # convention) == the dense global mean loss, not deflated by 1/W
    def dense_loss(w):
        w1, b1, w2, b2 = w
        pred = jax.nn.gelu(x @ w1 + b1) @ w2 + b2
        return jnp.mean((pred - y) ** 2)
    # loss returned is from the 3rd step: compare against dense after 2
    w2steps = _dense_oracle_run(params, x, y, steps=2, lr=0.1, momentum=0.9)
    np.testing.assert_allclose(
        float(loss), float(dense_loss(w2steps)), rtol=1e-4
    )
    # TP leaves really stay sharded over 'model'
    assert "model" in str(opt.params["w1"].sharding.spec)
    # wire accounting counts LOCAL shard bytes (TP leaves / TP)
    local = sum(
        int(np.prod(s)) for s in
        [(1, D, F // TP), (1, F // TP), (1, F // TP, D), (D,)]
    ) * 4
    assert data["wire_lowering"] == "psum"
    assert data["wire_bytes_per_worker"] == pytest.approx(
        2 * (DP - 1) / DP * local
    )


def test_mpips_step_equals_hand_rolled_vma_step(mesh_dp_tp):
    """MPI_PS's fused
    vma-unchecked step == the hand-rolled check_vma=True DP x TP step
    (the formulation test_tp.py::test_dp_tp_train_step_matches_single_device
    uses), leaf for leaf, over 2 steps."""
    from jax import lax

    params, x, y = _tp_setup()
    lr = 0.1

    # -- hand-rolled: check_vma=True autodiff inserts the grad psums ----
    def local_loss(p, xb, yb):
        pred = tp.tp_mlp(xb, p, "model")
        se = ((pred - yb) ** 2).sum()
        return lax.psum(se, "data") / (GB * SEQ * D)

    def spmd(p, xb, yb):
        loss, g = jax.value_and_grad(local_loss)(p, xb, yb)
        new_p = jax.tree.map(lambda w, gw: w - lr * gw, p, g)
        return new_p, loss

    spec = tp.tp_param_spec(params, "model")
    hand = jax.jit(
        jax.shard_map(
            spmd, mesh=mesh_dp_tp,
            in_specs=(spec, P("data"), P("data")),
            out_specs=(spec, P()), check_vma=True,
        )
    )
    hp = params
    for _ in range(2):
        hp, hloss = hand(hp, x, y)

    # -- MPI_PS -------------------------------------------------------
    opt = MPI_PS(
        params, optim="sgd", lr=lr,
        mesh=mesh_dp_tp, axis_name="data",
        param_specs=spec, batch_spec=P("data"),
    )
    for _ in range(2):
        loss, _ = opt.step(loss_fn=_tp_loss_fn, batch=(x, y))

    for a, b in zip(jax.tree.leaves(opt.params), jax.tree.leaves(hp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_mpips_dp_tp_payload_codec_exact(mesh_dp_tp):
    """topk(fraction=1.0) routes through the payload all_gather +
    decode_sum path (supports_psum=False) but keeps every element —
    numerics must still equal the dense oracle, proving the non-psum
    collective path composes with TP sharding."""
    params, x, y = _tp_setup()
    code = get_codec("topk", fraction=1.0)
    assert not code.supports_psum
    opt = MPI_PS(
        params, optim="sgd", lr=0.1, code=code,
        mesh=mesh_dp_tp, axis_name="data",
        param_specs=tp.tp_param_spec(params, "model"),
        batch_spec=P("data"),
    )
    for _ in range(2):
        loss, data = opt.step(loss_fn=_tp_loss_fn, batch=(x, y))
    dense_w = _dense_oracle_run(params, x, y, steps=2, lr=0.1)
    _assert_matches_dense(opt.params, dense_w, rtol=2e-4, atol=1e-5)
    assert data["wire_lowering"] == "allgather"


def test_mpips_dp_tp_leader_equals_allgather(mesh_dp_tp):
    """ZeRO-1 leader mode on the DPxTP mesh: numerics equal to the
    allgather twin over 3 Adam steps, optimizer state jointly sharded
    P(('data', 'model'))."""
    params, x, y = _tp_setup()
    kw = dict(
        optim="adam", lr=1e-2, mesh=mesh_dp_tp, axis_name="data",
        param_specs=tp.tp_param_spec(params, "model"),
        batch_spec=P("data"),
    )
    leader = MPI_PS(params, mode="leader", **kw)
    allg = MPI_PS(params, mode="allgather", **kw)
    for _ in range(3):
        leader.step(loss_fn=_tp_loss_fn, batch=(x, y))
        allg.step(loss_fn=_tp_loss_fn, batch=(x, y))
    for a, b in zip(jax.tree.leaves(leader.params), jax.tree.leaves(allg.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    assert leader._leader_lowering() == "psum_scatter"
    # the ZeRO shards of a TP leaf are jointly sharded over both axes
    sh = leader.opt_state.param_shards["w1"].sharding.spec
    assert "data" in str(sh) and "model" in str(sh)


def test_mpips_dp_tp_clip_norm_matches_dense(mesh_dp_tp):
    """Global-norm clipping counts each model shard once and each
    replicated leaf once — equals dense clipping."""
    params, x, y = _tp_setup()
    clip = 0.05  # tight enough that clipping definitely triggers
    opt = MPI_PS(
        params, optim="sgd", lr=0.1, clip_norm=clip,
        mesh=mesh_dp_tp, axis_name="data",
        param_specs=tp.tp_param_spec(params, "model"),
        batch_spec=P("data"),
    )
    for _ in range(2):
        opt.step(loss_fn=_tp_loss_fn, batch=(x, y))
    dense_w = _dense_oracle_run(params, x, y, steps=2, lr=0.1, clip=clip)
    _assert_matches_dense(opt.params, dense_w)


def test_mpips_dp_tp_leader_clip_matches_dense(mesh_dp_tp):
    """Clip inside the ZeRO-1 psum_scatter path on the TP mesh: shard
    sum-squares psum over 'data' AND each leaf's model axes."""
    params, x, y = _tp_setup()
    clip = 0.05
    opt = MPI_PS(
        params, optim="sgd", lr=0.1, clip_norm=clip, mode="leader",
        mesh=mesh_dp_tp, axis_name="data",
        param_specs=tp.tp_param_spec(params, "model"),
        batch_spec=P("data"),
    )
    for _ in range(2):
        opt.step(loss_fn=_tp_loss_fn, batch=(x, y))
    dense_w = _dense_oracle_run(params, x, y, steps=2, lr=0.1, clip=clip)
    _assert_matches_dense(opt.params, dense_w)


def test_mpips_dp_tp_bf16_codec_runs(mesh_dp_tp):
    """The psum fast path with a wire-narrowing cast codec on the TP
    mesh: converges and stays close to the dense oracle at bf16
    tolerance."""
    params, x, y = _tp_setup()
    opt = MPI_PS(
        params, optim="sgd", lr=0.1, code=get_codec("bf16"),
        mesh=mesh_dp_tp, axis_name="data",
        param_specs=tp.tp_param_spec(params, "model"),
        batch_spec=P("data"),
    )
    loss0, _ = opt.step(loss_fn=_tp_loss_fn, batch=(x, y))
    for _ in range(4):
        loss, _ = opt.step(loss_fn=_tp_loss_fn, batch=(x, y))
    assert float(loss) < float(loss0)
    dense_w = _dense_oracle_run(params, x, y, steps=5, lr=0.1)
    _assert_matches_dense(opt.params, dense_w, rtol=0.05, atol=2e-3)


def test_mpips_dp_tp_error_feedback_state_is_sharded(mesh_dp_tp):
    """EF(topk) on the TP mesh: codec state leaves are jointly sharded
    over (data, model) for TP params, evolve per shard, and training
    converges."""
    params, x, y = _tp_setup()
    code = get_codec("ef", inner=get_codec("topk", fraction=0.25))
    opt = MPI_PS(
        params, optim="sgd", lr=0.1, code=code,
        mesh=mesh_dp_tp, axis_name="data",
        param_specs=tp.tp_param_spec(params, "model"),
        batch_spec=P("data"),
    )
    state0 = jax.tree.map(lambda v: np.asarray(v), opt.codec_state)
    loss0, _ = opt.step(loss_fn=_tp_loss_fn, batch=(x, y))
    # TP leaf state: leading axis DP*TP, jointly sharded
    lead = jax.tree.leaves(opt.codec_state["w1"])[0]
    assert lead.shape[0] == DP * TP
    assert "model" in str(lead.sharding.spec)
    # replicated leaf state: leading axis DP only
    lead_b2 = jax.tree.leaves(opt.codec_state["b2"])[0]
    assert lead_b2.shape[0] == DP
    for _ in range(5):
        loss, _ = opt.step(loss_fn=_tp_loss_fn, batch=(x, y))
    assert float(loss) < float(loss0)
    # the error memory actually evolved
    moved = any(
        not np.allclose(np.asarray(a), b)
        for a, b in zip(jax.tree.leaves(opt.codec_state),
                        jax.tree.leaves(state0))
    )
    assert moved


def test_mpips_dp_tp_run_steps(mesh_dp_tp):
    """The scan'd multi-step path with param_specs: losses decrease and
    TP leaves stay sharded."""
    params, x, y = _tp_setup()
    opt = MPI_PS(
        params, optim="sgd", lr=0.1,
        mesh=mesh_dp_tp, axis_name="data",
        param_specs=tp.tp_param_spec(params, "model"),
        batch_spec=P("data"),
    )
    n = 6
    batches = (
        jnp.broadcast_to(x[None], (n,) + x.shape),
        jnp.broadcast_to(y[None], (n,) + y.shape),
    )
    losses, data = opt.run_steps(_tp_loss_fn, batches)
    assert float(losses[-1]) < float(losses[0])
    assert "model" in str(opt.params["w1"].sharding.spec)


def test_mpips_param_specs_guards(mesh_dp_tp):
    params, _, _ = _tp_setup()
    specs = tp.tp_param_spec(params, "model")
    # sharding over an aggregation axis is the EP layout — legal for
    # allgather (that leaf simply aggregates over the remaining axes),
    # but leader/ZeRO-1 requires uniform aggregation
    with pytest.raises(ValueError, match="leader"):
        MPI_PS(params, mesh=mesh_dp_tp, axis_name="model",
               param_specs=specs, mode="leader")
    with pytest.raises(NotImplementedError, match="instrument"):
        MPI_PS(params, mesh=mesh_dp_tp, axis_name="data",
               param_specs=specs, instrument=True)
    opt = MPI_PS(params, mesh=mesh_dp_tp, axis_name="data",
                 param_specs=specs)
    with pytest.raises(NotImplementedError, match="grads-only"):
        opt.step(grads=jax.tree.map(lambda p: p[None], params))
    # leader mode demands the leading-shard-axis convention
    bad = jax.tree.map(lambda _: P(), params)
    bad["w1"] = P(None, "model")
    with pytest.raises(ValueError, match="leading-shard-axis"):
        MPI_PS(params, mesh=mesh_dp_tp, axis_name="data",
               param_specs=bad, mode="leader")


def test_mpips_dp_ep_matches_dense_oracle():
    """MPI_PS drives a DP(2)xEP(4) mesh with the GShard token layout:
    tokens sharded jointly over ('data', 'expert'), expert weights over
    'expert'. Per-leaf aggregation: expert-sharded leaves aggregate over
    'data' only (their shard gradient over 'expert' is already
    complete); the replicated router aggregates over BOTH axes (the
    expert axis carries extra tokens). == dense top-1 oracle."""
    from pytorch_ps_mpi_tpu.parallel.ep import (
        init_moe, moe_apply, moe_dense_oracle, moe_spec,
    )

    dp, ep = 2, 4
    mesh = make_mesh(shape=(dp, ep), axis_names=("data", "expert"))
    d, f, n_exp, n_tok = 8, 16, 8, 32  # 4 tokens per device

    params = init_moe(jax.random.key(6), d, f, n_exp)
    x = jax.random.normal(jax.random.key(7), (n_tok, d))
    tgt = jax.random.normal(jax.random.key(8), (n_tok, d))

    def loss_fn(p, batch):
        xb, yb = batch
        out = moe_apply(xb, p, "expert", capacity=n_tok)
        return jnp.sum((out - yb) ** 2) / (n_tok * d)

    opt = MPI_PS(
        params, optim="sgd", lr=0.1,
        mesh=mesh, axis_name=("data", "expert"),
        param_specs=moe_spec(params, "expert"),
        batch_spec=P(("data", "expert")),
    )
    for _ in range(2):
        loss, _ = opt.step(loss_fn=loss_fn, batch=(x, tgt))
    assert jnp.isfinite(loss)

    def dense_loss(p):
        out = moe_dense_oracle(x, p)
        return jnp.mean((out - tgt) ** 2)

    w = params
    for _ in range(2):
        g = jax.grad(dense_loss)(w)
        w = jax.tree.map(lambda a, b: a - 0.1 * b, w, g)
    for a, b in zip(jax.tree.leaves(opt.params), jax.tree.leaves(w)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
    assert "expert" in str(opt.params["w1"].sharding.spec)


def _3d_setup(sp: str = "ring"):
    """Shared DP(2) x SP(2) x TP(2) toy transformer for the 3-D tests:
    returns (mesh, params, specs, tokens, loss_fn) — one definition so
    the ring/ulysses/leader variants can never silently diverge."""
    from jax import lax

    mesh = make_mesh(shape=(2, 2, 2), axis_names=("data", "seq", "model"))
    vocab, d, heads, ffn = 64, 16, 4, 32
    seq_len, batch = 16, 4
    l_local = seq_len // 2

    k = jax.random.key(0)
    k_emb, k_pos, k_attn, k_mlp, k_head, k_tok = jax.random.split(k, 6)
    params = {
        "emb": 0.02 * jax.random.normal(k_emb, (vocab, d)),
        "pos": 0.02 * jax.random.normal(k_pos, (seq_len, d)),
        "attn": tp.init_tp_attention(k_attn, d, heads, 2),
        "mlp": tp.init_tp_mlp(k_mlp, d, ffn, 2),
        "head": 0.02 * jax.random.normal(k_head, (d, vocab)),
    }
    specs = {
        "emb": P(), "pos": P(),
        "attn": tp.tp_param_spec(params["attn"], "model"),
        "mlp": tp.tp_param_spec(params["mlp"], "model"),
        "head": P(),
    }
    tokens = jax.random.randint(k_tok, (batch, seq_len), 1, vocab)

    def loss_fn(p, toks):
        offset = lax.axis_index("seq") * l_local
        x = p["emb"][toks] + p["pos"][offset + jnp.arange(l_local)][None]
        x = x + tp.tp_self_attention(
            x, p["attn"], "model", seq_axis="seq", causal=False,
            sp=sp, local_grads=True,
        )
        x = x + tp.tp_mlp(x, p["mlp"], "model", local_grads=True)
        logits = x @ p["head"]
        ll = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(ll, toks[..., None], axis=-1)[..., 0]
        return -ll.sum() / (batch * seq_len)  # static global normalizer

    return mesh, params, specs, tokens, loss_fn


def test_mpips_3d_dp_sp_tp_runs():
    """The full 3-D composition the dryrun validates, as a regression
    test: DP(2) x SP(2, ring attention) x TP(2) transformer block under
    MPI_PS with tuple aggregation axes ('data', 'seq') and a
    wire-narrowing codec. Loss must decrease and TP leaves stay
    sharded."""
    mesh, params, specs, tokens, loss_fn = _3d_setup()
    opt = MPI_PS(
        params, optim="sgd", lr=0.5, code=get_codec("bf16"),
        mesh=mesh, axis_name=("data", "seq"),
        param_specs=specs, batch_spec=P("data", "seq"),
    )
    loss0, data = opt.step(loss_fn=loss_fn, batch=tokens)
    for _ in range(5):
        loss, _ = opt.step(loss_fn=loss_fn, batch=tokens)
    assert float(loss) < float(loss0)
    assert "model" in str(opt.params["mlp"]["w1"].sharding.spec)
    assert data["wire_lowering"] == "psum"


def test_mpips_model_parallel_checkpoint_resume(mesh_dp_tp, tmp_path):
    """Bit-exact resume of a model-parallel MPI_PS: TP-sharded params,
    momentum state, and EF codec state (jointly sharded over
    (data, model)) survive a save/restore round trip — the restored
    optimizer continues EXACTLY where the original would have."""
    from pytorch_ps_mpi_tpu.utils.checkpoint import CheckpointManager

    params, x, y = _tp_setup()

    def mk():
        return MPI_PS(
            params, optim="sgd", lr=0.1, momentum=0.9,
            code=get_codec("ef", inner=get_codec("topk", fraction=0.25)),
            mesh=mesh_dp_tp, axis_name="data",
            param_specs=tp.tp_param_spec(params, "model"),
            batch_spec=P("data"),
        )

    opt = mk()
    for _ in range(3):
        opt.step(loss_fn=_tp_loss_fn, batch=(x, y))
    ckpt = CheckpointManager(str(tmp_path / "mp_ckpt"))
    ckpt.save(opt._step_count, opt.state_dict())

    # original runs 2 more steps — the ground truth
    for _ in range(2):
        opt.step(loss_fn=_tp_loss_fn, batch=(x, y))

    fresh = mk()
    restored = ckpt.restore(fresh.state_dict())
    fresh.load_state_dict(restored)
    assert fresh._step_count == 3
    for _ in range(2):
        fresh.step(loss_fn=_tp_loss_fn, batch=(x, y))

    for a, b in zip(jax.tree.leaves(opt.params), jax.tree.leaves(fresh.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(opt.codec_state),
                    jax.tree.leaves(fresh.codec_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the resumed TP leaves are still sharded over 'model'
    assert "model" in str(fresh.params["w1"].sharding.spec)


def test_mpips_model_parallel_numpy_fallback_restore(mesh_dp_tp, tmp_path):
    """The npz fallback path (use_orbax=False): restored leaves come
    back as host arrays with no sharding — _decommit_restored must let
    the next fused step reshard them, and training must continue
    bit-exactly on the TP mesh."""
    from pytorch_ps_mpi_tpu.utils.checkpoint import CheckpointManager

    params, x, y = _tp_setup()

    def mk():
        return MPI_PS(
            params, optim="sgd", lr=0.1, momentum=0.9,
            mesh=mesh_dp_tp, axis_name="data",
            param_specs=tp.tp_param_spec(params, "model"),
            batch_spec=P("data"),
        )

    opt = mk()
    for _ in range(2):
        opt.step(loss_fn=_tp_loss_fn, batch=(x, y))
    ckpt = CheckpointManager(str(tmp_path / "npz"), use_orbax=False)
    ckpt.save(opt._step_count, opt.state_dict())
    for _ in range(2):
        opt.step(loss_fn=_tp_loss_fn, batch=(x, y))

    fresh = mk()
    fresh.load_state_dict(ckpt.restore(fresh.state_dict()))
    for _ in range(2):
        fresh.step(loss_fn=_tp_loss_fn, batch=(x, y))
    for a, b in zip(jax.tree.leaves(opt.params), jax.tree.leaves(fresh.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    assert "model" in str(fresh.params["w1"].sharding.spec)


def test_mpips_leader_model_parallel_checkpoint_resume(mesh_dp_tp, tmp_path):
    """Same round trip for leader (ZeRO-1) mode: the jointly-sharded
    [data*model, shard_len] master-param/optimizer shards restore
    bit-exactly."""
    from pytorch_ps_mpi_tpu.utils.checkpoint import CheckpointManager

    params, x, y = _tp_setup()

    def mk():
        return MPI_PS(
            params, optim="adam", lr=1e-2, mode="leader",
            mesh=mesh_dp_tp, axis_name="data",
            param_specs=tp.tp_param_spec(params, "model"),
            batch_spec=P("data"),
        )

    opt = mk()
    for _ in range(3):
        opt.step(loss_fn=_tp_loss_fn, batch=(x, y))
    ckpt = CheckpointManager(str(tmp_path / "leader_ckpt"))
    ckpt.save(opt._step_count, opt.state_dict())
    for _ in range(2):
        opt.step(loss_fn=_tp_loss_fn, batch=(x, y))

    fresh = mk()
    fresh.load_state_dict(ckpt.restore(fresh.state_dict()))
    for _ in range(2):
        fresh.step(loss_fn=_tp_loss_fn, batch=(x, y))

    for a, b in zip(jax.tree.leaves(opt.params), jax.tree.leaves(fresh.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(tuple(opt.opt_state)),
                    jax.tree.leaves(tuple(fresh.opt_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_drives_model_parallel_optimizer(mesh_dp_tp, tmp_path):
    """The Trainer loop (fit + scan chunks + checkpoint/resume) composes
    with a model-parallel MPI_PS unchanged — the training-loop layer
    inherits TP sharding through the optimizer it owns."""
    from pytorch_ps_mpi_tpu.trainer import Trainer

    params, x, y = _tp_setup()

    def batches():
        while True:
            yield (x, y)

    def mk():
        opt = MPI_PS(
            params, optim="sgd", lr=0.1, momentum=0.9,
            mesh=mesh_dp_tp, axis_name="data",
            param_specs=tp.tp_param_spec(params, "model"),
            batch_spec=P("data"),
        )
        return Trainer(opt, _tp_loss_fn, checkpoint_dir=str(tmp_path / "t"),
                       checkpoint_every=4, scan_chunk=2)

    t = mk()
    # global initial loss via the dense equivalent (the TP forward needs
    # a bound 'model' axis, so it can't run outside shard_map)
    w1, b1, w2, b2 = tp.dense_equivalent_mlp(params)
    loss0 = float(jnp.mean((jax.nn.gelu(x @ w1 + b1) @ w2 + b2 - y) ** 2))
    out = t.fit(batches(), num_steps=6)
    assert out["final_loss"] < loss0, (out["final_loss"], loss0)
    assert "model" in str(t.opt.params["w1"].sharding.spec)

    # resume picks up the saved sharded state and continues
    t2 = mk()
    assert t2.maybe_restore()
    assert t2.step_count == 6
    for a, b in zip(jax.tree.leaves(t.opt.params), jax.tree.leaves(t2.opt.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out2 = t2.fit(batches(), num_steps=2)
    assert np.isfinite(out2["final_loss"])


def test_mpips_dp_tp_accumulate_matches_plain_step(mesh_dp_tp):
    """step_accumulate on the TP mesh: two identical microbatches mean
    to exactly one plain step's gradient — params must match the
    non-accum twin bit-for-bit shapes-wise and numerically."""
    params, x, y = _tp_setup()
    kw = dict(
        optim="sgd", lr=0.1, mesh=mesh_dp_tp, axis_name="data",
        param_specs=tp.tp_param_spec(params, "model"),
        batch_spec=P("data"),
    )
    plain = MPI_PS(params, **kw)
    accum = MPI_PS(params, **kw)
    plain.step(loss_fn=_tp_loss_fn, batch=(x, y))
    micro = (
        jnp.broadcast_to(x[None], (2,) + x.shape),
        jnp.broadcast_to(y[None], (2,) + y.shape),
    )
    loss, data = accum.step_accumulate(_tp_loss_fn, micro)
    assert data["accum_steps"] == 2.0
    for a, b in zip(jax.tree.leaves(plain.params), jax.tree.leaves(accum.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    assert "model" in str(accum.params["w1"].sharding.spec)


def test_mpips_3d_ulysses_equals_ring_twin():
    """The DP x SP x TP composition with the ALL-TO-ALL sequence-
    parallel design (Ulysses) under MPI_PS: both SP designs compute
    IDENTICAL full attention, so 3 optimizer steps through each must
    agree leaf-for-leaf — the numerics oracle for the ulysses +
    local_grads path (all_to_all's transpose is the reverse
    all_to_all). heads=4, tp=2 -> 2 local heads; seq size 2 divides
    them."""
    def run(sp):
        mesh, params, specs, tokens, loss_fn = _3d_setup(sp)
        opt = MPI_PS(
            params, optim="sgd", lr=0.5,
            mesh=mesh, axis_name=("data", "seq"),
            param_specs=specs, batch_spec=P("data", "seq"),
        )
        for _ in range(3):
            loss, _ = opt.step(loss_fn=loss_fn, batch=tokens)
        return opt.params, float(loss)

    ring_p, ring_loss = run("ring")
    uly_p, uly_loss = run("ulysses")
    np.testing.assert_allclose(ring_loss, uly_loss, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ring_p), jax.tree.leaves(uly_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
    assert "model" in str(uly_p["mlp"]["w1"].sharding.spec)


def test_mpips_3d_leader_equals_allgather():
    """Leader (ZeRO-1) mode with TUPLE aggregation axes ('data', 'seq')
    on the 3-D mesh: the psum_scatter/all_gather pair linearizes the
    joint axes exactly like the host-side shard build, so numerics must
    equal the allgather twin (the property examples/train_tp.py's
    --mode leader --sp 2 path rides on)."""
    mesh, params, specs, tokens, loss_fn = _3d_setup()

    def mk(mode):
        return MPI_PS(
            params, optim="adam", lr=1e-2, mode=mode,
            mesh=mesh, axis_name=("data", "seq"),
            param_specs=specs, batch_spec=P("data", "seq"),
        )

    leader, allg = mk("leader"), mk("allgather")
    for _ in range(3):
        l_loss, _ = leader.step(loss_fn=loss_fn, batch=tokens)
        a_loss, _ = allg.step(loss_fn=loss_fn, batch=tokens)
    np.testing.assert_allclose(float(l_loss), float(a_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(leader.params), jax.tree.leaves(allg.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_mpips_dp_pp_matches_sequential_dense():
    """MPI_PS drives a DP(2)xPP(4) mesh: GPipe pipeline_loss with
    local_grads=True under the fused vma-unchecked step == single-device
    sequential stage composition on the full batch."""
    pipe, dp = 4, 2
    mesh = make_mesh(shape=(dp, pipe), axis_names=("data", "pipe"))
    d, m, mb = 8, 4, 4  # microbatches per device after 'data' split

    def stage_fn(p, x):
        return x + jax.nn.gelu(x @ p["w1"]) @ p["w2"]

    def init_one(key):
        k1, k2 = jax.random.split(key)
        return {
            "w1": 0.1 * jax.random.normal(k1, (d, 2 * d), jnp.float32),
            "w2": 0.1 * jax.random.normal(k2, (2 * d, d), jnp.float32),
        }

    stacked = init_stage_stack(jax.random.key(3), pipe, init_one)
    x_mb = jax.random.normal(jax.random.key(4), (m, dp * mb, d))
    y_mb = jax.random.normal(jax.random.key(5), (m, dp * mb, d))

    def loss_fn(p, batch):
        xb, yb = batch  # [m, mb, d] local microbatches
        # local mean, scaled so the data-sum equals the global mean
        return pipeline_loss(
            p, xb, yb, stage_fn, lambda o, t: jnp.mean((o - t) ** 2),
            "pipe", local_grads=True,
        ) / dp

    opt = MPI_PS(
        stacked, optim="sgd", lr=0.1,
        mesh=mesh, axis_name="data",
        param_specs=stage_spec(stacked, "pipe"),
        batch_spec=P(None, "data"),
    )
    for _ in range(2):
        loss, _ = opt.step(loss_fn=loss_fn, batch=(x_mb, y_mb))

    # dense sequential oracle
    stages = [jax.tree.map(lambda v: v[i], stacked) for i in range(pipe)]

    def dense_loss(stages):
        def apply(x):
            for sp in stages:
                x = stage_fn(sp, x)
            return x
        outs = jax.vmap(apply)(x_mb)
        return jnp.mean(jax.vmap(lambda o, t: jnp.mean((o - t) ** 2))(outs, y_mb))

    w = stages
    for _ in range(2):
        g = jax.grad(dense_loss)(w)
        w = jax.tree.map(lambda p, l: p - 0.1 * l, w, g)

    for i in range(pipe):
        got = jax.tree.map(lambda v: v[i], opt.params)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(w[i])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)
    assert float(jnp.isfinite(loss))
    assert "pipe" in str(opt.params["w1"].sharding.spec)


def test_adafactor_tp_matches_global_oracle(mesh_dp_tp):
    """Model-parallel Adafactor (factored dims unsharded; scalar
    reductions pmean'd over the model axes) must equal the plain
    single-device adafactor_update on the GLOBAL stacked leaves, step
    for step — the exact-decomposability claim, proven."""
    from pytorch_ps_mpi_tpu.optim import (
        AdafactorHyper,
        adafactor_update,
        init_adafactor_state,
    )

    N, M = 256, 160  # both >= the factoring threshold
    kp = jax.random.key(0)
    params = {
        "w": jax.random.normal(kp, (TP, N, M)) * 0.1,       # P('model')
        "b": jax.random.normal(jax.random.fold_in(kp, 1), (TP, M)) * 0.1,
    }
    specs = {"w": P("model"), "b": P("model")}
    x = jax.random.normal(jax.random.key(1), (GB, N))
    y = jax.random.normal(jax.random.key(2), (GB, TP, M))

    def loss_fn(p, batch):
        xb, yb = batch
        i = jax.lax.axis_index("model")
        feat = xb @ p["w"][0] + p["b"][0]          # local column block
        yi = jax.lax.dynamic_index_in_dim(yb, i, axis=1, keepdims=False)
        # local loss, STATIC global normalizer (sum-over-data semantics)
        return ((feat - yi) ** 2).sum() / (GB * TP * M)

    lr = 0.02
    opt = MPI_PS(params, mesh=mesh_dp_tp, axis_name="data",
                 param_specs=specs, optim="adafactor", lr=lr)
    for _ in range(3):
        opt.step(loss_fn=loss_fn, batch=(x, y))

    # oracle: full-batch gradient of the same global computation, plain
    # (unsharded) adafactor_update on the global stacked leaves
    def global_loss(p):
        feats = jnp.einsum("bn,tnm->btm", x, p["w"]) + p["b"][None]
        return ((feats - y) ** 2).sum() / (GB * TP * M)

    p_ref = params
    st = init_adafactor_state(p_ref)
    h = AdafactorHyper(lr=lr)
    for _ in range(3):
        g = jax.grad(global_loss)(p_ref)
        p_ref, st = adafactor_update(p_ref, g, st, h)

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-7),
        opt.params, p_ref,
    )


def test_adafactor_sharded_factored_dim_rejected(mesh_dp_tp):
    """A leaf whose FACTORED (largest) dims are sharded must be
    rejected: those row/col means would span devices."""
    params = {"w": jnp.zeros((256, 160))}
    with pytest.raises(NotImplementedError, match="factor"):
        MPI_PS(params, mesh=mesh_dp_tp, axis_name="data",
               param_specs={"w": P("model")}, optim="adafactor")
