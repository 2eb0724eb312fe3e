"""Flat-bucket gradient aggregation (pytorch_ps_mpi_tpu/bucketing.py).

Only a payload codec's device exchange is bucketed
(``bucketing.exchange_plan``): for the psum codecs (identity, cast,
``comm_dtype``) ``bucket_mb`` changes nothing — no plan, the per-leaf
step's lowered text, bit-equal parameters. For int8 the bucketed step's
global-norm clip on bucket shards (leader) is compared with the
allgather form to a tight tolerance (the sum-of-squares accumulates in
a different grouping order). The launch-count tests assert the point of
the feature: the lowered program's collective op count drops from
one-per-leaf to one-per-bucket.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.bucketing import (
    count_collectives,
    flatten_into_buckets,
    lowered_collective_counts,
    plan_buckets,
    unflatten_from_buckets,
)
from pytorch_ps_mpi_tpu.codecs import get_codec
from pytorch_ps_mpi_tpu.ps import SGD, Adam, Adafactor

WORLD = 8


def mixed_tree():
    """Mixed-dtype tree with a 0-d scalar, an odd-size vector, and
    leaves small enough that a tiny bucket_mb still forces multiple
    buckets per dtype group."""
    return {
        "w1": jax.random.normal(jax.random.key(0), (300, 17)),
        "b1": jax.random.normal(jax.random.key(1), (17,)),
        "h": jax.random.normal(jax.random.key(2), (999,)).astype(jnp.bfloat16),
        "s": jnp.float32(3.0),  # 0-d leaf
        "big": jax.random.normal(jax.random.key(3), (4096,)),
    }


def grads_for(params, seed=9):
    return jax.tree.map(
        lambda p: jax.random.normal(
            jax.random.key(seed), (WORLD,) + np.shape(p)
        ).astype(jnp.asarray(p).dtype),
        params,
    )


def fresh(params):
    return jax.tree.map(jnp.array, params)


def assert_trees_equal(a, b):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        a, b,
    )


def assert_trees_close(a, b, rtol=2e-6):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            rtol=rtol, atol=1e-7,
        ),
        a, b,
    )


# ---------------------------------------------------------------------------
# Plan construction + pure transforms
# ---------------------------------------------------------------------------

def test_plan_roundtrip_bit_exact():
    t = mixed_tree()
    plan = plan_buckets(t, 0.01)
    buckets = flatten_into_buckets(plan, t)
    # dtype-uniform buckets
    for b, spec in zip(buckets, plan.buckets):
        assert b.dtype == jnp.dtype(spec.dtype)
        assert b.shape == (spec.size,)
    back = unflatten_from_buckets(plan, buckets)
    assert_trees_equal(t, back)


def test_plan_groups_by_dtype_and_respects_cap():
    t = mixed_tree()
    cap_mb = 0.02
    plan = plan_buckets(t, cap_mb)
    # bf16 leaf lands in its own dtype group
    assert {jnp.dtype(b.dtype).name for b in plan.buckets} == {
        "float32", "bfloat16"
    }
    # every multi-leaf bucket stays under the cap (a single oversize leaf
    # may exceed it by design)
    leaves_per_bucket = [0] * plan.num_buckets
    for slot in plan.leaf_slots:
        leaves_per_bucket[slot.bucket] += 1
    for i, b in enumerate(plan.buckets):
        if leaves_per_bucket[i] > 1:
            assert b.nbytes <= cap_mb * (1 << 20)


def test_plan_exact_offsets_scalar_and_odd_sizes():
    t = mixed_tree()
    plan = plan_buckets(t, 0.01)
    # offsets tile each bucket exactly: sorted slots per bucket are
    # contiguous and sum to the bucket size
    per_bucket = {}
    for slot in plan.leaf_slots:
        per_bucket.setdefault(slot.bucket, []).append(slot)
    for i, slots in per_bucket.items():
        slots.sort(key=lambda s: s.offset)
        off = 0
        for s in slots:
            assert s.offset == off
            off += s.size
        assert off == plan.buckets[i].size


def test_bucket_mb_zero_is_per_leaf_identity():
    assert plan_buckets(mixed_tree(), 0) is None
    opt = SGD(fresh(mixed_tree()), lr=0.1, bucket_mb=0)
    assert opt._bucket_plan is None


def test_plan_rejects_dtype_drift():
    t = mixed_tree()
    plan = plan_buckets(t, 0.01)
    wrong = dict(t, h=jnp.zeros((999,), jnp.float32))
    with pytest.raises(TypeError):
        flatten_into_buckets(plan, wrong)


# ---------------------------------------------------------------------------
# Psum codecs: bucket_mb does not apply to their device exchange
# ---------------------------------------------------------------------------

def _sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype),
        tree)


def _linear_loss(p, b):
    x, y = b
    return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)


_LINEAR_BATCH = (
    jax.random.normal(jax.random.key(5), (16, 64)),
    jax.random.normal(jax.random.key(6), (16, 4)),
)

def _sgd(p, **kw):
    return SGD(p, lr=0.05, momentum=0.9, **kw)


def _adam(p, **kw):
    return Adam(p, lr=0.01, **kw)


def _bf16(p, **kw):
    return SGD(p, lr=0.05, code=get_codec("bf16"), **kw)


def _narrow(p, **kw):
    return Adam(p, lr=0.01, average=True, comm_dtype=jnp.bfloat16, **kw)


# id -> (optimizer factory, its keywords, bucket_mb, steps); every case
# but "loss-fn" runs the grads-only step on mixed_tree()
_PSUM_CASES = {
    "identity-sgd-allgather": (_sgd, dict(mode="allgather"), 0.02, 3),
    "identity-sgd-leader": (_sgd, dict(mode="leader"), 0.02, 3),
    "identity-adam-allgather": (_adam, dict(mode="allgather"), 0.02, 3),
    "identity-adam-leader": (_adam, dict(mode="leader"), 0.02, 3),
    "adafactor-allgather": (Adafactor, {}, 0.02, 3),
    "bf16-allgather": (_bf16, dict(mode="allgather"), 0.02, 2),
    "bf16-leader": (_bf16, dict(mode="leader"), 0.02, 2),
    "comm-dtype-average-allgather": (_narrow, dict(mode="allgather"), 0.02, 2),
    "comm-dtype-average-leader": (_narrow, dict(mode="leader"), 0.02, 2),
    "loss-fn": (lambda p, **kw: SGD(p, lr=0.05, **kw), {}, 0.001, 3),
}


@pytest.mark.parametrize("case", list(_PSUM_CASES))
def test_psum_codecs_ignore_bucket_mb(mesh8, case):
    """With a psum codec (identity, the bf16 cast, ``comm_dtype``) the
    optimizer built with ``bucket_mb`` > 0 has no plan, lowers to the
    step of ``bucket_mb`` 0 and steps to bit-equal parameters, in both
    topologies and on the fused ``loss_fn`` path."""
    make, kw, bucket_mb, steps = _PSUM_CASES[case]
    loss_path = case == "loss-fn"
    params = ({"w": jnp.ones((64, 4)), "b": jnp.zeros((4,))} if loss_path
              else mixed_tree())
    per_leaf = make(fresh(params), **kw)
    asked = make(fresh(params), bucket_mb=bucket_mb, **kw)
    assert asked._bucket_plan is None

    def lowered(opt):
        if loss_path:
            fn, data = opt._build_grad_step(_linear_loss), _LINEAR_BATCH
        else:
            fn, data = opt._build_grads_only_step(), _sds(grads_for(params))
        return fn.lower(opt.params, opt.opt_state, opt.codec_state, data,
                        jax.random.key(0)).as_text()

    assert lowered(asked) == lowered(per_leaf)
    for _ in range(steps):
        if loss_path:
            l1, _ = per_leaf.step(loss_fn=_linear_loss, batch=_LINEAR_BATCH)
            l2, _ = asked.step(loss_fn=_linear_loss, batch=_LINEAR_BATCH)
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        else:
            per_leaf.step(grads=grads_for(params))
            asked.step(grads=grads_for(params))
    assert_trees_equal(per_leaf.params, asked.params)


# ---------------------------------------------------------------------------
# Payload codecs: the bucketed exchange
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda p, **kw: SGD(p, lr=0.05, momentum=0.9, **kw),
    lambda p, **kw: Adam(p, lr=0.01, **kw),
], ids=["sgd", "adam"])
def test_bucketed_global_norm_clip_parity(mesh8, make):
    # int8 on buckets: leader mode (dense_scatter) clips bucket SHARDS,
    # allgather mode the summed buckets; a tight clip so the scale
    # engages, and the sum-of-squares groups differently, hence allclose.
    # float32 leaves only: dense_scatter sums a bf16 leaf's decoded
    # contributions in bf16, where decode_sum adds them in float32
    params = {k: v for k, v in mixed_tree().items() if k != "h"}
    grads = grads_for(params)
    kw = dict(code=get_codec("int8"), clip_norm=0.5, bucket_mb=0.02)
    o1 = make(fresh(params), mode="allgather", **kw)
    o2 = make(fresh(params), mode="leader", **kw)
    assert o2._leader_lowering() == "dense_scatter"
    assert o1._bucket_plan is not None and o2._bucket_plan is not None
    for _ in range(3):
        o1.step(grads=grads)
        o2.step(grads=grads)
    assert_trees_close(o1.params, o2.params)


@pytest.mark.parametrize("codec", [
    ("sign", dict(use_pallas=False)),
    ("int8", {}),
    ("randomk", dict(fraction=0.1)),
])
@pytest.mark.parametrize("mode", ["allgather", "leader"])
def test_bucketable_lossy_codecs_run(mesh8, codec, mode):
    # per-bucket statistics are a documented semantics change for lossy
    # codecs: assert the bucketed step runs, moves params, and stays
    # finite (parity is only promised for identity/cast)
    name, kw = codec
    params = mixed_tree()
    grads = grads_for(params)
    opt = SGD(fresh(params), lr=0.05, mode=mode,
              code=get_codec(name, **kw), bucket_mb=0.02)
    assert opt._bucket_plan is not None
    opt.step(grads=grads)
    for x, p0 in zip(jax.tree.leaves(opt.params),
                     jax.tree.leaves(params)):
        assert np.isfinite(np.asarray(x, np.float32)).all()
    assert not all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(opt.params), jax.tree.leaves(params))
    )


def test_per_tensor_codec_keeps_per_leaf_path(mesh8):
    # Codec.bucketable=False (PowerSGD, top-k): bucket_mb is a no-op;
    # absolute-k randomk too (its k is per-UNIT — bucketing would
    # silently shrink the kept coordinate count by ~leaves/buckets)
    for name, kw in (("powersgd", {}), ("topk", dict(fraction=0.1)),
                     ("randomk", dict(k=8))):
        opt = SGD(fresh(mixed_tree()), lr=0.05,
                  code=get_codec(name, **kw), bucket_mb=16)
        assert opt._bucket_plan is None
        opt.step(grads=grads_for(mixed_tree()))
    # ...while the fraction form is bucket-safe (kept count unchanged)
    assert get_codec("randomk", fraction=0.1).bucketable
    assert not get_codec("randomk", k=8).bucketable


def test_bucketed_leader_state_dict_roundtrip(mesh8):
    params = mixed_tree()
    grads = grads_for(params)
    kw = dict(lr=0.01, mode="leader", code=get_codec("int8"), bucket_mb=0.02)
    o1 = Adam(fresh(params), **kw)
    o1.step(grads=grads)
    sd = o1.state_dict()
    o2 = Adam(fresh(params), **kw)
    o2.load_state_dict(sd)
    o1.step(grads=grads)
    o2.step(grads=grads)
    assert_trees_equal(o1.params, o2.params)


def test_bucket_form_leader_state_is_refused_where_state_is_per_leaf(mesh8):
    # the bucket-form state a leader optimizer with a psum codec and
    # bucket_mb > 0 once saved: that optimizer now keeps its state per
    # leaf, and the load says so instead of stepping on a wrong layout
    params = mixed_tree()
    kw = dict(lr=0.01, mode="leader", bucket_mb=0.02)
    bucket_form = Adam(fresh(params), code=get_codec("int8"), **kw)
    per_leaf = Adam(fresh(params), **kw)
    with pytest.raises(ValueError, match="does not fit"):
        per_leaf.load_state_dict(bucket_form.state_dict())


def test_functional_dp_bucketed_bit_exact(mesh8):
    # the functional step and MPI_PS, both on int8 buckets, agree bit
    # for bit: one plan decision (bucketing.exchange_plan) for both
    from pytorch_ps_mpi_tpu.parallel.dp import make_sync_train_step

    params = {"w": jnp.ones((32, 4)), "b": jnp.zeros((4,))}
    batch = (
        jax.random.normal(jax.random.key(7), (16, 32)),
        jax.random.normal(jax.random.key(8), (16, 4)),
    )

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    init_fn, step_fn = make_sync_train_step(
        loss_fn, mesh8, lr=0.1, code=get_codec("int8"), bucket_mb=0.0005,
        donate=False,
    )
    p = fresh(params)
    opt_state, codec_state = init_fn(p)
    opt = SGD(fresh(params), mesh=mesh8, lr=0.1, code=get_codec("int8"),
              bucket_mb=0.0005)
    assert opt._bucket_plan is not None
    for _ in range(3):
        p, opt_state, codec_state, loss = step_fn(
            p, opt_state, codec_state, batch, jax.random.key(0)
        )
        opt_loss, _ = opt.step(loss_fn=loss_fn, batch=batch)
    assert_trees_equal(p, opt.params)
    np.testing.assert_array_equal(np.asarray(loss), np.asarray(opt_loss))


def test_bucket_mb_rejects_model_parallel():
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.mesh import make_mesh

    mesh = make_mesh(shape=(4, 2), axis_names=("data", "model"))
    params = {"w": jnp.zeros((8, 4))}
    with pytest.raises(NotImplementedError):
        SGD(params, lr=0.1, mesh=mesh, axis_name="data", bucket_mb=16,
            param_specs={"w": P("model")})


def test_bucket_telemetry_fields(mesh8):
    params = mixed_tree()
    grads = grads_for(params)
    o = SGD(fresh(params), lr=0.05, code=get_codec("int8"), bucket_mb=0.02)
    _, data = o.step(grads=grads)
    assert data["bucket_count"] == o._bucket_plan.num_buckets
    assert data["agg_launches"] == o._bucket_plan.num_buckets
    assert data["bucket_bytes_total"] == o._bucket_plan.total_bytes
    for kw in ({}, dict(bucket_mb=16)):  # identity: per leaf either way
        o0 = SGD(fresh(params), lr=0.05, **kw)
        _, data0 = o0.step(grads=grads)
        assert data0["bucket_count"] == 0.0
        assert data0["agg_launches"] == len(jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Host wire (CodecWire bucketing; pure-python, no native transport needed)
# ---------------------------------------------------------------------------

def _wire_template():
    return {
        "a": np.zeros((100, 7), np.float32),
        "b": np.zeros((33,), np.float32),
        "s": np.zeros((), np.float32),
    }


def _wire_grad():
    rng = np.random.default_rng(0)
    return {
        "a": rng.standard_normal((100, 7)).astype(np.float32),
        "b": rng.standard_normal(33).astype(np.float32),
        "s": np.asarray(1.5, np.float32),
    }


@pytest.mark.parametrize("bucket_mb", [0.0, 16.0])
def test_codec_wire_bucketed_roundtrip(bucket_mb):
    from pytorch_ps_mpi_tpu.parallel.dcn import CodecWire

    wire = CodecWire(get_codec("identity"), _wire_template(),
                     bucket_mb=bucket_mb)
    grad = _wire_grad()
    buf = wire.encode_to_bytes(grad)
    assert isinstance(buf, np.ndarray) and buf.nbytes == wire.wire_bytes
    out = wire.decode_from_bytes(buf)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-6
        ),
        grad, out,
    )
    if bucket_mb:
        assert wire.plan is not None and wire.plan.num_buckets == 1
        # and bytes(buf) (the old immutable path) still decodes
        wire.decode_from_bytes(bytes(buf))


def test_codec_wire_bucketed_fewer_units_and_sidecars():
    from pytorch_ps_mpi_tpu.parallel.dcn import CodecWire

    code = get_codec("sign", use_pallas=False)
    per_leaf = CodecWire(code, _wire_template())
    bucketed = CodecWire(code, _wire_template(), bucket_mb=16)
    # one bucket -> one packed payload + ONE scale sidecar (vs 3)
    assert len(bucketed.shapes) == 1 < len(per_leaf.shapes)
    assert bucketed.wire_bytes < per_leaf.wire_bytes
    out = bucketed.decode_from_bytes(bucketed.encode_to_bytes(_wire_grad()))
    assert all(
        np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(out)
    )


def test_codec_wire_ping_pong_buffers():
    from pytorch_ps_mpi_tpu.parallel.dcn import CodecWire

    wire = CodecWire(get_codec("identity"), _wire_template(), bucket_mb=16)
    b1 = wire.encode_to_bytes(_wire_grad())
    b2 = wire.encode_to_bytes(_wire_grad())
    assert b1 is not b2  # previous buffer stays valid while next encodes


def test_codec_wire_truncated_buffer_raises():
    from pytorch_ps_mpi_tpu.parallel.dcn import CodecWire

    wire = CodecWire(get_codec("identity"), _wire_template(), bucket_mb=16)
    buf = wire.encode_to_bytes(_wire_grad())
    with pytest.raises(ValueError, match="truncated"):
        wire.decode_from_bytes(buf[: wire.wire_bytes - 8])


def test_codec_wire_per_tensor_codec_ignores_bucket_mb():
    from pytorch_ps_mpi_tpu.parallel.dcn import CodecWire

    wire = CodecWire(get_codec("topk", fraction=0.1), _wire_template(),
                     bucket_mb=16)
    assert wire.plan is None  # Codec.bucketable=False -> per-leaf wire


# ---------------------------------------------------------------------------
# Launch-count assertions (the CPU-backend smoke of the actual win)
# ---------------------------------------------------------------------------

def _launch_counts(bucket_mb, mode="allgather", codec="int8"):
    # 40-leaf tree, one dtype
    params = {f"p{i}": jnp.zeros((1000,), jnp.float32) for i in range(40)}
    grads = {k: jnp.zeros((WORLD, 1000), jnp.float32) for k in params}
    opt = SGD(params, lr=0.1, mode=mode, code=get_codec(codec),
              bucket_mb=bucket_mb)
    return lowered_collective_counts(
        opt._build_grads_only_step(), opt.params, opt.opt_state,
        opt.codec_state, _sds(grads), jax.random.key(0)
    )


def test_launch_count_reduced_5x_allgather(mesh8):
    # int8: an all-gather of the payload and of its scale a leaf, the
    # same two for the one bucket
    per_leaf = _launch_counts(0)
    bucketed = _launch_counts(16)
    assert per_leaf["all_gather"] >= 40
    assert bucketed["all_gather"] * 5 <= per_leaf["all_gather"]


def test_launch_count_reduced_5x_leader(mesh8):
    per_leaf = _launch_counts(0, mode="leader")
    bucketed = _launch_counts(16, mode="leader")
    # ZeRO-1 (dense_scatter): reduce_scatter in, all_gather out — both
    # collapse
    assert per_leaf["total"] >= 80
    assert bucketed["total"] * 5 <= per_leaf["total"]


def test_launch_count_of_a_psum_codec_ignores_bucket_mb(mesh8):
    # identity keeps one all-reduce a leaf: the compiler groups them
    per_leaf = _launch_counts(0, codec="identity")
    asked = _launch_counts(16, codec="identity")
    assert per_leaf["all_reduce"] >= 40
    assert asked == per_leaf


def test_count_collectives_parses_both_spellings():
    text = 'stablehlo.all_reduce stablehlo.all_gather all-reduce %x'
    c = count_collectives(text)
    assert c["all_reduce"] == 2 and c["all_gather"] == 1
