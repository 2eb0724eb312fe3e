"""All-reducible PowerSGD: the two-psum
shared-Q protocol (Vogels et al. 2019 Alg. 1) as the fused-path lowering.

``P = psum(M_w Q)`` → QR → ``Q = psum(M_wᵀ P̂)`` produces the rank-r
approximation of the SUMMED gradient with world-size-independent wire
cost; per-worker error feedback keeps ``e_w = M_w − P̂ P̂ᵀ M_w``. The
per-worker-factor form stays on the async/DCN wires (codec
``encode``/``decode_sum``, untouched).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.codecs import get_codec
from pytorch_ps_mpi_tpu.mesh import make_mesh
from pytorch_ps_mpi_tpu.ps import SGD

N, M = 16, 12
RANK = 2


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(shape=(8,), axis_names=("data",))


def _sequential_two_psum(grads_w, q0, memory_w):
    """Host-side oracle of one all-reduced PowerSGD round.

    grads_w: [W, n, m]; q0: [m, r] shared warm Q; memory_w: [W, n, m].
    Returns (summed_approx, new_q, new_memory_w).
    """
    corrected = grads_w + memory_w
    p_sum = np.einsum("wnm,mr->nr", corrected, q0)          # Σ M_w Q
    p_hat, _ = np.linalg.qr(p_sum)
    q_w = np.einsum("wnm,nr->wmr", corrected, p_hat)        # per-worker factor
    q_sum = q_w.sum(axis=0)                                 # Σ M_wᵀ P̂
    approx = p_hat @ q_sum.T
    new_memory = corrected - np.einsum("nr,wmr->wnm", p_hat, q_w)
    return approx, q_sum, new_memory


def test_fused_allreduce_matches_sequential_oracle(mesh8):
    """One grads-only MPI_PS step with powersgd == the host-side
    two-psum oracle, including the Q warm-start and per-worker error
    memories."""
    code = get_codec("powersgd", rank=RANK, min_compression_elems=4)
    params = {"w": jnp.zeros((N, M), jnp.float32)}
    opt = SGD(params, mesh=mesh8, lr=1.0, code=code)

    grads_w = np.asarray(
        jax.random.normal(jax.random.key(5), (8, N, M), jnp.float32)
    )
    q0 = np.asarray(code.init_state((N, M), jnp.float32)["Q"])

    opt.step(grads={"w": jnp.asarray(grads_w)})

    approx, q_sum, new_memory = _sequential_two_psum(
        grads_w, q0, np.zeros_like(grads_w)
    )
    # lr=1.0 from zero params: new params == -summed_approx
    np.testing.assert_allclose(
        np.asarray(opt.params["w"]), -approx, rtol=1e-4, atol=1e-5
    )
    st = opt.codec_state["w"]
    np.testing.assert_allclose(np.asarray(st["Q"][0]), q_sum,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st["memory"]), new_memory,
                               rtol=1e-4, atol=1e-5)


def test_error_feedback_residual_identity(mesh8):
    """Σ_w e_w == Σ_w M_w − decode: the local memories partition the
    global residual exactly (the property that makes per-worker EF
    converge in the all-reduced protocol)."""
    code = get_codec("powersgd", rank=RANK, min_compression_elems=4)
    params = {"w": jnp.zeros((N, M), jnp.float32)}
    opt = SGD(params, mesh=mesh8, lr=1.0, code=code)
    grads_w = np.asarray(
        jax.random.normal(jax.random.key(9), (8, N, M), jnp.float32)
    )
    opt.step(grads={"w": jnp.asarray(grads_w)})
    decode = -np.asarray(opt.params["w"])           # lr=1 from zeros
    mem_sum = np.asarray(opt.codec_state["w"]["memory"]).sum(axis=0)
    np.testing.assert_allclose(
        mem_sum, grads_w.sum(axis=0) - decode, rtol=1e-4, atol=1e-4
    )


def test_wire_bytes_world_size_independent():
    """The two-psum payload term is r(n+m) per leaf regardless of W —
    where the old per-worker-factor gather shipped (W-1)·r·(n+m)."""
    code4 = get_codec("powersgd", rank=RANK, min_compression_elems=4)
    code8 = get_codec("powersgd", rank=RANK, min_compression_elems=4)
    params = {"w": jnp.zeros((N, M), jnp.float32)}
    mesh4 = make_mesh(shape=(4,), axis_names=("data",),
                      devices=jax.devices()[:4])
    mesh8_ = make_mesh(shape=(8,), axis_names=("data",))
    o4 = SGD(params, mesh=mesh4, code=code4)
    o8 = SGD(params, mesh=mesh8_, code=code8)
    lowering4, wire4 = o4._wire_accounting
    lowering8, wire8 = o8._wire_accounting
    assert lowering4 == lowering8 == "two_psum_lowrank"
    payload = RANK * (N + M) * 4
    assert wire4 == pytest.approx(2 * (3 / 4) * payload)
    assert wire8 == pytest.approx(2 * (7 / 8) * payload)
    # payload term identical across W; the old form would grow 3 -> 7 x
    assert wire8 / wire4 == pytest.approx((7 / 8) / (3 / 4))


def test_leader_mode_equals_allgather(mesh8):
    """ZeRO-1 leader mode with the fused protocol == allgather twin."""
    code_a = get_codec("powersgd", rank=RANK, min_compression_elems=4)
    code_b = get_codec("powersgd", rank=RANK, min_compression_elems=4)
    params = {"w": jnp.ones((N, M), jnp.float32) * 0.1,
              "b": jnp.zeros((M,), jnp.float32)}

    def loss_fn(p, batch):
        x, y = batch
        # "b" (1-D, uncompressed) exercises the plain-psum branch of the
        # fused protocol alongside the compressed 2-D "w"
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    x = jax.random.normal(jax.random.key(1), (16, N))
    y = jax.random.normal(jax.random.key(2), (16, M))
    a = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9, code=code_a)
    b = SGD(params, mesh=mesh8, lr=0.05, momentum=0.9, code=code_b,
            mode="leader")
    for _ in range(3):
        a.step(loss_fn=loss_fn, batch=(x, y))
        b.step(loss_fn=loss_fn, batch=(x, y))
    for u, v in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                   rtol=1e-5, atol=1e-6)


def test_fused_protocol_composes_with_tp():
    """PowerSGD on a DP x TP mesh: each (data, model) device compresses
    its LOCAL shard, psums ride the data axis only, training converges."""
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.parallel import tp
    from pytorch_ps_mpi_tpu.ps import MPI_PS

    mesh = make_mesh(shape=(2, 4), axis_names=("data", "model"))
    d, f, gb, seq = 8, 32, 8, 4
    params = tp.init_tp_mlp(jax.random.key(0), d, f, tp=4)
    x = jax.random.normal(jax.random.key(1), (gb, seq, d))
    y = jax.random.normal(jax.random.key(2), (gb, seq, d))

    def loss_fn(p, batch):
        xb, yb = batch
        pred = tp.tp_mlp(xb, p, "model", local_grads=True)
        return ((pred - yb) ** 2).sum() / (gb * seq * d)

    opt = MPI_PS(
        params, optim="sgd", lr=0.1,
        code=get_codec("powersgd", rank=2, min_compression_elems=4),
        mesh=mesh, axis_name="data",
        param_specs=tp.tp_param_spec(params, "model"),
        batch_spec=P("data"),
    )
    loss0, data = opt.step(loss_fn=loss_fn, batch=(x, y))
    for _ in range(8):
        loss, _ = opt.step(loss_fn=loss_fn, batch=(x, y))
    assert float(loss) < float(loss0)
    assert data["wire_lowering"] == "two_psum_lowrank"


def test_tp_shard_leaves_actually_compress():
    """Regression: the leading [1] local-shard axis must not defeat
    compression — the matrix view of [1, d, f/tp] skips the singleton,
    so a TP leaf compresses exactly like its [d, f/tp] dense slice."""
    from pytorch_ps_mpi_tpu.codecs.powersgd import _matrix_shape

    code = get_codec("powersgd", rank=2, min_compression_elems=4)
    assert code._compresses((1, 16, 16))
    assert _matrix_shape((1, 16, 16)) == (16, 16)
    # and the wire is the rank-factor size, not the raw tensor
    assert code.payload_bits((1, 16, 16), jnp.float32) == 2 * 32 * 4 * 8


def test_fused_tp_matches_per_shard_sequential_oracle():
    """PowerSGD x TP under MPI_PS == a host-side oracle running the
    two-psum protocol independently per model shard: each (data, model)
    device compresses its LOCAL [d, f/tp] shard matrix, psums ride the
    data axis only, and the resulting update equals slicing the
    per-worker dense gradients and running the protocol per shard."""
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.parallel import tp
    from pytorch_ps_mpi_tpu.ps import MPI_PS

    dp, tpn, d, f, gb, seq = 2, 4, 8, 32, 8, 4
    mesh = make_mesh(shape=(dp, tpn), axis_names=("data", "model"))
    params = tp.init_tp_mlp(jax.random.key(0), d, f, tp=tpn)
    x = np.asarray(jax.random.normal(jax.random.key(1), (gb, seq, d)))
    y = np.asarray(jax.random.normal(jax.random.key(2), (gb, seq, d)))
    norm = gb * seq * d

    def loss_fn(p, batch):
        xb, yb = batch
        pred = tp.tp_mlp(xb, p, "model", local_grads=True)
        return ((pred - yb) ** 2).sum() / norm

    code = get_codec("powersgd", rank=2, min_compression_elems=4)
    opt = MPI_PS(
        params, optim="sgd", lr=1.0, code=code,
        mesh=mesh, axis_name="data",
        param_specs=tp.tp_param_spec(params, "model"),
        batch_spec=P("data"),
    )
    opt.step(loss_fn=loss_fn, batch=(jnp.asarray(x), jnp.asarray(y)))

    # per-data-worker dense gradients of the same local losses
    w1, b1, w2, b2 = (np.asarray(v) for v in tp.dense_equivalent_mlp(params))

    def dense_local_loss(wts, xw, yw):
        w1, b1, w2, b2 = wts
        pred = jax.nn.gelu(xw @ w1 + b1) @ w2 + b2
        return ((pred - yw) ** 2).sum() / norm

    gworker = [
        jax.grad(dense_local_loss)(
            (w1, b1, w2, b2),
            x[w * (gb // dp):(w + 1) * (gb // dp)],
            y[w * (gb // dp):(w + 1) * (gb // dp)],
        )
        for w in range(dp)
    ]

    fpt = f // tpn
    for mshard in range(tpn):
        for leaf, slicer, local_shape in [
            ("w1", lambda g: np.asarray(g[0])[:, mshard * fpt:(mshard + 1) * fpt],
             (1, d, fpt)),
            ("w2", lambda g: np.asarray(g[2])[mshard * fpt:(mshard + 1) * fpt, :],
             (1, fpt, d)),
        ]:
            grads_w = np.stack([slicer(g).reshape(
                local_shape[1], local_shape[2]) for g in gworker])
            q0 = np.asarray(code.init_state(local_shape, jnp.float32)["Q"])
            approx, _, _ = _sequential_two_psum(
                grads_w, q0, np.zeros_like(grads_w)
            )
            got = np.asarray(opt.params[leaf][mshard])
            want = np.asarray(params[leaf][mshard]) - approx.reshape(
                local_shape[1], local_shape[2])
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5,
                                       err_msg=f"{leaf} shard {mshard}")


def test_async_wire_form_unchanged():
    """The per-worker-factor payload form (encode/decode_sum) survives
    for wires with no synchronous collective: decode_sum of stacked
    payloads still sums W separate rank-r approximations."""
    code = get_codec("powersgd", rank=RANK, min_compression_elems=4)
    g = jax.random.normal(jax.random.key(3), (4, N, M), jnp.float32)
    payloads, states = [], []
    for w in range(4):
        pl, st = code.encode(g[w], code.init_state((N, M), jnp.float32))
        payloads.append(pl)
        states.append(st)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *payloads)
    out = code.decode_sum(stacked, (N, M), jnp.float32)
    expected = sum(
        np.asarray(pl["P"]) @ np.asarray(pl["Q"]).T for pl in payloads
    )
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-4,
                               atol=1e-5)
