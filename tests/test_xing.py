"""``models/xing.py`` at a tiny preset (hidden 32, 4 heads of 8 + 4 wide keys
over an 8-wide value, 4 streams, 8 routed experts of which 2 are held, one
dense and two expert layers, the prediction module; 24 positions) against
the plain reference ``chipbench/reference/xing.py`` on seeded weights: the
stack, each mechanism alone, the shares of a deployment, and the leaves
with two readers.

Tolerances: float32 on both sides, 2e-4 of the largest entry (the
reference runs its products at "highest"; the attention, the grouped
products and the blocks sum in other orders). A gradient that is zero by
construction (``STRUCTURAL_ZEROS``) is held to rounding."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import xing as ref
from pytorch_ps_mpi_tpu.models import xing
from pytorch_ps_mpi_tpu.ops import hyper_connection as hc
from pytorch_ps_mpi_tpu.parallel import dropless

T = 24


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


def leaf_tol(path, leaf):
    """The residual mixing matrix's parameters see the loss through twenty
    float32 Sinkhorn normalisations of streams that differ little: their
    gradients are 1e-7 and agree to 2e-3; a gate is one number, the sum of
    a whole product's gradient."""
    return 1e-2 if path.endswith("_res']") else 2e-3 if leaf.ndim == 0 \
        else 2e-4


def reference_cfg(cfg):
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d.update(n_routed_experts=cfg.experts_held[1],
             published_n_routed_experts=cfg.n_routed_experts,
             first_expert=cfg.experts_held[0],
             published_layer_index=list(cfg.layer_index),
             num_hidden_layers=len(cfg.layer_index),
             rope_scaling=dict(cfg.rope_scaling) if cfg.rope_scaling else None)
    return d


def case(seed=0, **kw):
    # gates and biases of order one: the hyper-connections mix for real
    # (at the seed's 0.01 and 8 every stream stays within 1e-3 of the
    # plain residual stream and the mixing weights' gradients are rounding)
    cfg = xing.XingConfig.tiny(**dict(dict(hc_init_gate=0.3,
                                           hc_init_bias=1.0), **kw))
    params = xing.init(jax.random.key(seed), cfg)
    # off the seed's zeros and ones: every bias and gain takes part
    params = jax.tree.map(lambda a: a + 0.05 * jax.random.normal(
        jax.random.key(a.size), a.shape), params)
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, T), 0,
                                cfg.vocab_size)
    return cfg, params, {"tokens": tokens}


def reference_loss(params, batch, rcfg):
    total, count = ref.terms(params, batch, rcfg)
    return total / count


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The reference's blocks at this size: several of each."""
    monkeypatch.setattr(ref, "ROW_CHUNK", 8)
    monkeypatch.setattr(ref, "Q_CHUNK", 8)
    monkeypatch.setattr(ref, "HEAD_CHUNK", 2)


# Zero by construction, so that program and reference agree on rounding
# alone: the streams enter layer 0 and the prediction module's layer as n
# copies, so H_pre only scales what an RMSNorm reads next and H_res, whose
# rows sum to one, leaves them what they are; the streams
# are summed after the last layer's last mix, and H_res's columns sum to
# one; the router's bias sits behind stop_gradient.
STRUCTURAL_ZEROS = (
    [f"['{where}']['hc_attn']['{leaf}']"
     for where in ("layer_0", "mtp']['layer")
     for leaf in ("a_pre", "b_pre", "w_pre", "a_res", "b_res", "w_res")]
    + [f"['{where}']['hc_mlp']['{leaf}']"
       for where in ("layer_2", "mtp']['layer")
       for leaf in ("a_res", "b_res", "w_res")])


@pytest.fixture(scope="module")
def gradients():
    """Loss and gradients of program and reference, once for the module
    (at the small blocks: a module's fixture cannot take ``monkeypatch``)."""
    blocks = ("ROW_CHUNK", "Q_CHUNK", "HEAD_CHUNK")
    before = [getattr(ref, b) for b in blocks]
    ref.ROW_CHUNK, ref.Q_CHUNK, ref.HEAD_CHUNK = 8, 8, 2
    try:
        cfg, params, batch = case()
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: xing.causal_lm_loss(p, batch, cfg)))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_loss(p, batch, reference_cfg(cfg))))(params)
    finally:
        for b, value in zip(blocks, before):
            setattr(ref, b, value)
    named = lambda tree: {jax.tree_util.keystr(k): v for k, v in
                          jax.tree_util.tree_leaves_with_path(tree)}
    return float(loss), float(want), named(grads), named(want_grads)


@pytest.mark.parametrize("attention", ["einsum", "flash"])
@pytest.mark.parametrize("remat", [False, True])
def test_the_stack(attention, remat):
    cfg, params, batch = case(attention=attention, remat=remat)
    rcfg = reference_cfg(cfg)
    main, second, loads = jax.jit(
        lambda p: xing.apply(p, batch["tokens"], cfg))(params)
    want_main, want_second = jax.jit(
        lambda p: ref.logits(p, batch, rcfg))(params)
    assert close(main, want_main) and close(second, want_second)
    assert np.array_equal(loads, jax.jit(
        lambda p: ref.router_loads(p, batch, rcfg))(params))
    loss = jax.jit(lambda p: xing.causal_lm_loss(p, batch, cfg))(params)
    want = jax.jit(lambda p: reference_loss(p, batch, rcfg))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))


def test_both_losses(gradients):
    loss, want, _, _ = gradients
    assert abs(loss - want) < 1e-5 * abs(want)
    # the prediction module's loss is in it: without the module the loss
    # is the next-token loss alone
    cfg, params, batch = case(num_nextn_predict_layers=0)
    alone = float(jax.jit(
        lambda p: xing.causal_lm_loss(p, batch, cfg))(params))
    assert abs(alone - float(jax.jit(lambda p: reference_loss(
        p, batch, reference_cfg(cfg)))(params))) < 1e-5 * alone
    assert loss > alone + 0.2 * 3.0   # 0.3 x a cross-entropy near ln 96


@pytest.mark.parametrize("leaf", [
    "['embed_tokens']", "['lm_head']", "['norm']",
    "['layer_0']['mlp']['gate_proj']", "['layer_0']['mlp']['down_proj']",
    "['layer_0']['self_attn']['q_a_proj']",
    "['layer_0']['self_attn']['q_b_proj']",
    "['layer_0']['self_attn']['kv_a_proj_with_mqa']",
    "['layer_0']['self_attn']['kv_b_proj']",
    "['layer_0']['self_attn']['kv_a_layernorm']",
    "['layer_0']['self_attn']['o_proj']",
    "['layer_0']['hc_mlp']['w_pre']", "['layer_0']['hc_attn']['w_post']",
    "['layer_0']['hc_mlp']['w_res']", "['layer_1']['hc_attn']['w_pre']",
    "['layer_1']['hc_attn']['a_res']", "['layer_1']['hc_mlp']['b_post']",
    "['layer_1']['router']", "['layer_1']['experts']['gate_proj']",
    "['layer_1']['experts']['up_proj']", "['layer_1']['experts']['down_proj']",
    "['layer_1']['shared']['gate_proj']", "['layer_2']['shared']['down_proj']",
    "['layer_2']['hc_attn']['w_res']", "['layer_2']['hc_mlp']['w_post']",
    "['mtp']['eh_proj']", "['mtp']['enorm']", "['mtp']['hnorm']",
    "['mtp']['norm']", "['mtp']['layer']['experts']['down_proj']",
    "['mtp']['layer']['shared']['up_proj']",
    "['mtp']['layer']['hc_mlp']['w_pre']", "['mtp']['layer']['router']",
])
def test_the_gradient_of(leaf, gradients):
    _, _, grads, want = gradients
    assert np.abs(want[leaf]).max() > 1e-9, "no gradient to compare"
    assert close(grads[leaf], want[leaf], leaf_tol(leaf, want[leaf])), leaf


def test_every_other_gradient_and_the_structural_zeros(gradients):
    _, _, grads, want = gradients
    assert set(grads) == set(want) and set(STRUCTURAL_ZEROS) < set(grads)
    for path, g in grads.items():
        if path in STRUCTURAL_ZEROS:
            assert np.abs(g).max() < 1e-7 and np.abs(want[path]).max() < 1e-7
        elif "e_score_correction_bias" in path:
            assert not np.any(g) and not np.any(want[path]), path
        else:
            assert close(g, want[path], leaf_tol(path, g)), path


def test_embedding_and_head_gradients_sum_over_trunk_and_module():
    """The two leaves with two readers: d loss / d leaf = the trunk's part
    + mtp_loss_weight x the module's."""
    cfg, params, batch = case()
    grad = lambda w: jax.jit(jax.grad(lambda p: xing.causal_lm_loss(
        p, batch, dataclasses.replace(cfg, mtp_loss_weight=w))))(params)
    g0, g3, g1 = grad(0.0), grad(0.3), grad(1.0)
    for leaf in ("embed_tokens", "lm_head"):
        module = g1[leaf] - g0[leaf]
        assert np.abs(module).max() > 1e-3 * np.abs(g0[leaf]).max()
        assert close(g3[leaf], g0[leaf] + 0.3 * module, 1e-5)
    # a leaf of the module alone has no trunk part
    assert not np.any(g0["mtp"]["eh_proj"])


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    cfg, params, batch = case()
    rcfg = reference_cfg(cfg)
    loss = lambda b: float(jax.jit(
        lambda p: reference_loss(p, b, rcfg))(params))
    blocks = loss(batch)
    monkeypatch.setattr(ref, "ROW_CHUNK", T)
    monkeypatch.setattr(ref, "Q_CHUNK", T)
    monkeypatch.setattr(ref, "HEAD_CHUNK", 4)
    assert abs(blocks - loss(batch)) < 1e-6 * blocks
    # total / count over rows = the mean of the rows' losses
    rows = [loss({"tokens": batch["tokens"][r:r + 1]}) for r in range(2)]
    assert abs(blocks - sum(rows) / 2) < 1e-6 * blocks


# -- the shares of a deployment ---------------------------------------------------

def test_eight_expert_shares_and_the_shared_expert_once_are_the_layer():
    """Every chip computes the shared expert alike and its own experts'
    part: the eight routed parts plus the shared expert counted ONCE are
    what the uncut layer gives, in program and reference."""
    cfg, params, _ = case(experts_held=(0, 8))
    lp = params["layer_1"]
    u = jax.random.normal(jax.random.key(5), (2, T, cfg.hidden_size))
    whole, loads = xing.expert_ffn(u, lp, cfg)
    shared = xing.swiglu(u, lp["shared"], cfg.dtype, "moe.shared")
    parts, counts = [], []
    for first in range(8):
        share = dataclasses.replace(cfg, experts_held=(first, 1))
        mine = dict(lp, experts=jax.tree.map(lambda a: a[first:first + 1],
                                             lp["experts"]))
        y, n = xing.expert_ffn(u, mine, share)
        parts.append(y - shared)
        counts.append(int(n[0]))
        want, _ = ref.expert_layer(u.reshape(-1, cfg.hidden_size), mine,
                                   reference_cfg(share))
        assert close(y.reshape(-1, cfg.hidden_size), want)
    assert close(shared + sum(parts), whole)
    assert counts == loads.tolist()
    assert sum(counts) == 2 * T * cfg.num_experts_per_tok
    want, _ = ref.expert_layer(u.reshape(-1, cfg.hidden_size), lp,
                               reference_cfg(cfg))
    assert close(whole.reshape(-1, cfg.hidden_size), want)


def test_eight_vocabulary_slices_side_by_side_are_the_head():
    cfg, params, batch = case()
    main, second, _ = xing.apply(params, batch["tokens"], cfg)
    rows = cfg.vocab_size // 8
    for logits in (main, second):
        assert logits.shape == (2, T, cfg.vocab_size)
    slices = [xing.apply(dict(params, lm_head=params["lm_head"][
        :, i * rows:(i + 1) * rows]), batch["tokens"], cfg)[:2]
        for i in range(8)]
    assert close(jnp.concatenate([s[0] for s in slices], -1), main, 1e-6)
    assert close(jnp.concatenate([s[1] for s in slices], -1), second, 1e-6)


# -- hyper-connections -------------------------------------------------------------

def test_sinkhorn_is_doubly_stochastic_and_its_gradient_is_the_loops():
    m = jnp.exp(jax.random.normal(jax.random.key(0), (4, 4, 2, T)))
    out = hc.sinkhorn(m, 20, 1e-6)
    assert np.abs(np.sum(out, axis=0) - 1).max() < 1e-4     # columns
    assert np.abs(np.sum(out, axis=1) - 1).max() < 1e-4     # rows
    # the reference's loop carries the position first: [s, n, n]
    weigh = jax.random.normal(jax.random.key(1), m.shape)
    to_ref = lambda a: a.reshape(4, 4, -1).transpose(2, 0, 1)
    mine = jax.grad(lambda m: jnp.sum(hc.sinkhorn(m, 20, 1e-6) * weigh))(m)
    theirs = jax.grad(lambda m: jnp.sum(
        ref.sinkhorn(m, 20, 1e-6) * to_ref(weigh)))(to_ref(m))
    assert close(to_ref(out), ref.sinkhorn(to_ref(m), 20, 1e-6), 1e-6)
    assert close(to_ref(mine), theirs, 1e-5)


def test_the_mixing_weights_are_the_references():
    cfg, params, _ = case()
    p = params["layer_1"]["hc_mlp"]
    streams = jax.random.normal(jax.random.key(2), (4, 2, T, cfg.hidden_size))
    h_pre, h_post, h_res = hc.mixing_weights(
        streams, p, iters=20, eps=cfg.hc_eps, clamp=(-30.0, 30.0),
        norm_eps=cfg.rms_norm_eps)
    for row in range(2):
        w_pre, w_post, w_res = ref.mixing(
            streams[:, row].transpose(1, 0, 2), p, reference_cfg(cfg))
        assert close(h_pre[:, row].T, w_pre)
        assert close(h_post[:, row].T, w_post)
        assert close(h_res[:, :, row].transpose(2, 0, 1), w_res)
    assert np.all(h_pre > 0) and np.all(h_pre < 1) and np.all(h_post < 2)


@pytest.mark.parametrize("bias, tol", [(30.0, 1e-5), (8.0, 5e-3)])
@pytest.mark.parametrize("dense", [True, False])
def test_with_closed_gates_a_layer_is_the_plain_residual_layer(bias, tol,
                                                               dense):
    """``a_* = 0`` and the seed's biases: stream 0 is read, every stream
    takes the output, nothing crosses streams — n copies of ``x + attn(
    norm(x))``, then ``+ ffn(norm(.))``; exactly at the clamp's bias,
    nearly (sigmoid(8) = 0.9997) at the seed's."""
    cfg = xing.XingConfig.tiny(hc_init_gate=0.0, hc_init_bias=bias)
    lp = xing.init(jax.random.key(3), cfg)["layer_0" if dense else "layer_1"]
    x = jax.random.normal(jax.random.key(4), (2, T, cfg.hidden_size))
    positions = jnp.arange(T)
    streams, _ = xing.decoder_layer(xing._spread(x, cfg), lp, cfg, positions,
                                    dense)
    eps = cfg.rms_norm_eps
    plain = x + xing.latent_attention(
        xing.rms_norm(x, lp["input_layernorm"], eps), lp["self_attn"], cfg,
        positions)
    u = xing.rms_norm(plain, lp["post_attention_layernorm"], eps)
    plain = plain + (xing.swiglu(u, lp["mlp"], cfg.dtype, "mlp.swiglu")
                     if dense else xing.expert_ffn(u, lp, cfg)[0])
    for stream in streams:
        assert close(stream, plain, tol)


def test_hc_plan_and_flash_tiles_rows_on_the_recorder():
    from pytorch_ps_mpi_tpu import telemetry

    cfg, params, batch = case(attention="flash")
    rec = telemetry.configure()
    try:
        xing.causal_lm_loss(params, batch, cfg)
        events = rec.events()
    finally:
        telemetry.disable()
    plans = [e for e in events if e["name"] == "hc.plan"]
    assert len(plans) == 1
    row = plans[0]["attrs"]
    assert (row["streams"], row["iterations"], row["sub_layers"]) == (4, 20, 8)
    assert row["stream_bytes"] == 4 * 2 * T * cfg.hidden_size * 4
    # 32 wide: under a lane tile, so the jax.numpy functions mix
    assert (row["mover"], row["tile"]) == ("jnp", 0)
    tiles = [e for e in events if e["name"] == "attn.flash_tiles"]
    assert len(tiles) == 4      # three layers and the module's


# -- the router -----------------------------------------------------------------------

def test_selection_follows_score_plus_bias_and_gates_follow_score():
    x = jax.random.normal(jax.random.key(0), (T, 16))
    w = jax.random.normal(jax.random.key(1), (16, 8))
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, w, precision=jax.lax.Precision.HIGHEST)))
    _, chosen = dropless.route(x, w, 2, scoring="sigmoid", scaling=2.0)
    assert np.array_equal(np.sort(chosen, -1),
                          np.sort(np.argsort(-scores, -1)[:, :2], -1))
    # a bias that lifts expert 5 above every score: always chosen, and its
    # gate is still its UNBIASED score over the chosen pair's sum, times 2
    bias = jnp.zeros(8).at[5].set(10.0)
    gates, chosen = dropless.route(x, w, 2, scoring="sigmoid", bias=bias,
                                   scaling=2.0)
    assert np.all(np.any(np.asarray(chosen) == 5, axis=-1))
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    assert close(gates, 2.0 * picked / picked.sum(-1, keepdims=True), 1e-6)
    assert close(np.sum(gates, -1), 2.0 * np.ones(T), 1e-6)
    # without normalisation the gates are the scores themselves
    raw, _ = dropless.route(x, w, 2, False, scoring="sigmoid", bias=bias)
    assert close(raw, picked, 1e-6)
    with pytest.raises(ValueError, match="scoring"):
        dropless.route(x, w, 2, scoring="softmax", bias=bias)
    with pytest.raises(ValueError, match="scoring"):
        dropless.route(x, w, 2, scoring="tanh")


def test_the_softmax_router_is_what_it_was():
    x = jax.random.normal(jax.random.key(0), (T, 16))
    w = jax.random.normal(jax.random.key(1), (16, 8))
    gates, chosen = dropless.route(x, w, 3)
    probs = np.asarray(jax.nn.softmax(jnp.dot(
        x, w, precision=jax.lax.Precision.HIGHEST), -1))
    top = -np.sort(-probs, -1)[:, :3]
    assert close(gates, top / top.sum(-1, keepdims=True), 1e-6)
    assert np.array_equal(chosen, np.argsort(-probs, -1)[:, :3])


def test_the_router_bias_takes_no_gradient_and_no_step_moves_it():
    from pytorch_ps_mpi_tpu import MPI_PS
    from pytorch_ps_mpi_tpu.mesh import make_mesh

    cfg, params, batch = case()
    grads = jax.jit(jax.grad(
        lambda p: xing.causal_lm_loss(p, batch, cfg)))(params)
    assert not np.any(grads["layer_1"]["e_score_correction_bias"])
    assert np.any(grads["layer_1"]["router"])
    before = jax.device_get(params)
    opt = MPI_PS(params, optim="adam", lr=1e-2, mode="allgather",
                 mesh=make_mesh(devices=jax.devices()[:1]), average=True)
    for _ in range(2):
        opt.step(loss_fn=lambda p, b: xing.causal_lm_loss(p, b, cfg),
                 batch=batch)
    after = jax.device_get(opt.params)
    for where in ("layer_1", "layer_2"):
        assert np.array_equal(after[where]["e_score_correction_bias"],
                              before[where]["e_score_correction_bias"])
    assert np.array_equal(after["mtp"]["layer"]["e_score_correction_bias"],
                          before["mtp"]["layer"]["e_score_correction_bias"])
    assert not np.array_equal(after["layer_1"]["router"],
                              before["layer_1"]["router"])


# -- latent attention --------------------------------------------------------------------

def test_yarn_frequencies_are_the_references():
    cfg = xing.XingConfig.tiny(
        qk_rope_head_dim=64, rope_scaling=tuple(sorted(dict(
            type="yarn", factor=64, beta_fast=32, beta_slow=1, mscale=1,
            mscale_all_dim=1, original_max_position_embeddings=4096).items())))
    freq, on_cos_sin, on_scale = xing.yarn_frequencies(cfg)
    want = ref.yarn(reference_cfg(cfg))
    assert np.allclose(freq, want[0], rtol=1e-6)
    assert (on_cos_sin, on_scale) == pytest.approx(want[1:])
    plain = 10000.0 ** (-np.arange(32) / 32)
    # the fast pairs keep their frequency, the slow ones are divided by 64
    assert np.allclose(freq[:8], plain[:8], rtol=1e-6)
    assert np.allclose(freq[-8:], plain[-8:] / 64, rtol=1e-6)
    assert np.all(np.diff(freq) < 0)
    assert on_cos_sin == 1.0
    assert on_scale == pytest.approx((0.1 * np.log(64) + 1) ** 2)
    # no scaling: the plain frequencies and no factor
    freq, a, b = xing.yarn_frequencies(dataclasses.replace(
        cfg, rope_scaling=None))
    assert np.allclose(freq, plain, rtol=1e-6) and (a, b) == (1.0, 1.0)


def test_latent_attention_is_the_references():
    cfg, params, _ = case()
    p = params["layer_0"]["self_attn"]
    u = jax.random.normal(jax.random.key(6), (2, T, cfg.hidden_size))
    out = xing.latent_attention(u, p, cfg, jnp.arange(T))
    for row in range(2):
        assert close(out[row], ref.attention(u[row], p, reference_cfg(cfg)))


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-4),
                                        (jnp.bfloat16, 3e-2)])
def test_flash_kernels_at_192_over_128_against_the_dense_oracle(dtype, tol):
    """The cell's widths (q and k 192 = one and a half lane tiles, v 128),
    causal, interpreted: forward, logsumexp and the three gradients."""
    from pytorch_ps_mpi_tpu.ops import attention_pallas as ap

    keys = jax.random.split(jax.random.key(0), 4)
    q, k = (jax.random.normal(kk, (1, 256, 2, 192), dtype) for kk in keys[:2])
    v, w = (jax.random.normal(kk, (1, 256, 2, 128), dtype) for kk in keys[2:])
    scale = 192 ** -0.5 * 2.0

    def kernel(q, k, v):
        out, lse = ap.flash_attention(q, k, v, mask="causal", scale=scale,
                                      return_lse=True)
        return jnp.sum(out.astype(jnp.float32) * w) + jnp.sum(lse), (out, lse)

    def dense(q, k, v):
        out, lse = ap._attention_jnp(q, k, v, 0, 0, ("causal",), scale)
        return jnp.sum(out.astype(jnp.float32) * w) + jnp.sum(lse), (out, lse)

    (_, (out, lse)), grads = jax.value_and_grad(kernel, (0, 1, 2),
                                                has_aux=True)(q, k, v)
    (_, (want, want_lse)), want_grads = jax.value_and_grad(
        dense, (0, 1, 2), has_aux=True)(q, k, v)
    assert out.shape == (1, 256, 2, 128)
    assert close(out, want, tol) and close(lse, want_lse, tol)
    for g, wg in zip(grads, want_grads):
        assert g.shape == wg.shape and close(g, wg, tol)


# -- the configuration's refusals ------------------------------------------------------------

@pytest.mark.parametrize("key, value", [
    ("n_group", 8), ("topk_group", 4), ("scoring_func", "softmax"),
    ("topk_method", "group_limited_greedy")])
def test_what_the_router_does_not_compute_is_refused(key, value):
    source = dict(reference_cfg(xing.XingConfig.tiny()), **{key: value})
    with pytest.raises(ValueError, match=key):
        xing.XingConfig.from_source(source)


def test_from_source_and_the_layout():
    cfg = xing.XingConfig.tiny()
    again = xing.XingConfig.from_source(dict(
        reference_cfg(cfg), moe_capacity_factor=cfg.capacity_factor))
    assert again == dataclasses.replace(cfg, dtype=again.dtype)
    assert cfg.layers_dense == (True, False, False)
    shapes = jax.eval_shape(lambda k: xing.init(k, cfg), jax.random.key(0))
    assert "mlp" in shapes["layer_0"] and "experts" not in shapes["layer_0"]
    assert shapes["layer_1"]["experts"]["gate_proj"].shape == (2, 32, 16)
    assert shapes["layer_1"]["router"].shape == (32, 8)
    assert shapes["mtp"]["eh_proj"].shape == (64, 32)
    assert xing.param_count(cfg) == sum(
        a.size for a in jax.tree.leaves(shapes))
    with pytest.raises(ValueError, match="prediction"):
        xing.XingConfig.tiny(num_nextn_predict_layers=2)
    with pytest.raises(ValueError, match="published_layer_index"):
        xing.XingConfig.from_source(dict(reference_cfg(cfg),
                                         num_hidden_layers=2))
