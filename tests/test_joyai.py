"""``models/xing.py`` on its PLAIN residual path (a source without
``hc_mult``: JoyAI-LLM-Flash) at a tiny preset (hidden 32, 4 heads of 8 + 4
wide keys over an 8-wide value, 16 routed experts of which 2 are held, 3 a
token, one dense and two expert layers, the prediction module, interleaved
rotary pairs, plain frequencies; 24 positions) against the plain reference
``chipbench/reference/joyai.py`` on seeded weights: logits of both heads,
both losses, every leaf's gradient, the router's loads; the 16 shares of a
layer; the rotary pairing; ``remat``; and the hyper-connected path held to
the parent's program.

Tolerances: float32 on both sides within 1e-5 of the largest entry for
logits and losses (the reference runs its products at "highest"; the
attention, the grouped products and the blocks sum in other orders), 2e-4
for gradients (``tests/test_xing.py``'s, sums over 48 positions of such
terms); bf16 compute against the float32 reference within 3e-2 of the
largest logit and 2e-3 of the loss (eight bits of mantissa through five
blocks)."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import joyai as ref
from pytorch_ps_mpi_tpu.models import xing

T = 24


def close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


def tiny(**kw):
    return xing.XingConfig.tiny(**dict(dict(
        hc_mult=0, rope_scaling=None, rope_interleave=True,
        rope_theta=32e6, first_k_dense_replace=1, layer_index=(0, 1, 2),
        n_routed_experts=16, num_experts_per_tok=3, experts_held=(0, 2),
        routed_scaling_factor=2.5, capacity_factor=16 * 2 / (3 * 2)), **kw))


def reference_cfg(cfg):
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d.update(n_routed_experts=cfg.experts_held[1],
             published_n_routed_experts=cfg.n_routed_experts,
             first_expert=cfg.experts_held[0],
             published_layer_index=list(cfg.layer_index),
             num_hidden_layers=len(cfg.layer_index),
             rope_scaling=dict(cfg.rope_scaling) if cfg.rope_scaling else None)
    d.pop("hc_mult")
    return d


def case(seed=0, **kw):
    cfg = tiny(**kw)
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


BLOCKS = dict(ROW_CHUNK=8, Q_CHUNK=8, HEAD_CHUNK=2)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The reference's blocks at this size: several of each."""
    for name, size in BLOCKS.items():
        monkeypatch.setattr(ref, name, size)


def named(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture(scope="module")
def gradients():
    """Loss and gradients of program and reference, once for the module
    (at the small blocks: a module's fixture cannot take ``monkeypatch``)."""
    before = {b: getattr(ref, b) for b in BLOCKS}
    for name, size in BLOCKS.items():
        setattr(ref, name, size)
    try:
        cfg, params, batch = case()
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: xing.causal_lm_loss(p, batch, cfg)))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_loss(p, batch, reference_cfg(cfg))))(params)
    finally:
        for name, size in before.items():
            setattr(ref, name, size)
    return float(loss), float(want), named(grads), named(want_grads)


# -- the plain path against the reference -------------------------------------------

def test_the_plain_path_has_no_stream_and_no_hyper_connection_leaf():
    cfg, params, batch = case()
    assert cfg.hc_mult == 0
    assert not [p for p in named(params) if "hc_" in p]
    assert set(params["layer_0"]) == {
        "input_layernorm", "post_attention_layernorm", "self_attn", "mlp"}
    assert set(params["mtp"]["layer"]) == {
        "input_layernorm", "post_attention_layernorm", "self_attn", "router",
        "e_score_correction_bias", "experts", "shared"}
    h, loads = xing.hidden_states(params, batch["tokens"], cfg)
    assert h.shape == (2, T, cfg.hidden_size) and loads.shape == (2, 2)
    # no hyper-connection scope, and none of their operations, in the text
    text = jax.jit(jax.grad(lambda p: xing.causal_lm_loss(
        p, batch, cfg))).lower(params).as_text(debug_info=True)
    assert "hc." not in text and "attn.mla_proj" in text
    assert "mtp.attn.mla" in text and "loss.mtp" in text
    with pytest.raises(ValueError, match="hc_mult 1"):
        tiny(hc_mult=1)


@pytest.mark.parametrize("attention", ["einsum", "flash"])
@pytest.mark.parametrize("remat", [False, True])
def test_logits_of_both_heads_losses_and_loads(attention, remat):
    cfg, params, batch = case(attention=attention, remat=remat)
    rcfg = reference_cfg(cfg)
    main, second, loads = jax.jit(
        lambda p: xing.apply(p, batch["tokens"], cfg))(params)
    want_main, want_second = jax.jit(
        lambda p: ref.logits(p, batch, rcfg))(params)
    assert main.dtype == second.dtype == jnp.float32
    assert close(main, want_main) and close(second, want_second)
    want_loads = jax.jit(lambda p: ref.router_loads(p, batch, rcfg))(params)
    assert loads.shape == (3, 2)       # two expert layers and the module's
    assert np.array_equal(loads, want_loads)
    loss = jax.jit(lambda p: xing.causal_lm_loss(p, batch, cfg))(params)
    want = jax.jit(lambda p: reference_loss(p, batch, rcfg))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))


def test_both_losses_apart():
    """``terms`` is CE(main) + 0.3 CE(module), each a mean over its own
    positions; the program's two terms are the reference's two."""
    cfg, params, batch = case()
    rcfg = reference_cfg(cfg)
    first, second = jax.jit(lambda p: ref.losses(p, batch, rcfg))(params)
    first, second = float(first) / (2 * (T - 1)), float(second) / (2 * (T - 2))
    loss = lambda w: float(jax.jit(lambda p: xing.causal_lm_loss(
        p, batch, dataclasses.replace(cfg, mtp_loss_weight=w)))(params))
    assert abs(loss(0.0) - first) < 1e-5 * first
    assert abs(loss(1.0) - loss(0.0) - second) < 1e-5 * second
    assert abs(loss(0.3) - (first + 0.3 * second)) < 1e-5 * first
    # the module's term is 0.3 of 1.3 of the loss, near enough: a
    # comparison that passes without it is too loose
    assert 0.2 < 0.3 * second / loss(0.3) < 0.26
    # without the module the loss is the next-token loss alone
    alone = dataclasses.replace(cfg, num_nextn_predict_layers=0)
    trunk = {k: v for k, v in params.items() if k != "mtp"}
    assert float(xing.causal_lm_loss(trunk, batch, alone)) == pytest.approx(
        first, rel=1e-5)
    assert float(reference_loss(trunk, batch, reference_cfg(alone))) == \
        pytest.approx(first, rel=1e-5)


LEAVES = [
    "['embed_tokens']", "['lm_head']", "['norm']",
    "['layer_0']['input_layernorm']", "['layer_0']['mlp']['gate_proj']",
    "['layer_0']['mlp']['up_proj']", "['layer_0']['mlp']['down_proj']",
    "['layer_0']['self_attn']['q_a_proj']",
    "['layer_0']['self_attn']['q_a_layernorm']",
    "['layer_0']['self_attn']['q_b_proj']",
    "['layer_0']['self_attn']['kv_a_proj_with_mqa']",
    "['layer_0']['self_attn']['kv_a_layernorm']",
    "['layer_0']['self_attn']['kv_b_proj']",
    "['layer_0']['self_attn']['o_proj']",
    "['layer_1']['post_attention_layernorm']", "['layer_1']['router']",
    "['layer_1']['experts']['gate_proj']", "['layer_1']['experts']['up_proj']",
    "['layer_1']['experts']['down_proj']",
    "['layer_1']['shared']['gate_proj']", "['layer_2']['shared']['down_proj']",
    "['layer_2']['self_attn']['kv_b_proj']", "['layer_2']['router']",
    "['mtp']['eh_proj']", "['mtp']['enorm']", "['mtp']['hnorm']",
    "['mtp']['norm']", "['mtp']['layer']['self_attn']['q_b_proj']",
    "['mtp']['layer']['experts']['down_proj']",
    "['mtp']['layer']['shared']['up_proj']", "['mtp']['layer']['router']",
]


@pytest.mark.parametrize("leaf", LEAVES)
def test_the_gradient_of(leaf, gradients):
    _, _, grads, want = gradients
    assert np.abs(want[leaf]).max() > 1e-9, "no gradient to compare"
    assert close(grads[leaf], want[leaf], 2e-4), leaf


def test_every_other_gradient_and_the_frozen_bias(gradients):
    loss, want_loss, grads, want = gradients
    assert abs(loss - want_loss) < 1e-5 * abs(want_loss)
    assert set(grads) == set(want) and set(LEAVES) < set(grads)
    biases = [p for p in grads if "e_score_correction_bias" in p]
    assert len(biases) == 3
    for path, g in grads.items():
        if path in biases:       # exactly zero, in program and reference
            assert not np.any(g) and not np.any(want[path]), path
        else:
            assert np.any(g) and close(g, want[path], 2e-4), path


def test_bf16_compute_stays_within_its_band():
    cfg, params, batch = case(dtype=jnp.bfloat16)
    rcfg = reference_cfg(dataclasses.replace(cfg, dtype=jnp.float32))
    main, second, _ = jax.jit(
        lambda p: xing.apply(p, batch["tokens"], cfg))(params)
    want_main, want_second = jax.jit(
        lambda p: ref.logits(p, batch, rcfg))(params)
    assert main.dtype == jnp.float32
    assert close(main, want_main, 3e-2) and close(second, want_second, 3e-2)
    assert not close(main, want_main, 1e-5)      # it IS another precision
    loss = float(jax.jit(lambda p: xing.causal_lm_loss(p, batch, cfg))(params))
    want = float(jax.jit(lambda p: reference_loss(p, batch, rcfg))(params))
    assert abs(loss - want) < 2e-3 * want


def test_remat_on_and_off_give_equal_gradients():
    cfg, params, batch = case(attention="flash")
    grad = lambda c: named(jax.jit(jax.grad(
        lambda p: xing.causal_lm_loss(p, batch, c)))(params))
    off, on = grad(cfg), grad(dataclasses.replace(cfg, remat=True))
    for path in off:
        assert np.array_equal(off[path], on[path]), path


def test_remat_carries_x_and_keeps_what_the_checkpoint_names():
    """The layer's checkpoint has ``x [b, s, d]`` as its explicit carry and
    saves the flash kernels' output and logsumexp, the expert layer's plan
    and the router's choice: the backward pass runs no second flash
    forward and no second ``top_k``."""
    cfg, params, batch = case(attention="flash", remat=True)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: xing.causal_lm_loss(p, batch, cfg)))(params))
    layers = len(cfg.layer_index) + 1
    for name in ("flash.out", "flash.lse", "moe.plan"):
        assert f"name={name}" in text, name
    # one forward kernel a layer (without the policy: two)
    assert text.count("name=flash.out") == layers
    # one top_k an expert layer
    assert text.count("top_k[") == 3


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


def test_embedding_and_head_gradients_sum_over_trunk_and_module():
    cfg, params, batch = case()
    grad = lambda w: jax.jit(jax.grad(lambda p: xing.causal_lm_loss(
        p, batch, dataclasses.replace(cfg, mtp_loss_weight=w))))(params)
    g0, g3, g1 = grad(0.0), grad(0.3), grad(1.0)
    for leaf in ("embed_tokens", "lm_head"):
        module = g1[leaf] - g0[leaf]
        assert np.abs(module).max() > 1e-3 * np.abs(g0[leaf]).max()
        assert close(g3[leaf], g0[leaf] + 0.3 * module, 1e-5)
    assert not np.any(g0["mtp"]["eh_proj"])


# -- the shares of a deployment ----------------------------------------------------------

def test_sixteen_expert_shares_and_the_shared_expert_once_are_the_layer():
    """Every chip computes the shared expert alike and its own experts'
    part: the 16 routed parts plus the shared expert counted ONCE are what
    the uncut layer gives, in program and reference."""
    cfg, params, _ = case(experts_held=(0, 16), capacity_factor=16.0 / 3)
    lp = params["layer_1"]
    u = jax.random.normal(jax.random.key(5), (2, T, cfg.hidden_size))
    whole, loads = xing.expert_ffn(u, lp, cfg)
    shared = xing.swiglu(u, lp["shared"], cfg.dtype, "moe.shared")
    flat = u.reshape(-1, cfg.hidden_size)
    parts, counts = [], []
    for first in range(16):
        share = dataclasses.replace(cfg, experts_held=(first, 1),
                                    capacity_factor=16.0)
        mine = dict(lp, experts=jax.tree.map(lambda a: a[first:first + 1],
                                             lp["experts"]))
        y, n = xing.expert_ffn(u, mine, share)
        parts.append(y - shared)
        counts.append(int(n[0]))
        want, want_n = ref.expert_layer(flat, mine, reference_cfg(share))
        assert close(y.reshape(flat.shape), want) and int(want_n[0]) == int(n[0])
    assert close(shared + sum(parts), whole)
    assert counts == loads.tolist()
    assert sum(counts) == 2 * T * cfg.num_experts_per_tok
    want, _ = ref.expert_layer(flat, lp, reference_cfg(cfg))
    assert close(whole.reshape(flat.shape), want)


def test_no_pair_is_dropped_at_the_worst_case():
    """A bias that sends every position to the two held experts: the
    buffer of ``capacity_factor`` = experts x min(top_k, held) / (top_k x
    held) holds all of them."""
    cfg, params, _ = case()
    assert cfg.capacity_factor == 16 * min(3, 2) / (3 * 2)
    lp = dict(params["layer_1"], e_score_correction_bias=jnp.zeros(16).at[
        :2].set(10.0))
    u = jax.random.normal(jax.random.key(6), (2, T, cfg.hidden_size))
    y, loads = xing.expert_ffn(u, lp, cfg)
    assert loads.tolist() == [2 * T, 2 * T]
    want, _ = ref.expert_layer(u.reshape(-1, cfg.hidden_size), lp,
                               reference_cfg(cfg))
    assert close(y.reshape(want.shape), want)


# -- the rotary pairing --------------------------------------------------------------------

def interleaved_to_halves(w, heads, nope, rope, lead=0):
    """The columns of a projection whose heads end in ``rope`` rotary
    columns (after ``lead`` others), each head's rotary columns permuted
    ``[0, 2, 4, ..., 1, 3, 5, ...]``: what makes the rotate-half form
    compute the interleaved rotation."""
    order = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    body = w[:, lead:].reshape(w.shape[0], heads, nope + rope)
    body = jnp.concatenate([body[..., :nope], body[..., nope:][..., order]], -1)
    return jnp.concatenate([w[:, :lead], body.reshape(w.shape[0], -1)], axis=1)


def test_interleaved_pairs_are_rotate_half_under_a_column_permutation():
    """The program turns the published pairs ``(2i, 2i + 1)``: the same
    weights under rotate-half give OTHER logits, and rotate-half gives the
    same logits once the rotary columns of ``q_b_proj`` (each head's last
    ``rope``) and of ``kv_a_proj_with_mqa`` (its last ``rope``) are
    permuted ``[0, 2, ..., 1, 3, ...]``."""
    cfg, params, batch = case()
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    halves = dataclasses.replace(cfg, rope_interleave=False)
    logits = lambda p, c: xing.apply(p, batch["tokens"], c)[:2]
    main, second = logits(params, cfg)
    assert not close(logits(params, halves)[0], main, 1e-3)

    def permuted(attn):
        return dict(
            attn,
            q_b_proj=interleaved_to_halves(
                attn["q_b_proj"], cfg.num_attention_heads, nope, rope),
            kv_a_proj_with_mqa=interleaved_to_halves(
                attn["kv_a_proj_with_mqa"], 1, 0, rope, cfg.kv_lora_rank))

    turned = dict(params)
    for i in range(3):
        turned[f"layer_{i}"] = dict(params[f"layer_{i}"], self_attn=permuted(
            params[f"layer_{i}"]["self_attn"]))
    turned["mtp"] = dict(params["mtp"], layer=dict(
        params["mtp"]["layer"],
        self_attn=permuted(params["mtp"]["layer"]["self_attn"])))
    same_main, same_second = logits(turned, halves)
    assert close(same_main, main, 1e-6) and close(same_second, second, 1e-6)
    # and the reference without the key rotates halves too
    want, _ = ref.logits(turned, batch, reference_cfg(halves))
    assert close(same_main, want)


def test_the_references_rotation_is_the_published_pairing():
    """Pair i = columns (2i, 2i + 1), turned by position x theta^(-2i /
    rope), each value where it lies."""
    x = jax.random.normal(jax.random.key(0), (T, 2, 4))
    freq = ref.frequencies({"qk_rope_head_dim": 4, "rope_theta": 32e6})
    assert np.allclose(freq, [1.0, 32e6 ** -0.5])
    out = np.asarray(ref.rotary(x, jnp.arange(T), freq, True))
    for pos in (0, 5, T - 1):
        for i in range(2):
            c, s = np.cos(pos * freq[i]), np.sin(pos * freq[i])
            a, b = np.asarray(x[pos, :, 2 * i]), np.asarray(x[pos, :, 2 * i + 1])
            assert np.allclose(out[pos, :, 2 * i], a * c - b * s, atol=1e-6)
            assert np.allclose(out[pos, :, 2 * i + 1], b * c + a * s, atol=1e-6)
    with pytest.raises(ValueError, match="rope_scaling"):
        ref.frequencies({"qk_rope_head_dim": 4, "rope_theta": 1e4,
                         "rope_scaling": {"type": "yarn"}})


def test_latent_attention_is_the_references_at_plain_frequencies():
    cfg, params, _ = case()
    assert xing.yarn_frequencies(cfg)[1:] == (1.0, 1.0)    # no mscale
    p = params["layer_0"]["self_attn"]
    u = jax.random.normal(jax.random.key(6), (2, T, cfg.hidden_size))
    out = xing.latent_attention(u, p, cfg, jnp.arange(T))
    for row in range(2):
        assert close(out[row], ref.attention(u[row], p, reference_cfg(cfg)))


# -- the hyper-connected path is the parent's program -----------------------------------------

# sha256 of the tiny hyper-connected preset's loss and of its gradients'
# bytes (leaves in path order), computed with the PARENT's models/xing.py
# (commit 8445f6a) by the statements of ``hc_digest`` on this machine's
# CPU backend; a backend whose parent reads otherwise skips the digest
# and the test still holds the lowered text's scopes. PR 40 put ONE backward
# flash kernel where two were (``ops/attention_pallas.py``): the loss and
# every gradient through the four attention layers kept 8445f6a's digests
# (the kernel adds a row's terms in the pair's order), the text is PR 40's
# (7c44feb20892304c... before it)
PARENT = {
    "loss": "187eab777a1d3d0623eae0731fba64905837d7c403db407a4376708ba9c41666",
    "grads": "9e5806610d3a1e61acd3036323cc69a26f31409c5bd042cce4a3c9b577651ea1",
    "text": "ddbf732c853c9bae1bdcf5b06e63694d2cdce6276da87fcf8f0a646e21b2cad3",
}


def hc_digest():
    cfg = xing.XingConfig.tiny(attention="flash", remat=True)
    params = xing.init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, T), 0, cfg.vocab_size)
    fn = jax.jit(jax.value_and_grad(
        lambda p: xing.causal_lm_loss(p, {"tokens": tokens}, cfg)))
    loss, grads = fn(params)
    h = hashlib.sha256()
    for _, g in sorted(named(grads).items()):
        h.update(np.asarray(g).tobytes())
    text = fn.lower(params).as_text()
    return {"loss": hashlib.sha256(np.asarray(loss).tobytes()).hexdigest(),
            "grads": h.hexdigest(),
            "text": hashlib.sha256(text.encode()).hexdigest()}, cfg, params


def test_a_file_with_hc_mult_4_is_the_parents_program_bit_for_bit():
    """Loss, every gradient and the lowered program text of the tiny
    hyper-connected preset are the parent's, byte for byte."""
    got, cfg, params = hc_digest()
    assert cfg.hc_mult == 4 and "hc_attn" in params["layer_0"]
    assert got == PARENT
    source = dict(reference_cfg(tiny()), hc_mult=4)
    assert xing.XingConfig.from_source(source).hc_mult == 4
    assert xing.XingConfig.from_source(reference_cfg(tiny())).hc_mult == 0


def test_model_plan_row_on_both_paths():
    from pytorch_ps_mpi_tpu import telemetry

    def rows(cfg, params, batch):
        rec = telemetry.configure()
        try:
            xing.causal_lm_loss(params, batch, cfg)
            return rec.events()
        finally:
            telemetry.disable()

    cfg, params, batch = case()
    events = rows(cfg, params, batch)
    plans = [e["attrs"] for e in events if e["name"] == "model.plan"]
    assert plans == [dict(
        dense_layers=[0], expert_layers=[1, 2], residual="plain", streams=0,
        prediction_modules=1, first_expert=0, experts_held=2, experts=16,
        vocab_rows=96, vocab_published=96)]
    assert not [e for e in events if e["name"] == "hc.plan"]
    hc_cfg = xing.XingConfig.tiny(published_vocab_size=768)
    hc_params = xing.init(jax.random.key(0), hc_cfg)
    events = rows(hc_cfg, hc_params, batch)
    # the recorder begins with the set-up log, which holds the first row
    _, plan = [e["attrs"] for e in events if e["name"] == "model.plan"]
    assert (plan["residual"], plan["streams"]) == ("hc", 4)
    assert (plan["dense_layers"], plan["expert_layers"]) == ([0], [2, 3])
    assert (plan["vocab_rows"], plan["vocab_published"]) == (96, 768)
    assert len([e for e in events if e["name"] == "hc.plan"]) == 1


# -- the configuration's refusals -----------------------------------------------------------------

@pytest.mark.parametrize("key, value", [
    ("n_group", 2), ("topk_group", 4), ("scoring_func", "softmax"),
    ("topk_method", "group_limited_greedy")])
def test_what_the_router_does_not_compute_is_refused(key, value):
    source = dict(reference_cfg(tiny()), **{key: value})
    with pytest.raises(ValueError, match=key):
        xing.XingConfig.from_source(source)


def test_from_source_and_the_layout():
    cfg = tiny()
    again = xing.XingConfig.from_source(dict(
        reference_cfg(cfg), moe_capacity_factor=cfg.capacity_factor))
    assert again == dataclasses.replace(cfg, dtype=again.dtype)
    assert cfg.layers_dense == (True, False, False)
    shapes = jax.eval_shape(lambda k: xing.init(k, cfg), jax.random.key(0))
    assert shapes["layer_1"]["experts"]["gate_proj"].shape == (2, 32, 16)
    assert shapes["layer_1"]["router"].shape == (32, 16)
    assert shapes["mtp"]["eh_proj"].shape == (64, 32)
    assert xing.param_count(cfg) == sum(
        a.size for a in jax.tree.leaves(shapes))
    # the hyper-connected twin holds the same leaves and ten more a layer
    twin = dataclasses.replace(cfg, hc_mult=4)
    assert xing.param_count(twin) > xing.param_count(cfg)
