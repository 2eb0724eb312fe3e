"""The ``solar`` family (``models/solar.py`` through ``models/lfm2.py``'s
pair decoder) at a tiny preset that keeps both operator kinds (hidden 32;
a NoPE gated GQA layer of 4 query over 2 key-value heads of 8, then two
gated delta-rule layers of 2 heads of 8 with 4 taps, chunk 64; 8 routed
experts of which 2 are held, top-2, beside a shared one; an untied head;
21 positions, which no chunk divides) against the plain reference
``chipbench/reference/solar.py`` on seeded weights: the stack, every leaf's
gradient, the shares of a deployment (heads and experts), the counts, the
scopes, and what each switch of the widened shared code does.

Tolerances: float32 on both sides, 2e-4 of the largest entry (the
reference runs its products at "highest" and the delta rule token by
token; the program chunks it, and sums attention, the grouped products and
the blocks in other orders)."""

import contextlib
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import lfm2 as ref_lfm2
from chipbench.reference import solar as ref
from pytorch_ps_mpi_tpu.models import lfm2, sdar_moe, solar
from pytorch_ps_mpi_tpu.ops import short_conv

T = 21
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


def reference_cfg(cfg):
    """The configuration file's dictionary the reference reads."""
    c = cfg
    return dict(
        rms_norm_eps=c.rms_norm_eps, num_attention_heads=c.num_attention_heads,
        num_key_value_heads=c.num_key_value_heads, head_dim=c.head_dim,
        use_rope=c.use_rope, use_gqa_gate=c.use_gqa_gate,
        gqa_layers=[i for i, k in zip(c.layer_index, c.layer_types)
                    if k == "full_attention"],
        published_layer_index=list(c.layer_index),
        num_hidden_layers=len(c.layer_index),
        linear_attn_config=dict(
            num_heads=c.linear_num_heads, head_dim=c.linear_head_dim,
            short_conv_kernel_size=c.short_conv_kernel_size,
            num_kv_heads=None),
        kda_allow_neg_eigval=c.kda_allow_neg_eigval,
        n_routed_experts=c.experts_held[1], first_expert=c.experts_held[0],
        num_experts_per_tok=c.num_experts_per_tok,
        norm_topk_prob=c.norm_topk_prob,
        routed_scaling_factor=c.routed_scaling_factor)


def case(seed=0, **kw):
    cfg = solar.SolarConfig.tiny(**kw)
    params = solar.init(jax.random.key(seed), cfg, scale=0.3)
    # off the seed's zeros and ones: every bias and gain takes part
    params = jax.tree.map(lambda a: a + 0.05 * jax.random.normal(
        jax.random.key(a.size), a.shape), params)
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, T), 0,
                                cfg.vocab_size)
    return cfg, params, {"tokens": tokens}


def reference_loss(params, batch, rcfg):
    total, count = ref.terms(params, batch, rcfg)
    return total / count


@contextlib.contextmanager
def small_blocks():
    """The reference's blocks at this size: several of each."""
    before = ref_lfm2.ROW_CHUNK, ref.Q_CHUNK, ref.TOKEN_BLOCK
    ref_lfm2.ROW_CHUNK, ref.Q_CHUNK, ref.TOKEN_BLOCK = 8, 8, 8
    try:
        yield
    finally:
        ref_lfm2.ROW_CHUNK, ref.Q_CHUNK, ref.TOKEN_BLOCK = before


@pytest.fixture(autouse=True)
def blocks():
    with small_blocks():
        yield


@pytest.fixture(scope="module")
def gradients():
    """Loss and gradients of program and reference, once for the module."""
    with small_blocks():
        cfg, params, batch = case()
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: lfm2.causal_lm_loss(p, batch, cfg)))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_loss(p, batch, reference_cfg(cfg))))(params)
    named = lambda tree: {jax.tree_util.keystr(k): v for k, v in
                          jax.tree_util.tree_leaves_with_path(tree)}
    return float(loss), float(want), named(grads), named(want_grads)


# -- the stack against the reference -----------------------------------------------

@pytest.mark.parametrize("attention", ["einsum", "flash"])
@pytest.mark.parametrize("remat", [False, True])
def test_the_stack(attention, remat):
    cfg, params, batch = case(attention=attention, remat=remat)
    rcfg = reference_cfg(cfg)
    logits, loads = jax.jit(
        lambda p: lfm2.apply(p, batch["tokens"], cfg))(params)
    assert logits.shape == (2, T, cfg.vocab_size)
    assert close(logits, jax.jit(lambda p: ref.logits(p, batch, rcfg))(params))
    assert loads.shape == (3, 2)      # three expert layers, two held experts
    assert np.array_equal(loads, jax.jit(
        lambda p: ref.router_loads(p, batch, rcfg))(params))
    assert np.array_equal(loads, jax.jit(
        lambda p: lfm2.router_loads(p, batch, cfg))(params))
    loss = jax.jit(lambda p: lfm2.causal_lm_loss(p, batch, cfg))(params)
    want = jax.jit(lambda p: reference_loss(p, batch, rcfg))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))


def test_the_loss(gradients):
    loss, want, _, _ = gradients
    assert abs(loss - want) < 1e-5 * abs(want)


def leaves():
    cfg = solar.SolarConfig.tiny()
    shapes = jax.eval_shape(lambda k: solar.init(k, cfg), jax.random.key(0))
    return [jax.tree_util.keystr(k)
            for k, _ in jax.tree_util.tree_leaves_with_path(shapes)]


@pytest.mark.parametrize("leaf", leaves())
def test_the_gradient_of(leaf, gradients):
    _, _, got, want = gradients
    assert np.any(np.asarray(want[leaf])), "a gradient of zeros shows nothing"
    assert close(got[leaf], want[leaf]), leaf


def test_every_leaf_of_both_kinds_is_there(gradients):
    _, _, got, want = gradients
    assert sorted(got) == sorted(want) == sorted(leaves())
    assert "['layer_0']['self_attn']['g_proj']" in got
    assert "['layer_0']['self_attn']['q_norm']" not in got
    for name in ("A_log", "dt_bias", "q_conv", "f_a_proj", "g_b_bias",
                 "o_norm"):
        assert f"['layer_1']['linear_attn']['{name}']" in got, name
    assert "['layer_2']['shared']['down_proj']" in got
    assert "['lm_head']" in got and "['layer_0']['expert_bias']" not in got


def test_the_operators_are_the_references():
    cfg, params, _ = case()
    rcfg = reference_cfg(cfg)
    u = jax.random.normal(jax.random.key(3), (2, T, cfg.hidden_size))
    attn = lfm2.gqa_attention(
        u, params["layer_0"]["self_attn"], cfg, jnp.arange(T), "causal",
        scope="attn.gqa", proj_scope="attn.gqa_proj")
    rule = lfm2.delta_rule_operator(u, params["layer_1"]["linear_attn"], cfg)
    for r in range(2):
        assert close(attn[r], ref.attention(
            u[r], params["layer_0"]["self_attn"], rcfg))
        assert close(rule[r], ref.delta_rule(
            u[r], params["layer_1"]["linear_attn"], rcfg))


# -- the shares of a deployment -------------------------------------------------------

def head_share(lp, kind, cfg, first, count):
    """The leaves of heads ``first .. first + count`` (and, for the GQA
    layer, the key-value heads that go with them): what a chip of a
    head-split layer holds; the low-rank decay's and gate's first matrices
    are whole."""
    if kind == "full_attention":
        hd, group = cfg.head_dim, (cfg.num_attention_heads
                                   // cfg.num_key_value_heads)
        q = slice(first * hd, (first + count) * hd)
        kv = slice(first // group * hd, (first + count) // group * hd)
        return {"q_proj": lp["q_proj"][:, q], "g_proj": lp["g_proj"][:, q],
                "k_proj": lp["k_proj"][:, kv], "v_proj": lp["v_proj"][:, kv],
                "o_proj": lp["o_proj"][q]}
    hd = cfg.linear_head_dim
    cols = slice(first * hd, (first + count) * hd)
    out = dict(lp)
    for name in ("q_proj", "k_proj", "v_proj", "f_b_proj", "g_b_proj"):
        out[name] = lp[name][:, cols]
    for name in ("q_conv", "k_conv", "v_conv", "dt_bias", "g_b_bias",
                 "o_proj"):
        out[name] = lp[name][cols]
    out["b_proj"] = lp["b_proj"][:, first:first + count]
    out["A_log"] = lp["A_log"][first:first + count]
    return out


@pytest.mark.parametrize("layer, kind", [(0, "full_attention"),
                                         (1, "linear_attention")])
def test_head_and_expert_shares_are_the_uncut_layer(layer, kind):
    """Chips share a layer: its heads (the GQA layer's 4 query over 2
    key-value heads as two chips of 2 over 1; the delta rule's 4 heads as
    four chips of 1) and its 8 experts (four chips of 2). Every chip
    computes its own heads' and experts' parts; the router and the shared
    expert are computed alike on every chip and counted once. The parts add
    up to the uncut reference layer, operator and expert layer both."""
    cfg, params, _ = case(num_attention_heads=4, num_key_value_heads=2,
                          linear_num_heads=4, experts_held=(0, 8))
    rcfg = reference_cfg(cfg)
    lp = params[f"layer_{layer}"]
    x = jax.random.normal(jax.random.key(5), (2, T, cfg.hidden_size))
    u = sdar_moe.rms_norm(x, lp["operator_norm"], cfg.rms_norm_eps)
    op_parts = []
    if kind == "full_attention":
        share = dataclasses.replace(cfg, num_attention_heads=2,
                                    num_key_value_heads=1)
        for chip in range(2):
            op_parts.append(lfm2.gqa_attention(
                u, head_share(lp["self_attn"], kind, cfg, 2 * chip, 2),
                share, jnp.arange(T), "causal"))
    else:
        share = dataclasses.replace(cfg, linear_num_heads=1)
        for chip in range(4):
            op_parts.append(lfm2.delta_rule_operator(
                u, head_share(lp["linear_attn"], kind, cfg, chip, 1), share))
    x1 = x + sum(op_parts)
    a = sdar_moe.rms_norm(x1, lp["ffn_norm"], cfg.rms_norm_eps)
    ffn_parts = []
    for chip in range(4):
        mine = dict(lp, experts=jax.tree.map(
            lambda e: e[2 * chip:2 * chip + 2], lp["experts"]))
        if chip:   # the shared expert: counted once
            mine.pop("shared")
        y, _ = lfm2.expert_ffn(
            a, mine, dataclasses.replace(cfg, experts_held=(2 * chip, 2)))
        ffn_parts.append(y)
    got = x1 + sum(ffn_parts)
    for r in range(2):
        want, _ = ref.layer(x[r], lp, rcfg, kind)
        assert close(got[r], want)


def test_the_shared_expert_is_in_the_layer_once():
    cfg, params, _ = case()
    lp = params["layer_1"]
    u = jax.random.normal(jax.random.key(7), (2, T, cfg.hidden_size))
    with_it, loads = lfm2.expert_ffn(u, lp, cfg)
    without, same = lfm2.expert_ffn(u, {k: v for k, v in lp.items()
                                        if k != "shared"}, cfg)
    assert np.array_equal(loads, same)
    assert close(with_it - without, lfm2.swiglu(u, lp["shared"], cfg.dtype,
                                                "moe.shared"), 1e-5)


# -- the counts ------------------------------------------------------------------------

def config_file():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "solar-open2-250b.json")) as f:
        return json.load(f)


def test_param_count_of_the_published_model():
    config = config_file()
    uncut = dict(config, first_expert=0, published_layer_index=range(48),
                 **{k[len("published_"):]: v for k, v in config.items()
                    if k[len("published_"):] in config["reduced"]})
    cfg = solar.SolarConfig.from_source(uncut)
    assert lfm2.param_count(cfg, solar.init) == 250_288_089_856
    assert cfg.layer_types.count("full_attention") == 12
    assert cfg.layer_types.count("linear_attention") == 36
    assert (cfg.num_experts, cfg.experts_held, cfg.linear_num_heads,
            cfg.num_attention_heads, cfg.num_key_value_heads) == (
        320, (0, 320), 64, 64, 8)


def test_param_count_of_the_cut():
    cfg = solar.SolarConfig.from_source(config_file())
    assert lfm2.param_count(cfg, solar.init) == 840_874_392
    assert cfg.layer_types == ("full_attention", "linear_attention",
                               "linear_attention", "linear_attention")
    assert (cfg.num_experts, cfg.experts_held) == (320, (0, 8))
    assert cfg.dtype == jnp.bfloat16 and cfg.remat


@pytest.mark.parametrize("layer, part, want", [
    (1, "linear_attn", 18_135_176), (0, "self_attn", 13_631_488),
    (0, "experts", 8 * 15_728_640), (0, "shared", 15_728_640),
    (0, "router", 4096 * 320),
])
def test_param_count_of_a_part(layer, part, want):
    cfg = solar.SolarConfig.from_source(config_file())
    shapes = jax.eval_shape(lambda k: solar.init(k, cfg), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(
        shapes[f"layer_{layer}"][part])) == want


@pytest.mark.parametrize("change, match", [
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(kda_use_full_proj=True), "kda_use_full_proj"),
    (dict(num_hidden_layers=6), "published_layer_index"),
])
def test_what_is_not_built_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        solar.SolarConfig.from_source(dict(config_file(), **change))


def test_the_family_refuses_a_wrong_count():
    from chipbench.families import solar as family

    config, traffic = config_file(), {"seq": 8192, "generator": "lm_zipf"}
    family.build(config, traffic)
    with pytest.raises(ValueError, match="uncut sizes"):
        family.build(dict(config, published_parameter_count=250_000_000_000),
                     traffic)
    with pytest.raises(ValueError, match="uncut sizes"):   # a width changed
        family.build(dict(config, moe_intermediate_size=1024), traffic)


# -- the decay's initialisation ----------------------------------------------------------

def test_the_decay_starts_in_the_family_range():
    cfg = solar.SolarConfig.tiny(linear_num_heads=64, linear_head_dim=128)
    lp = solar.init_layer(jax.random.key(0), "linear_attention", cfg)[
        "linear_attn"]
    a = np.exp(np.asarray(lp["A_log"]))
    dt = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001


# -- the shared code, widened: each switch does what its leaf or key says ------------------

def attention_case(**leaves):
    """``sdar_moe``'s tiny attention at float32 (4 query heads over 2
    key-value heads of 16, hidden 64: ``o_proj`` the identity, so the
    output is the heads' own), with ``leaves`` added to the layer."""
    cfg = sdar_moe.SdarMoeConfig.tiny(attention="einsum")
    k = jax.random.split(jax.random.key(11), 4)
    w = lambda key, *shape: 0.3 * jax.random.normal(key, shape)
    lp = {"q_proj": w(k[0], 64, 64), "k_proj": w(k[1], 64, 32),
          "v_proj": w(k[2], 64, 32), "o_proj": jnp.eye(64), **leaves}
    x = jax.random.normal(k[3], (2, 9, 64))
    run = lambda lp, c=cfg, positions=jnp.arange(9): sdar_moe.gqa_attention(
        x, lp, c, positions, "causal")
    return cfg, lp, x, run


def test_the_qk_norm_is_applied_where_its_leaves_are():
    """RMSNorm does not see the scale of what it normalises: with
    ``q_norm`` / ``k_norm`` the projections' scale leaves the output as it
    was; without them it does not."""
    gain = 1.0 + 0.2 * jax.random.normal(jax.random.key(12), (16,))
    _, lp, _, run = attention_case(q_norm=gain, k_norm=gain[::-1])
    scaled = dict(lp, q_proj=3.0 * lp["q_proj"], k_proj=5.0 * lp["k_proj"])
    assert close(run(scaled), run(lp), 1e-5)
    bare = {k: v for k, v in lp.items() if not k.endswith("_norm")}
    assert not close(run(dict(bare, q_proj=3.0 * bare["q_proj"],
                              k_proj=5.0 * bare["k_proj"])), run(bare), 1e-2)


def test_rotary_is_left_out_where_use_rope_is_false():
    """Causal attention without a position encoding does not see the
    positions; with the rotary embedding (the default) it does."""
    cfg, lp, _, run = attention_case()
    spread = 3 * jnp.arange(9)
    assert not close(run(lp, positions=spread), run(lp), 1e-2)
    nope = solar.SolarConfig.tiny(num_attention_heads=4, num_key_value_heads=2,
                                  head_dim=16, attention="einsum")
    assert not nope.use_rope
    assert np.array_equal(run(lp, nope, spread), run(lp, nope))


def test_the_output_gate_is_applied_where_its_leaf_is():
    """``g_proj`` multiplies each head's output, element by element, by
    ``sigmoid(x W_g)`` before ``W_o``."""
    g_proj = 0.3 * jax.random.normal(jax.random.key(13), (64, 64))
    _, lp, x, run = attention_case()
    assert close(run(dict(lp, g_proj=g_proj)),
                 run(lp) * jax.nn.sigmoid(x @ g_proj), 1e-6)


def test_both_short_convolutions_are_one_tap_sum():
    """LFM2's gated form is ``C * conv(B * u)``; the plain form
    ``silu(conv(x))``: with B = C = 1 the gated form is the plain one's
    convolution."""
    x = jax.random.normal(jax.random.key(14), (2, 9, 6))
    taps = jax.random.normal(jax.random.key(15), (6, 4))
    ones = jnp.ones_like(x)
    conv = short_conv.gated_short_conv(jnp.concatenate([ones, ones, x], -1),
                                       taps)
    assert close(short_conv.silu_short_conv(x, taps, scope="kda.conv"),
                 jax.nn.silu(conv), 1e-6)
    # causal: the first position sees only itself, through the last tap
    assert close(conv[:, 0], x[:, 0] * taps[:, -1], 1e-6)


# -- what a device trace reads -----------------------------------------------------------

def test_the_scopes_a_device_trace_reads():
    cfg, params, batch = case(remat=True)
    text = jax.jit(jax.grad(
        lambda p: lfm2.causal_lm_loss(p, batch, cfg))).lower(
        params).as_text(debug_info=True)
    # a scope is a component of an operation's name path, bare or inside a
    # transformation's brackets (jobs/sync_train_streamed.py reads it so)
    found = set(re.findall(r'(?<=[/("])[a-z_]+(?:\.[a-z_]+)+(?=[/")])', text))
    assert {"kda.proj", "kda.conv", "kda.scan", "attn.gqa_proj", "attn.gqa",
            "moe.shared", "moe.route", "moe.dispatch", "moe.experts",
            "moe.combine", "loss.head"} <= found, found
    assert not {"conv.mix", "conv.proj", "mlp.swiglu"} & found


def test_the_plans_on_the_recorder():
    from pytorch_ps_mpi_tpu import telemetry

    cfg, params, batch = case(attention="flash")
    rec = telemetry.configure()
    try:
        lfm2.causal_lm_loss(params, batch, cfg)
        events = rec.events()
    finally:
        telemetry.disable()
    plans = [e["attrs"] for e in events if e["name"] == "kda.plan"]
    assert len(plans) == 2          # the two delta-rule layers
    assert all(p["rows"] == 2 and p["heads"] == 2 and p["T"] == T
               and p["chunk"] == 64 and p["dk"] == p["dv"] == 8 for p in plans)
    assert len([e for e in events if e["name"] == "moe.row_moves"]) == 3
