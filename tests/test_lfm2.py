"""``models/lfm2.py`` at a tiny preset that keeps both operator kinds and
both feed-forward kinds (hidden 32, 4 query over 2 key-value heads of 8, 3
taps, 8 routed experts of which 2 are held; a dense ``conv`` layer, an
expert attention layer, an expert ``conv`` layer; 24 positions) against the
plain reference ``chipbench/reference/lfm2.py`` on seeded weights: the
stack, every leaf's gradient, the shares of a deployment, the counts, and
the router's frozen bias.

Tolerances: float32 on both sides, 2e-4 of the largest entry (the
reference runs its products at "highest"; the attention, the grouped
products and the blocks sum in other orders)."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import lfm2 as ref
from pytorch_ps_mpi_tpu.models import lfm2, sdar_moe, xing
from pytorch_ps_mpi_tpu.parallel import dropless

T = 24
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = ["conv", "conv", "full_attention", "conv"]   # tiny's layer list


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


def reference_cfg(cfg):
    """The configuration file's dictionary the reference reads."""
    published = list(PUBLISHED)
    for kind, i in zip(cfg.layer_types, cfg.layer_index):
        published[i] = kind
    return dict(
        hidden_size=cfg.hidden_size, norm_eps=cfg.norm_eps,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        rope_parameters={"rope_theta": cfg.rope_theta},
        layer_types=published, published_layer_index=list(cfg.layer_index),
        num_hidden_layers=len(cfg.layer_index),
        num_dense_layers=cfg.num_dense_layers,
        num_experts=cfg.experts_held[1], first_expert=cfg.experts_held[0],
        published_num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        conv_L_cache=cfg.conv_L_cache)


def case(seed=0, **kw):
    cfg = lfm2.Lfm2Config.tiny(**kw)
    params = lfm2.init(jax.random.key(seed), cfg, scale=0.3)
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


@pytest.fixture(scope="module")
def gradients():
    """Loss and gradients of program and reference, once for the module
    (at the small blocks: a module's fixture cannot take ``monkeypatch``)."""
    before = ref.ROW_CHUNK, ref.Q_CHUNK
    ref.ROW_CHUNK, ref.Q_CHUNK = 8, 8
    try:
        cfg, params, batch = case()
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: lfm2.causal_lm_loss(p, batch, cfg)))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_loss(p, batch, reference_cfg(cfg))))(params)
    finally:
        ref.ROW_CHUNK, ref.Q_CHUNK = before
    named = lambda tree: {jax.tree_util.keystr(k): v for k, v in
                          jax.tree_util.tree_leaves_with_path(tree)}
    return float(loss), float(want), named(grads), named(want_grads)


# -- the stack against the reference -------------------------------------------------

@pytest.mark.parametrize("attention", ["einsum", "flash"])
@pytest.mark.parametrize("remat", [False, True])
def test_the_stack(attention, remat):
    cfg, params, batch = case(attention=attention, remat=remat)
    rcfg = reference_cfg(cfg)
    logits, loads = jax.jit(
        lambda p: lfm2.apply(p, batch["tokens"], cfg))(params)
    assert logits.shape == (2, T, cfg.vocab_size)
    assert close(logits, jax.jit(lambda p: ref.logits(p, batch, rcfg))(params))
    assert loads.shape == (2, 2)      # two expert layers, two held experts
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


LEAVES = [
    "['embed_tokens']", "['embedding_norm']",
    "['layer_0']['operator_norm']", "['layer_0']['ffn_norm']",
    "['layer_0']['conv']['in_proj']", "['layer_0']['conv']['conv']",
    "['layer_0']['conv']['out_proj']",
    "['layer_0']['feed_forward']['gate_proj']",
    "['layer_0']['feed_forward']['up_proj']",
    "['layer_0']['feed_forward']['down_proj']",
    "['layer_1']['operator_norm']", "['layer_1']['ffn_norm']",
    "['layer_1']['self_attn']['q_proj']", "['layer_1']['self_attn']['k_proj']",
    "['layer_1']['self_attn']['v_proj']", "['layer_1']['self_attn']['o_proj']",
    "['layer_1']['self_attn']['q_norm']", "['layer_1']['self_attn']['k_norm']",
    "['layer_1']['router']", "['layer_1']['experts']['gate_proj']",
    "['layer_1']['experts']['up_proj']", "['layer_1']['experts']['down_proj']",
    "['layer_2']['operator_norm']", "['layer_2']['ffn_norm']",
    "['layer_2']['conv']['in_proj']", "['layer_2']['conv']['conv']",
    "['layer_2']['conv']['out_proj']", "['layer_2']['router']",
    "['layer_2']['experts']['gate_proj']", "['layer_2']['experts']['up_proj']",
    "['layer_2']['experts']['down_proj']",
]
FROZEN = ["['layer_1']['expert_bias']", "['layer_2']['expert_bias']"]


@pytest.mark.parametrize("leaf", LEAVES)
def test_the_gradient_of(leaf, gradients):
    _, _, got, want = gradients
    assert np.any(np.asarray(want[leaf])), "a gradient of zeros shows nothing"
    assert close(got[leaf], want[leaf]), leaf


def test_no_leaf_is_left_out_and_the_frozen_ones_are_zero(gradients):
    _, _, got, want = gradients
    assert sorted(got) == sorted(LEAVES + FROZEN) == sorted(want)
    for leaf in FROZEN:
        assert not np.any(np.asarray(got[leaf])), leaf
        assert not np.any(np.asarray(want[leaf])), leaf


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    cfg, params, batch = case()
    rcfg = reference_cfg(cfg)
    loss = lambda b: float(jax.jit(
        lambda p: reference_loss(p, b, rcfg))(params))
    blocks = loss(batch)
    monkeypatch.setattr(ref, "ROW_CHUNK", T)
    monkeypatch.setattr(ref, "Q_CHUNK", T)
    assert abs(blocks - loss(batch)) < 1e-6 * blocks
    # total / count over rows = the mean of the rows' losses
    rows = [loss({"tokens": batch["tokens"][r:r + 1]}) for r in range(2)]
    assert abs(blocks - sum(rows) / 2) < 1e-6 * blocks


# -- a layer is a pair: operator kind x feed-forward kind --------------------------------

@pytest.mark.parametrize("kind", lfm2.OPERATORS)
@pytest.mark.parametrize("dense", [True, False])
def test_every_pair_of_operator_and_feed_forward(kind, dense):
    """The two kinds vary independently: a one-layer model of each of the
    four pairs against the reference."""
    cfg, params, batch = case(layer_types=(kind,),
                              layer_index=(1 if dense else 2,))
    assert cfg.layers == ((kind, dense),)
    rcfg = reference_cfg(cfg)
    lp = params["layer_0"]
    assert ("conv" in lp) == (kind == "conv")
    assert ("self_attn" in lp) == (kind == "full_attention")
    assert ("feed_forward" in lp) == dense
    assert ("experts" in lp) == (not dense)
    logits, loads = lfm2.apply(params, batch["tokens"], cfg)
    assert close(logits, ref.logits(params, batch, rcfg))
    assert loads.shape == (0 if dense else 1, 2)


def test_the_operators_are_the_references():
    cfg, params, _ = case()
    u = jax.random.normal(jax.random.key(3), (2, T, cfg.hidden_size))
    rcfg = reference_cfg(cfg)
    conv = lfm2.short_conv_operator(u, params["layer_0"]["conv"], cfg)
    attn = lfm2.gqa_attention(
        u, params["layer_1"]["self_attn"], cfg, jnp.arange(T), "causal",
        scope="attn.gqa", proj_scope="attn.gqa_proj")
    for r in range(2):
        assert close(conv[r], ref.short_conv(u[r], params["layer_0"]["conv"]))
        assert close(attn[r], ref.attention(
            u[r], params["layer_1"]["self_attn"], rcfg))


def test_it_writes_no_mechanism_of_its_own():
    assert lfm2.rms_norm is sdar_moe.rms_norm
    assert lfm2.gqa_attention is sdar_moe.gqa_attention
    assert lfm2.swiglu is xing.swiglu
    assert lfm2.dropless_moe is dropless.dropless_moe
    for name in ("rotary", "attention", "silu"):
        assert not hasattr(lfm2, name), name


# -- the shares of a deployment ---------------------------------------------------

def test_eight_expert_shares_are_the_layer():
    """Every chip computes its own experts' part: the eight parts add up
    to what the uncut layer gives, in program and reference (no shared
    expert: nothing is computed on every chip alike)."""
    cfg, params, _ = case(experts_held=(0, 8))
    lp = params["layer_1"]
    u = jax.random.normal(jax.random.key(5), (2, T, cfg.hidden_size))
    whole, loads = lfm2.expert_ffn(u, lp, cfg)
    parts, counts = [], []
    for first in range(8):
        share = dataclasses.replace(cfg, experts_held=(first, 1))
        mine = dict(lp, experts=jax.tree.map(lambda a: a[first:first + 1],
                                             lp["experts"]))
        y, n = lfm2.expert_ffn(u, mine, share)
        parts.append(y)
        counts.append(int(n[0]))
        want, _ = ref.expert_layer(u.reshape(-1, cfg.hidden_size), mine,
                                   reference_cfg(share))
        assert close(y.reshape(-1, cfg.hidden_size), want)
    assert close(sum(parts), whole)
    assert counts == loads.tolist()
    assert sum(counts) == 2 * T * cfg.num_experts_per_tok
    want, _ = ref.expert_layer(u.reshape(-1, cfg.hidden_size), lp,
                               reference_cfg(cfg))
    assert close(whole.reshape(-1, cfg.hidden_size), want)


def test_eight_vocabulary_slices_side_by_side_are_the_head():
    cfg, params, batch = case()
    x, _ = lfm2.hidden_states(params, batch["tokens"], cfg)
    whole = lfm2.logits_of(params, x, cfg)
    rows = cfg.vocab_size // 8
    slices = [lfm2.logits_of(dict(params, embed_tokens=params["embed_tokens"][
        i * rows:(i + 1) * rows]), x, cfg) for i in range(8)]
    assert close(jnp.concatenate(slices, -1), whole, 1e-6)


# -- the counts ---------------------------------------------------------------------

def config_file():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "lfm2-24b-a2b.json")) as f:
        return json.load(f)


def test_param_count_of_the_published_model():
    config = config_file()
    uncut = dict(config, num_hidden_layers=40, num_experts=64,
                 vocab_size=65536, first_expert=0,
                 published_layer_index=range(40))
    cfg = lfm2.Lfm2Config.from_source(uncut)
    assert lfm2.param_count(cfg) == 23_843_661_440
    assert sum(k == "full_attention" for k, _ in cfg.layers) == 10
    assert sum(k == "conv" for k, _ in cfg.layers) == 30
    assert sum(dense for _, dense in cfg.layers) == 2


def test_param_count_of_the_cut():
    cfg = lfm2.Lfm2Config.from_source(config_file())
    assert lfm2.param_count(cfg) == 647_819_904
    assert cfg.layers == (
        ("conv", True), ("full_attention", False), ("conv", False),
        ("conv", False), ("conv", False), ("full_attention", False),
        ("conv", False))
    assert (cfg.num_experts, cfg.experts_held) == (64, (0, 8))


@pytest.mark.parametrize("part, want", [
    ("conv", 16_783_360), ("self_attn", 10_485_888),
    ("feed_forward", 72_351_744), ("experts", 8 * 9_437_184),
    ("router", 2048 * 64), ("expert_bias", 64),
])
def test_param_count_of_a_part(part, want):
    cfg = lfm2.Lfm2Config.from_source(config_file())
    shapes = jax.eval_shape(lambda k: lfm2.init(k, cfg), jax.random.key(0))
    layer = shapes["layer_0" if part in ("conv", "feed_forward")
                   else "layer_1"]
    assert sum(a.size for a in jax.tree.leaves(layer[part])) == want


def test_from_source_reads_the_published_list_by_index():
    config = config_file()
    assert len(config["layer_types"]) == 40
    cfg = lfm2.Lfm2Config.from_source(dict(
        config, num_hidden_layers=3, published_layer_index=[1, 2, 39]))
    assert cfg.layers == (("conv", True), ("full_attention", False),
                          ("conv", False))
    assert (cfg.head_dim, cfg.rope_theta, cfg.norm_eps,
            cfg.conv_L_cache) == (64, 1e6, 1e-5, 3)
    assert cfg.dtype == jnp.bfloat16 and cfg.remat
    with pytest.raises(ValueError, match="published_layer_index"):
        lfm2.Lfm2Config.from_source(dict(config, num_hidden_layers=6))


@pytest.mark.parametrize("change, match", [
    (dict(conv_bias=True), "conv_bias"),
    (dict(layer_types=["conv", "conv", "mamba"] + ["conv"] * 37), "only"),
])
def test_what_is_not_computed_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        lfm2.Lfm2Config.from_source(dict(config_file(), **change))


# -- the router's bias ------------------------------------------------------------------

def test_the_bias_steers_the_choice_and_not_the_gates():
    cfg, params, _ = case()
    lp = params["layer_1"]
    u = jax.random.normal(jax.random.key(7), (2, T, cfg.hidden_size))
    # a bias that lifts held expert 1 above every score: every position
    # chooses it; one that sinks it: none does
    up = dict(lp, expert_bias=jnp.zeros(8).at[1].set(10.0))
    down = dict(lp, expert_bias=jnp.zeros(8).at[1].set(-10.0))
    y_up, n_up = lfm2.expert_ffn(u, up, cfg)
    _, n_down = lfm2.expert_ffn(u, down, cfg)
    assert int(n_up[1]) == 2 * T and int(n_down[1]) == 0
    want, _ = ref.expert_layer(u.reshape(-1, cfg.hidden_size), up,
                               reference_cfg(cfg))
    assert close(y_up.reshape(-1, cfg.hidden_size), want)
    # the gates are the UNBIASED scores over the chosen, summing to the
    # scaling factor
    x = u.reshape(-1, cfg.hidden_size)
    gates, chosen = dropless.route(
        x, lp["router"], cfg.num_experts_per_tok, scoring="sigmoid",
        bias=up["expert_bias"], scaling=cfg.routed_scaling_factor)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, lp["router"], precision=jax.lax.Precision.HIGHEST)))
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    assert close(gates, picked / picked.sum(-1, keepdims=True), 1e-6)


def test_the_bias_takes_no_gradient_and_no_step_moves_it():
    from pytorch_ps_mpi_tpu import MPI_PS
    from pytorch_ps_mpi_tpu.mesh import make_mesh

    cfg, params, batch = case()
    before = jax.device_get(params)
    opt = MPI_PS(params, optim="adam", lr=1e-2, mode="allgather",
                 mesh=make_mesh(devices=jax.devices()[:1]), average=True)
    for _ in range(2):
        opt.step(loss_fn=lambda p, b: lfm2.causal_lm_loss(p, b, cfg),
                 batch=batch)
    after = jax.device_get(opt.params)
    for where in ("layer_1", "layer_2"):
        assert np.array_equal(after[where]["expert_bias"],
                              before[where]["expert_bias"])
        assert not np.array_equal(after[where]["router"],
                                  before[where]["router"])
    assert not np.array_equal(after["layer_0"]["conv"]["conv"],
                              before["layer_0"]["conv"]["conv"])


def test_without_use_expert_bias_there_is_no_such_leaf():
    cfg, params, batch = case(use_expert_bias=False)
    assert "expert_bias" not in params["layer_1"]
    logits, _ = lfm2.apply(params, batch["tokens"], cfg)
    assert close(logits, ref.logits(params, batch, reference_cfg(cfg)))


# -- what the set-up log holds ------------------------------------------------------------

def test_conv_plan_flash_tiles_and_row_moves_on_the_recorder():
    from pytorch_ps_mpi_tpu import telemetry

    cfg, params, batch = case(attention="flash")
    rec = telemetry.configure()
    try:
        lfm2.causal_lm_loss(params, batch, cfg)
        events = rec.events()
    finally:
        telemetry.disable()
    plans = [e["attrs"] for e in events if e["name"] == "conv.plan"]
    assert len(plans) == 2          # the two conv layers
    assert all(p == dict(rows=2, T=T, channels=32, taps=3,
                         bytes_read=2 * T * 96 * 4, bytes_written=2 * T * 32 * 4,
                         mover="jnp") for p in plans)
    assert len([e for e in events if e["name"] == "attn.flash_tiles"]) == 1
    assert len([e for e in events if e["name"] == "moe.row_moves"]) == 2


def test_the_scopes_a_device_trace_reads():
    cfg, params, batch = case()
    text = jax.jit(jax.grad(
        lambda p: lfm2.causal_lm_loss(p, batch, cfg))).lower(
        params).as_text(debug_info=True)
    # a scope is a component of an operation's name path, bare or inside a
    # transformation's brackets (jobs/sync_train_streamed.py reads it so)
    found = set(re.findall(r'(?<=[/("])[a-z_]+(?:\.[a-z_]+)+(?=[/")])', text))
    assert {"conv.proj", "conv.mix", "attn.gqa_proj", "attn.gqa",
            "mlp.swiglu", "moe.route", "moe.dispatch", "moe.experts",
            "moe.combine", "loss.head"} <= found, found
