"""``models/sambay.py`` at a tiny preset (hidden 32, 4 query heads over 2
key-value heads of 8, d_inner 64, state 4, window 8, 24 positions)
against the plain reference ``chipbench/reference/sambay.py`` on seeded
weights: the six-kind stack, each mixer alone, the two hand-overs with
several readers, and the vocabulary's share.

Tolerances: float32 on both sides, 2e-4 of the largest entry (the
reference runs its products at "highest"; the scan, the attention and
the blocks sum in other orders)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import sambay as ref
from pytorch_ps_mpi_tpu.models import sambay

T = 24


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


def reference_cfg(cfg):
    return dict(
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        hidden_size=cfg.hidden_size, layer_norm_eps=cfg.layer_norm_eps,
        sliding_window=cfg.sliding_window, layer_types=list(cfg.layer_types),
        published_layer_index=list(cfg.layer_index),
        mamba_d_state=cfg.mamba_d_state, mamba_dt_rank=cfg.mamba_dt_rank,
        mamba_d_conv=cfg.mamba_d_conv)


def case(seed=0, **kw):
    cfg = sambay.SambaYConfig.tiny(**kw)
    params = sambay.init(jax.random.key(seed), cfg)
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
    monkeypatch.setattr(ref, "SCAN_CHUNK", 4)
    monkeypatch.setattr(ref, "Q_CHUNK", 8)


@pytest.mark.parametrize("attention", ["einsum", "flash"])
@pytest.mark.parametrize("remat", [False, True])
def test_the_stack_of_six_kinds(attention, remat):
    cfg, params, batch = case(attention=attention, remat=remat)
    rcfg = reference_cfg(cfg)
    assert close(sambay.apply(params, batch["tokens"], cfg),
                 ref.logits(params, batch, rcfg))
    loss, grads = jax.value_and_grad(sambay.causal_lm_loss)(params, batch, cfg)
    want, want_grads = jax.value_and_grad(reference_loss)(params, batch, rcfg)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert close(g, w), jax.tree_util.keystr(path)


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    cfg, params, batch = case()
    rcfg = reference_cfg(cfg)
    blocked = reference_loss(params, batch, rcfg)
    for name in ("ROW_CHUNK", "SCAN_CHUNK", "Q_CHUNK"):
        monkeypatch.setattr(ref, name, 10 ** 6)
    assert abs(float(blocked) - float(reference_loss(params, batch, rcfg))
               ) < 1e-6 * float(blocked)


def test_mamba_mixer_alone():
    cfg, params, _ = case()
    u = jax.random.normal(jax.random.key(5), (2, T, cfg.hidden_size))
    p = params["layer_0"]["mixer"]
    out, y = sambay.mamba_mixer(u, p, cfg)
    for r in range(2):
        want_out, want_y = ref.mamba(u[r], p, reference_cfg(cfg))
        assert close(out[r], want_out) and close(y[r], want_y)


def test_gmu_mixer_alone():
    cfg, params, _ = case()
    u = jax.random.normal(jax.random.key(5), (2, T, cfg.hidden_size))
    m = jax.random.normal(jax.random.key(6), (2, T, cfg.d_inner))
    p = params["layer_4"]["mixer"]
    want = (m * jax.nn.silu(u @ p["in_proj"])) @ p["out_proj"]
    assert close(sambay.gmu_mixer(u, p, m, cfg), want)


@pytest.mark.parametrize("attention", ["einsum", "flash"])
@pytest.mark.parametrize("layer, kind", [(1, "sliding_attention"),
                                         (3, "full_attention"),
                                         (5, "cross_attention")])
def test_differential_attention_alone(layer, kind, attention):
    cfg, params, _ = case(attention=attention)
    rcfg = reference_cfg(cfg)
    u = jax.random.normal(jax.random.key(5), (2, T, cfg.hidden_size))
    p = params[f"layer_{layer}"]["mixer"]
    source = p if "kv_proj" in p else params["layer_3"]["mixer"]
    index = cfg.layer_index[layer]
    out = sambay.diff_attention(u, p, sambay.keys_values(u, source, cfg), cfg,
                                kind, index)
    window = cfg.sliding_window if kind == "sliding_attention" else None
    for r in range(2):
        want = ref.diff_attention(u[r], p, ref.keys_values(u[r], source, rcfg),
                                  rcfg, window, index)
        assert close(out[r], want)


def test_lambda_follows_the_published_index():
    assert sambay.lambda_init(0) == pytest.approx(0.2)
    assert sambay.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    cfg, params, batch = case()
    moved = dataclasses.replace(cfg, layer_index=(0, 1, 16, 17, 18, 19))
    assert not close(sambay.apply(params, batch["tokens"], cfg),
                     sambay.apply(params, batch["tokens"], moved))


@pytest.mark.parametrize("remat", [False, True])
def test_the_hand_overs_sum_their_readers_gradients(remat):
    """Two gated memory units read one memory, two cross layers one set
    of keys and values: the gradients of what made them equal the
    reference's (plain autodiff), and differ from one reader's."""
    kinds = ("mamba_memory", "full_attention", "gmu", "cross_attention",
             "gmu", "cross_attention")
    cfg, params, batch = case(layer_types=kinds, remat=remat)
    grads = jax.grad(sambay.causal_lm_loss)(params, batch, cfg)
    want = jax.grad(reference_loss)(params, batch, reference_cfg(cfg))
    for layer, leaf in ((0, "x_proj"), (0, "A_log"), (1, "kv_proj"),
                        (1, "kv_bias")):
        assert close(grads[f"layer_{layer}"]["mixer"][leaf],
                     want[f"layer_{layer}"]["mixer"][leaf]), (layer, leaf)
    one = sambay.SambaYConfig.tiny(layer_types=kinds[:4],
                                   layer_index=(0, 1, 2, 3), remat=remat)
    fewer = jax.grad(sambay.causal_lm_loss)(
        {k: v for k, v in params.items() if k not in ("layer_4", "layer_5")},
        batch, one)
    assert not close(fewer["layer_1"]["mixer"]["kv_proj"],
                     grads["layer_1"]["mixer"]["kv_proj"], tol=1e-2)


def test_the_vocabulary_slices_side_by_side_are_the_uncut_head():
    """Eight chips hold an eighth of the embedding's rows each: their
    logits side by side are the uncut head's, and the first chip's model
    on ids of its own slice is the uncut model's first eighth."""
    cfg, params, _ = case()
    share = cfg.vocab_size // 8
    tokens = jax.random.randint(jax.random.key(9), (2, T), 0, share)
    x = sambay.hidden_states(params, tokens, cfg)
    whole = sambay.logits_of(params, x, cfg)
    slices = [sambay.logits_of(dict(params, embed_tokens=params[
        "embed_tokens"][s * share:(s + 1) * share]), x, cfg) for s in range(8)]
    assert close(jnp.concatenate(slices, axis=-1), whole, tol=1e-6)
    first = dict(params, embed_tokens=params["embed_tokens"][:share])
    held = dataclasses.replace(cfg, vocab_size=share)
    assert close(sambay.apply(first, tokens, held), whole[..., :share],
                 tol=1e-6)


def test_parameter_counts():
    cfg, params, _ = case()
    assert sambay.param_count(cfg) == sum(
        a.size for a in jax.tree.leaves(params))
    published = sambay.SambaYConfig.from_source(dict(
        vocab_size=200064, hidden_size=2560, intermediate_size=10240,
        num_attention_heads=40, num_key_value_heads=20, sliding_window=512,
        num_hidden_layers=32))
    assert sambay.param_count(published) == 3_852_562_944   # "3.8B"
    kinds = published.layer_types
    assert [kinds.count(k) for k in sambay.LAYER_KINDS] == [8, 1, 7, 8, 1, 7]
    assert kinds[16] == "mamba_memory" and kinds[17] == "full_attention"
    assert kinds[:2] == ("mamba", "sliding_attention")
    assert kinds[18:20] == ("gmu", "cross_attention")
    assert published.d_inner == 5120 and published.head_dim == 64


def test_the_layout_is_checked():
    with pytest.raises(ValueError, match="needs a mamba_memory"):
        sambay.SambaYConfig.tiny(layer_types=("mamba", "gmu"),
                                 layer_index=(0, 1))
    with pytest.raises(ValueError, match="needs a full_attention"):
        sambay.SambaYConfig.tiny(
            layer_types=("cross_attention", "full_attention"),
            layer_index=(0, 1))
    with pytest.raises(ValueError, match="layer_types"):
        sambay.SambaYConfig.tiny(layer_types=("mamba", "conv"),
                                 layer_index=(0, 1))
    with pytest.raises(ValueError, match="layer_types for"):
        sambay.SambaYConfig.from_source(dict(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            num_attention_heads=4, num_key_value_heads=2, sliding_window=8,
            num_hidden_layers=3, layer_types=["mamba"]))
