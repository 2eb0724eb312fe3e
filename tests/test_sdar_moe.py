"""``models/sdar_moe.py`` and ``parallel/dropless.py`` at a tiny preset
(hidden 64, 4 query heads over 2 key-value heads of 16, 8 experts top-2,
2 held, block 4, L 32) against the plain reference
``chipbench/reference/sdar_moe.py`` on seeded weights.

Tolerances: float32 on both sides, so 2e-4 of the largest entry (the
reference runs its products at "highest", the system at the CPU's
default float32; the sums are taken in other orders: sorted rows and
ragged products against every expert at every position).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.gen.bd_zipf import batches
from chipbench.reference import sdar_moe as ref
from pytorch_ps_mpi_tpu.models import sdar_moe
from pytorch_ps_mpi_tpu.parallel import dropless

RCFG = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            rms_norm_eps=1e-6, rope_theta=1e6, block_length=4,
            num_experts_per_tok=2, num_experts=2, first_expert=0,
            num_hidden_layers=2, norm_topk_prob=True)


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-12)


def case(**kw):
    cfg = sdar_moe.SdarMoeConfig.tiny(**kw)
    # scale 0.1: at 0.02 a tiny model's router is flat and every gradient
    # but the head's is below float32's reach of the loss
    params = sdar_moe.init(jax.random.key(3), cfg, scale=0.1)
    batch = next(batches(3, 2, 32, cfg.vocab_size))
    return cfg, params, batch


@pytest.mark.parametrize("attention, remat", [
    ("einsum", False), ("flash", False), ("flash", True)])
def test_system_matches_reference(attention, remat):
    cfg, params, batch = case(attention=attention, remat=remat)
    logits, loads = jax.jit(
        lambda p, b: sdar_moe.block_diffusion_logits(p, b, cfg))(params, batch)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, b: ref.logits(p, b, RCFG))(params, batch)
    assert close(logits, want, 1e-5)
    assert np.array_equal(loads, ref.router_loads(params, batch, RCFG))
    assert np.array_equal(
        loads, jax.jit(lambda p, b: sdar_moe.router_loads(p, b, cfg))(
            params, batch))

    def ref_loss(p, b):
        total, count = ref.terms(p, b, RCFG)
        return total / count

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: sdar_moe.block_diffusion_loss(p, b, cfg)))(params, batch)
    want_loss, want_grads = jax.jit(jax.value_and_grad(ref_loss))(params, batch)
    assert close(loss, want_loss, 1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == 3 + 2 * 12          # every leaf has a gradient
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        assert np.abs(want).max() > 0, path
        assert close(got, want), jax.tree_util.keystr(path)


def test_shares_add_up_to_the_uncut_layer():
    """The parts all four shares give (experts 0-1, 2-3, 4-5, 6-7; router
    whole on each) add up to the uncut reference's whole layer."""
    k = jax.random.split(jax.random.key(1), 5)
    b = jax.random.normal(k[0], (64, 64))
    whole = {"router": 0.5 * jax.random.normal(k[1], (64, 8)),
             "experts": {"gate_proj": 0.1 * jax.random.normal(k[2], (8, 64, 32)),
                         "up_proj": 0.1 * jax.random.normal(k[3], (8, 64, 32)),
                         "down_proj": 0.1 * jax.random.normal(k[4], (8, 32, 64))}}
    with jax.default_matmul_precision("highest"):
        want, want_loads = ref.moe_layer(b, whole, dict(RCFG, num_experts=8))
    total, loads = 0.0, []
    for first in range(0, 8, 2):
        mine = {n: w[first:first + 2] for n, w in whole["experts"].items()}
        part, n = dropless.dropless_moe(
            b, whole["router"], mine["gate_proj"], mine["up_proj"],
            mine["down_proj"], top_k=2, experts_held=(first, 2),
            capacity_factor=4.0)
        with jax.default_matmul_precision("highest"):
            ref_part, _ = ref.moe_layer(b, dict(whole, experts=mine), dict(
                RCFG, num_experts=2, first_expert=first))
        assert close(part, ref_part, 1e-5)
        total, loads = total + part, loads + list(np.asarray(n))
    assert close(total, want, 1e-5)
    assert loads == list(np.asarray(want_loads)) and sum(loads) == 64 * 2


def forced_router(expert: int):
    """A router that sends every position to ``expert`` first."""
    return jnp.zeros((64, 8)).at[:, expert].set(1.0)


@pytest.mark.parametrize("capacity_factor, finite", [
    (4.0, True),    # 8 * min(2, 2) / (2 * 2): the worst case, cannot overflow
    (2.0, False),   # 32 rows for 40 pairs: the output is NaN, nothing dropped
])
def test_no_pair_is_dropped_silently(capacity_factor, finite):
    k = jax.random.split(jax.random.key(2), 4)
    b = jnp.abs(jax.random.normal(k[0], (40, 64)))   # positive: logit > 0
    w = [0.1 * jax.random.normal(k[i], s) for i, s in
         ((1, (2, 64, 32)), (2, (2, 64, 32)), (3, (2, 32, 64)))]
    y, loads = dropless.dropless_moe(
        b, forced_router(1), *w, top_k=2, experts_held=(0, 2),
        capacity_factor=capacity_factor)
    assert int(loads[1]) == 40              # every position chose expert 1
    if not finite:
        assert bool(jnp.all(jnp.isnan(y)))
        # and the gradient pass runs (the groups are cut at the buffer's
        # end: a grouped product never reads past it)
        g = jax.grad(lambda b: jnp.sum(dropless.dropless_moe(
            b, forced_router(1), *w, top_k=2, experts_held=(0, 2),
            capacity_factor=capacity_factor)[0]))(b)
        assert g.shape == b.shape
        return
    with jax.default_matmul_precision("highest"):
        want, want_loads = ref.moe_layer(
            b, {"router": forced_router(1),
                "experts": dict(zip(("gate_proj", "up_proj", "down_proj"), w))},
            dict(RCFG, num_experts=2))
    assert close(y, want, 1e-5) and np.array_equal(loads, want_loads)


def test_an_overflow_makes_the_loss_non_finite():
    cfg, params, batch = case(capacity_factor=0.25)
    loss = sdar_moe.block_diffusion_loss(params, batch, cfg)
    assert not np.isfinite(float(loss))


def test_capacity_rows():
    # the cell: 16,384 positions x 8 x 16 / 128 expected, twice that held
    assert dropless.capacity_rows(16384, 8, 128, 16, 2.0) == 32768
    assert dropless.capacity_rows(16384, 8, 128, 16, 100.0) == 16384 * 8
    assert dropless.capacity_rows(64, 2, 8, 2, 4.0) == 128


def test_config_from_the_source_keys():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench/configs/sdar-30b-a3b.json")) as f:
        cfg = sdar_moe.SdarMoeConfig.from_source(json.load(f))
    assert (cfg.num_experts, cfg.experts_held) == (128, (0, 16))
    assert (cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size) == (
        2048, 128, 768)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads) == (32, 4)
    assert jnp.dtype(cfg.dtype) == jnp.bfloat16 and cfg.remat
    assert cfg.capacity_factor == 4.0 and cfg.block_length == 4
    shapes = jax.eval_shape(lambda: sdar_moe.init(jax.random.key(0), cfg))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 645_623_296


def test_trains_through_mpi_ps_and_trainer():
    """The normal path: ``MPI_PS`` + ``Trainer.fit``, loss falling."""
    from pytorch_ps_mpi_tpu import MPI_PS
    from pytorch_ps_mpi_tpu.mesh import make_mesh
    from pytorch_ps_mpi_tpu.trainer import Trainer

    cfg, params, batch = case()
    mesh = make_mesh(devices=jax.devices()[:1])
    opt = MPI_PS(params, optim="adam", lr=1e-2, mesh=mesh, mode="allgather",
                 average=True)
    trainer = Trainer(opt, lambda p, b: sdar_moe.block_diffusion_loss(p, b, cfg))
    first = trainer.fit(iter([batch] * 1), 1)["final_loss"]
    last = trainer.fit(iter([batch] * 8), 8)["final_loss"]
    assert np.isfinite(last) and last < first


def _gqa_attention_of_pr_36(x, lp, cfg, positions, mask, block=None,
                            half=None):
    """``sdar_moe.gqa_attention`` as it stood before it gained ``scope``
    and ``proj_scope`` for its second caller (``models/lfm2.py``, PR 37),
    line for line."""
    from pytorch_ps_mpi_tpu.ops import attention_pallas as ap
    from pytorch_ps_mpi_tpu.models.sdar_moe import rms_norm, rotary

    c = cfg
    b, s, _ = x.shape
    dt = c.dtype
    q = (x @ lp["q_proj"].astype(dt)).reshape(b, s, c.num_attention_heads,
                                              c.head_dim)
    k = (x @ lp["k_proj"].astype(dt)).reshape(b, s, c.num_key_value_heads,
                                              c.head_dim)
    v = (x @ lp["v_proj"].astype(dt)).reshape(b, s, c.num_key_value_heads,
                                              c.head_dim)
    q = rotary(rms_norm(q, lp["q_norm"], c.rms_norm_eps), positions,
               c.rope_theta)
    k = rotary(rms_norm(k, lp["k_norm"], c.rms_norm_eps), positions,
               c.rope_theta)
    kernel = c.attention == "flash" or (
        c.attention == "full" and ap.flash_auto_ok(s, s, dt))
    with jax.named_scope("attn.bd" if mask == "block_diffusion" else "attn"):
        if kernel:
            out = ap.flash_attention(q, k, v, mask=mask, block=block,
                                     half=half)
        else:
            out, _ = ap._attention_jnp(
                q, k, v, 0, 0, ap._mask_spec(False, mask, block, half),
                c.head_dim ** -0.5)
    return out.reshape(b, s, -1) @ lp["o_proj"].astype(dt)


@pytest.mark.parametrize("attention", ["einsum", "flash"])
def test_the_attention_is_bit_equal_to_what_it_was(attention):
    """``bd4k``'s shape of call (the block-diffusion mask over a doubled
    row, grouped heads, bf16 compute, no scope argument): the same output
    and the same gradients, bit for bit, and the same program."""
    cfg, params, _ = case(attention=attention, dtype=jnp.bfloat16)
    lp = params["layer_0"]
    half = 32
    x = jax.random.normal(jax.random.key(8), (2, 2 * half, cfg.hidden_size),
                          jnp.bfloat16)
    ids = jnp.concatenate([jnp.arange(half)] * 2)

    def run(fn):
        def total(x, lp):
            out = fn(x, lp, cfg, ids, "block_diffusion", cfg.block_length,
                     half)
            return jnp.sum(out.astype(jnp.float32) ** 2), out

        return jax.jit(jax.value_and_grad(total, (0, 1), has_aux=True))

    now, then = run(sdar_moe.gqa_attention), run(_gqa_attention_of_pr_36)
    ((_, out), grads), ((_, was), grads_then) = now(x, lp), then(x, lp)
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(was, np.float32))
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_then)):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    # the program itself: the same operations under the same names
    text = lambda f: f.lower(x, lp).as_text()
    assert text(now) == text(then)
