"""What a layer's checkpoint keeps (``ops/_common.py``: ``KEPT``, ``keep``,
``checkpoint_layer``): under ``remat`` the four models' backward passes
run every flash forward kernel and every expert layer's sorts ONCE, the
saved values are the first forward's own arrays (so loss and gradients
are bit-equal to a plain ``jax.checkpoint``'s, and equal to rounding to
those of no checkpoint at all, which XLA compiles as another program),
outside a checkpoint a name lowers to nothing, and the set-up log says
what was named.

The tiny presets of ``tests/test_{sdar_moe,sambay,xing,lfm2}.py`` (their
``case()`` helpers, float32) with attention on the flash path; the
kernels run interpreted on the CPU."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.gen.bd_zipf import batches
from pytorch_ps_mpi_tpu import telemetry
from pytorch_ps_mpi_tpu.models import lfm2, sambay, sdar_moe, xing
from pytorch_ps_mpi_tpu.ops import _common
from pytorch_ps_mpi_tpu.ops import attention_pallas as ap
from pytorch_ps_mpi_tpu.parallel import dropless

T = 24


def _off_the_seed(params):
    # off the seed's zeros and ones: every bias and gain takes part
    return jax.tree.map(lambda a: a + 0.05 * jax.random.normal(
        jax.random.key(a.size), a.shape), params)


def _tokens(cfg):
    return {"tokens": jax.random.randint(jax.random.key(1), (2, T), 0,
                                         cfg.vocab_size)}


def sdar_case(remat):
    cfg = sdar_moe.SdarMoeConfig.tiny(attention="flash", remat=remat)
    params = sdar_moe.init(jax.random.key(3), cfg, scale=0.1)
    batch = next(batches(3, 2, 32, cfg.vocab_size))
    return params, lambda p: sdar_moe.block_diffusion_loss(p, batch, cfg)


def sambay_case(remat):
    cfg = sambay.SambaYConfig.tiny(attention="flash", remat=remat)
    params = _off_the_seed(sambay.init(jax.random.key(0), cfg))
    batch = _tokens(cfg)
    return params, lambda p: sambay.causal_lm_loss(p, batch, cfg)


def xing_case(remat):
    cfg = xing.XingConfig.tiny(attention="flash", remat=remat,
                               hc_init_gate=0.3, hc_init_bias=1.0)
    params = _off_the_seed(xing.init(jax.random.key(0), cfg))
    batch = _tokens(cfg)
    return params, lambda p: xing.causal_lm_loss(p, batch, cfg)


def lfm2_case(remat):
    cfg = lfm2.Lfm2Config.tiny(attention="flash", remat=remat)
    params = _off_the_seed(lfm2.init(jax.random.key(0), cfg, scale=0.3))
    batch = _tokens(cfg)
    return params, lambda p: lfm2.causal_lm_loss(p, batch, cfg)


# family -> (its case, its module, attention layers, expert layers)
CASES = {
    "sdar": (sdar_case, sdar_moe, 2, 2),
    "sambay": (sambay_case, sambay, 3, 0),
    "xing": (xing_case, xing, 4, 3),
    "lfm2": (lfm2_case, lfm2, 1, 2),
}


def primitives(jaxpr, found=None):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    primitives(sub, found)
    return found


def is_flash_forward(eqn) -> bool:
    """The forward kernel alone returns (out ``[bh, lq, dv]``, the
    lane-replicated logsumexp ``float32[bh, lq, 128]``)."""
    if eqn.primitive.name != "pallas_call" or len(eqn.outvars) != 2:
        return False
    out, lse = (v.aval for v in eqn.outvars)
    return (lse.shape == (*out.shape[:2], _common.LANE)
            and lse.dtype == jnp.float32)


def is_flash_backward(eqn) -> bool:
    """A flash kernel that is not the forward one. The ONE backward kernel
    returns (dq, dk, dv): two outputs of q's width and one of v's, none of
    them a float32 ride (the pair it replaces was a call with one output
    and a call with two). These presets run no other Pallas kernel."""
    return eqn.primitive.name == "pallas_call" and not is_flash_forward(eqn)


def census(loss, params):
    eqns = primitives(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    count = collections.Counter(e.primitive.name for e in eqns)
    backward = [e for e in eqns if is_flash_backward(e)]
    assert all(len(e.outvars) == 3 for e in backward), backward
    return dict(flash_forward=sum(map(is_flash_forward, eqns)),
                flash_backward=len(backward),
                sort=count["sort"], top_k=count["top_k"])


@pytest.mark.parametrize("family", sorted(CASES))
def test_the_backward_pass_runs_the_kept_work_once(family, monkeypatch):
    case, module, attn_layers, expert_layers = CASES[family]
    sigmoid = family in ("xing", "lfm2")

    def run(remat):
        params, loss = case(remat)
        return census(loss, params), jax.jit(jax.value_and_grad(loss))(params)

    off, (loss_off, grads_off) = run(False)
    kept, (loss_kept, grads_kept) = run(True)
    # the helper's policy swapped for none: ``jax.checkpoint`` itself
    monkeypatch.setattr(module, "checkpoint_layer", jax.checkpoint)
    plain, (loss_plain, grads_plain) = run(True)

    # one forward kernel an attention layer, one pair of sorts an expert
    # layer; under the sigmoid router the second top_k goes with the plan,
    # under softmax its values are the gate weights and it stays. ONE
    # backward kernel an attention layer (dq, dk and dv from one
    # recomputation of the scores; two before PR 40), whatever the
    # checkpoint
    assert off == dict(flash_forward=attn_layers, flash_backward=attn_layers,
                       sort=2 * expert_layers, top_k=expert_layers)
    assert kept == dict(off, top_k=expert_layers * (1 if sigmoid else 2))
    assert plain == dict(flash_forward=2 * attn_layers,
                         flash_backward=attn_layers,
                         sort=4 * expert_layers, top_k=2 * expert_layers)

    # the saved values are the first forward's own arrays: the two
    # checkpoints agree to the bit. A program WITHOUT a checkpoint is
    # another program to XLA, fused otherwise (the CPU's contractions move
    # the last bits, under the plain checkpoint as well): held to rounding
    assert np.isfinite(loss_kept) and loss_kept == loss_plain
    assert abs(loss_kept - loss_off) <= 1e-6 * abs(loss_off)
    # (of the largest gradient: xing's a_pre has gradients of 1e-11 that
    # ARE rounding)
    largest = max(float(jnp.max(jnp.abs(c))) for c in jax.tree.leaves(grads_off))
    for (path, a), b, c in zip(jax.tree.leaves_with_path(grads_kept),
                               jax.tree.leaves(grads_plain),
                               jax.tree.leaves(grads_off)):
        assert np.array_equal(a, b), jax.tree_util.keystr(path)
        assert np.max(np.abs(a - c)) <= 1e-6 * largest, \
            jax.tree_util.keystr(path)


def _stripped(text):
    """Lowered text without what names a source line, and without the
    counter behind a private function's name (``@_where_68``: it counts
    the process's equations, named ones too)."""
    text = re.sub(r"loc\([^\n]*\)", "", text)
    text = re.sub(r"@([A-Za-z_]+)_\d+", r"@\1", text)
    return "\n".join(line.rstrip() for line in text.splitlines()
                     if not line.lstrip().startswith("#loc"))


@pytest.mark.parametrize("spec", [dict(causal=True),
                                  dict(mask="window", window=8)])
def test_outside_a_checkpoint_a_name_lowers_to_nothing(spec, monkeypatch):
    q, k, v = (jax.random.normal(jax.random.key(i), (2, 32, 4, 16))
               for i in range(3))

    def text():
        return jax.jit(jax.grad(
            lambda q, k, v: ap.flash_attention(q, k, v, **spec).sum(),
            argnums=(0, 1, 2))).lower(q, k, v).as_text()

    named = text()
    monkeypatch.setattr(ap, "keep", lambda x, name: x)
    assert _stripped(named) == _stripped(text())


def test_the_set_up_log_says_what_a_traced_step_keeps():
    params, loss = lfm2_case(True)
    jax.make_jaxpr(jax.grad(loss))(params)
    rows = [e["attrs"] for e in telemetry.setup_rows()
            if e["name"] == "remat.keep"]
    by_name = collections.defaultdict(list)
    for row in rows:
        by_name[row["kept"]].append(row)
        assert row["bytes"] == (np.prod(row["shape"], dtype=int)
                                * jnp.dtype(row["dtype"]).itemsize)
    assert set(by_name) == set(_common.KEPT)
    # tiny: 2 rows of 24 positions, 4 query heads of 8, float32; one
    # attention layer, so one output and one logsumexp a row and head:
    # the lane-replicated [8, 24, 128] ride is not kept
    assert by_name["flash.out"] == [dict(
        kept="flash.out", shape=[8, T, 8], dtype="float32",
        bytes=8 * T * 8 * 4)]
    assert by_name["flash.lse"] == [dict(
        kept="flash.lse", shape=[8, T], dtype="float32", bytes=8 * T * 4)]
    assert not any(row["shape"][-1:] == [_common.LANE] for row in rows)
    # two expert layers: the router's choice and the plan's seven leaves
    plans = by_name["moe.plan"]
    assert len(plans) == 2 * (1 + len(dropless.Plan._fields))
    assert all(row["dtype"] in ("int32", "bool") for row in plans)
    assert sum(row["bytes"] for row in plans) < 2 * 4096
