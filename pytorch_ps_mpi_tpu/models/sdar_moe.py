"""Config-driven decoder of the ``sdar_moe`` family (SDAR-30B-A3B-Chat:
a Qwen3-MoE-style block trained by diffusion over blocks), read straight
from the source ``config.json``'s key names. One function a mechanism:
``rms_norm``, ``rotary``, ``gqa_attention`` (with a ``mask`` kind),
the router and the SwiGLU experts (``parallel/dropless.py``).

All layers are alike. With P positions, x in R^{P x hidden}:

- a = RMSNorm(x); q = a W_q [P, heads, head_dim], k = a W_k, v = a W_v
  [P, kv_heads, head_dim], no biases; q and k take a per-head RMSNorm
  over the head dimension, then the rotary embedding at each position's
  id; query head h reads key-value head h // (heads // kv_heads);
  softmax(q k^T / sqrt(head_dim) + M) v, then W_o; x <- x + that.
- b = RMSNorm(x); r = softmax(b W_r) in float32 over ALL experts; the
  ``num_experts_per_tok`` largest, renormalised; y = the weighted sum of
  the chosen experts HELD here, each down(silu(gate(b)) * up(b));
  x <- x + y.
- after the last layer RMSNorm and an untied head.

Parameters are a plain pytree under the source's names (float32); the
compute dtype is ``cfg.dtype``; norms, the router, the softmaxes and the
logits are float32. Every named scope (``attn.bd``, ``moe.route``,
``moe.dispatch``, ``moe.experts``, ``moe.combine``, ``loss.head``) is in
the step program's instruction metadata for a device trace to read.
With ``remat`` a layer is a checkpoint that keeps what is dear to
recompute and small to hold (``ops/_common.checkpoint_layer``: the flash
kernels' output and per-row logsumexp, the expert layer's plan) and
recomputes the rest: the backward pass runs no flash forward kernel and
no sort a second time.

**Training by diffusion over blocks** (``block_diffusion_loss``): a row
of L tokens runs as 2L positions — the noised copy at 0..L-1, the clean
copy at L..2L-1, token i at position id i in both — under the
block-diffusion mask of ``ops/attention_pallas.py``; logits are taken at
the noised half only, and the loss is the mean over rows x L of
(1 / t_block) * (-log p(token)) at the replaced positions.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.models.bert import target_log_likelihood
from pytorch_ps_mpi_tpu.ops._common import checkpoint_layer
from pytorch_ps_mpi_tpu.parallel.dropless import dropless_moe


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int                   # the router's width (published)
    num_experts_per_tok: int
    experts_held: Tuple[int, int]      # (first, count) of the experts here
    norm_topk_prob: bool = True
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    block_length: int = 4
    capacity_factor: float = 2.0       # parallel/dropless.py
    dtype: Any = jnp.float32
    attention: str = "full"            # 'full' | 'flash' | 'einsum' (bert.py)
    remat: bool = False                # checkpoint_layer around each layer

    @staticmethod
    def from_source(config: dict) -> "SdarMoeConfig":
        """From a configuration file under the source's key names. Where
        a chip holds a share, ``num_experts`` counts the experts held
        (first ``first_expert``) and ``published_num_experts`` is the
        router's width."""
        held = int(config["num_experts"])
        fields = {f.name for f in dataclasses.fields(SdarMoeConfig)}
        kw = {k: v for k, v in config.items() if k in fields}
        kw.update(
            num_experts=int(config.get("published_num_experts", held)),
            experts_held=(int(config.get("first_expert", 0)), held),
            capacity_factor=float(config.get("moe_capacity_factor", 2.0)),
            dtype=jnp.dtype(config.get("dtype", "float32")).type)
        return SdarMoeConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "SdarMoeConfig":
        defaults = dict(
            vocab_size=96, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            experts_held=(0, 2), block_length=4, capacity_factor=4.0)
        defaults.update(kw)
        return SdarMoeConfig(**defaults)


def init(key, cfg: SdarMoeConfig, scale: float = 0.02,
         embed_scale: float = 1.0):
    """Seeded float32 parameters: normal(0, ``scale``) matrices (the
    family's ``initializer_range``), unit norm gains, and embedding rows
    at ``embed_scale``. Unit-variance rows keep the residual stream
    token-specific: with rows at 0.02 the stream after the first layer is
    the attention's average of the context, nearly one direction for
    every position, and a random router then sends most positions to the
    same few experts (held pairs a layer 0.01-3.0x the expectation from
    seed to seed, against 0.65-1.7x: PERF.md section 4)."""
    c = cfg
    d, hd, f = c.hidden_size, c.head_dim, c.moe_intermediate_size
    held = c.experts_held[1]

    def normal(k, *shape):
        return scale * jax.random.normal(k, shape, jnp.float32)

    keys = jax.random.split(key, c.num_hidden_layers + 2)
    params = {"embed_tokens": embed_scale / scale * normal(
                  keys[0], c.vocab_size, d),
              "norm": jnp.ones((d,), jnp.float32),
              "lm_head": normal(keys[1], d, c.vocab_size)}
    for i in range(c.num_hidden_layers):
        k = jax.random.split(keys[i + 2], 8)
        params[f"layer_{i}"] = {
            "input_layernorm": jnp.ones((d,), jnp.float32),
            "q_proj": normal(k[0], d, c.num_attention_heads * hd),
            "k_proj": normal(k[1], d, c.num_key_value_heads * hd),
            "v_proj": normal(k[2], d, c.num_key_value_heads * hd),
            "o_proj": normal(k[3], c.num_attention_heads * hd, d),
            "q_norm": jnp.ones((hd,), jnp.float32),
            "k_norm": jnp.ones((hd,), jnp.float32),
            "post_attention_layernorm": jnp.ones((d,), jnp.float32),
            "router": normal(k[4], d, c.num_experts),
            "experts": {"gate_proj": normal(k[5], held, d, f),
                        "up_proj": normal(k[6], held, d, f),
                        "down_proj": normal(k[7], held, f, d)},
        }
    return params


def rms_norm(x, gain, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain).astype(x.dtype)


def rotary(x, positions, theta: float, freq=None):
    """``x [b, s, heads, head_dim]`` rotated at ``positions [s]``: pairs
    (i, i + head_dim / 2) by the angle position * theta^(-2i / head_dim)
    (the rotate-half form of the family's modelling code), or by
    position * ``freq[i]`` where a caller brings its own frequencies
    (``models/xing.py``: YaRN's)."""
    half = x.shape[-1] // 2
    if freq is None:
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def gqa_attention(x, lp, cfg, positions, mask, block=None, half=None, *,
                  scope=None, proj_scope=None):
    """Grouped-query attention over ``x [b, s, hidden]`` under ``mask``
    (``None``, ``'causal'`` or ``'block_diffusion'`` with ``block`` and
    ``half``). ``cfg`` is this family's, or another's with the same
    fields (``models/lfm2.py``: ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``rms_norm_eps``,
    ``rope_theta``, ``attention``, ``dtype``). ``scope`` names the
    attention itself (``attn.bd`` under the block-diffusion mask, else
    ``attn``); ``proj_scope`` the four projections with the q/k norm and
    the rotary embedding, which carry no scope without it."""
    from pytorch_ps_mpi_tpu.ops import attention_pallas as ap

    c = cfg
    b, s, _ = x.shape
    dt = c.dtype
    projections = (functools.partial(jax.named_scope, proj_scope)
                   if proj_scope else contextlib.nullcontext)
    with projections():
        q = (x @ lp["q_proj"].astype(dt)).reshape(
            b, s, c.num_attention_heads, c.head_dim)
        k = (x @ lp["k_proj"].astype(dt)).reshape(
            b, s, c.num_key_value_heads, c.head_dim)
        v = (x @ lp["v_proj"].astype(dt)).reshape(
            b, s, c.num_key_value_heads, c.head_dim)
        q = rotary(rms_norm(q, lp["q_norm"], c.rms_norm_eps), positions,
                   c.rope_theta)
        k = rotary(rms_norm(k, lp["k_norm"], c.rms_norm_eps), positions,
                   c.rope_theta)
    if c.attention not in ("full", "flash", "einsum"):
        raise ValueError(f"unknown attention={c.attention!r}")
    # as models/bert.py: 'flash' is always the kernel, 'full' takes it
    # where ops/attention_pallas.flash_auto_ok says so, 'einsum' never
    kernel = c.attention == "flash" or (
        c.attention == "full" and ap.flash_auto_ok(s, s, dt))
    with jax.named_scope(scope or (
            "attn.bd" if mask == "block_diffusion" else "attn")):
        if kernel:
            out = ap.flash_attention(q, k, v, mask=mask, block=block,
                                     half=half)
        else:
            out, _ = ap._attention_jnp(
                q, k, v, 0, 0, ap._mask_spec(False, mask, block, half),
                c.head_dim ** -0.5)
    with projections():
        return out.reshape(b, s, -1) @ lp["o_proj"].astype(dt)


def decoder_layer(x, lp, cfg: SdarMoeConfig, positions, mask, block, half):
    """One block; returns (x, pairs per held expert [count])."""
    c = cfg
    b, s, d = x.shape
    x = x + gqa_attention(rms_norm(x, lp["input_layernorm"], c.rms_norm_eps),
                          lp, c, positions, mask, block, half)
    y = rms_norm(x, lp["post_attention_layernorm"], c.rms_norm_eps)
    ex = lp["experts"]
    y, loads = dropless_moe(
        y.reshape(b * s, d), lp["router"],
        ex["gate_proj"].astype(c.dtype), ex["up_proj"].astype(c.dtype),
        ex["down_proj"].astype(c.dtype),
        top_k=c.num_experts_per_tok, experts_held=c.experts_held,
        capacity_factor=c.capacity_factor, norm_topk_prob=c.norm_topk_prob)
    return x + y.reshape(b, s, d), loads


def hidden_states(params, tokens, positions, cfg: SdarMoeConfig, *,
                  mask=None, block=None, half=None):
    """``tokens [b, s]`` at ``positions [s]`` -> (hidden ``[b, s, d]``
    before the final norm, pairs per held expert ``[layers, count]``)."""
    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(cfg.dtype)

    def layer(x, lp):
        return decoder_layer(x, lp, cfg, positions, mask, block, half)

    if cfg.remat:
        layer = checkpoint_layer(layer)
    loads = []
    for i in range(cfg.num_hidden_layers):
        x, n = layer(x, params[f"layer_{i}"])
        loads.append(n)
    return x, jnp.stack(loads)


def logits_of(params, x, cfg: SdarMoeConfig):
    """Final norm and the untied head: float32 logits."""
    with jax.named_scope("loss.head"):
        x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
        return jnp.dot(x, params["lm_head"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)


def _doubled(batch):
    noised, clean = batch["noised"], batch["tokens"]
    length = clean.shape[1]
    ids = jnp.arange(length, dtype=jnp.int32)
    return (jnp.concatenate([noised, clean], axis=1),
            jnp.concatenate([ids, ids]), length)


def block_diffusion_logits(params, batch, cfg: SdarMoeConfig):
    """Float32 logits ``[rows, L, vocab]`` at the noised positions, and
    the router loads ``[layers, count]``."""
    tokens, positions, length = _doubled(batch)
    x, loads = hidden_states(params, tokens, positions, cfg,
                             mask="block_diffusion", block=cfg.block_length,
                             half=length)
    return logits_of(params, x[:, :length], cfg), loads


def block_diffusion_loss(params, batch, cfg: SdarMoeConfig):
    """``batch``: ``tokens [rows, L]`` (clean), ``noised [rows, L]``,
    ``replaced [rows, L]`` bool, ``t [rows, L / block]`` in (0, 1]."""
    logits, _ = block_diffusion_logits(params, batch, cfg)
    with jax.named_scope("loss.head"):
        ll = target_log_likelihood(logits, batch["tokens"])
        weight = batch["replaced"].astype(jnp.float32) / jnp.repeat(
            batch["t"].astype(jnp.float32), cfg.block_length, axis=1)
        return -jnp.sum(ll * weight) / ll.size


def router_loads(params, batch, cfg: SdarMoeConfig):
    """Pairs per held expert in every layer ``[layers, count]`` for this
    batch: what the benchmark's comparison holds against the reference's
    router (jit it; nothing of the training step computes it)."""
    tokens, positions, length = _doubled(batch)
    return hidden_states(params, tokens, positions, cfg,
                         mask="block_diffusion", block=cfg.block_length,
                         half=length)[1]
