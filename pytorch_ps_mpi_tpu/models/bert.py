"""BERT-style encoder with an MLM head — BASELINE config #5 (large flat
gradient vector: the ~110M-param embedding+encoder stack stresses
aggregation bandwidth the way the config intends).

TPU-first: attention and MLPs are einsum/matmul shaped for the MXU,
bfloat16 compute with float32 params supported via ``dtype``, and
long-context runs under sequence parallelism — set
``attention='ring'`` and call ``apply`` inside ``shard_map`` with the
sequence sharded over ``seq_axis`` (``parallel/ring.py``); position
embeddings take a per-shard ``position_offset``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.parallel.ring import ring_attention
from pytorch_ps_mpi_tpu.parallel.ulysses import ulysses_attention


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    dtype: Any = jnp.float32
    attention: str = "full"       # 'full', 'ring', or 'ulysses'
    seq_axis: str = "seq"         # mesh axis for ring/ulysses attention
    causal: bool = False          # decoder-only masking (GPT family)
    remat: bool = False           # rematerialize each layer's activations
    # in the backward pass (jax.checkpoint): activation memory drops from
    # O(layers) to O(1) layers' worth for ~1/3 extra FLOPs — the standard
    # HBM-for-FLOPs trade for long sequences / deep stacks on TPU
    scan_layers: bool = False     # lax.scan over a stacked layer body:
    # ONE layer's HLO instead of num_layers unrolled copies, cutting
    # compile time ~proportionally at identical math. Param layout changes
    # (stacked [L, ...] leaves under 'layers'), so it is opt-in;
    # stack_layer_params converts a loop-layout checkpoint.
    f32_logits: bool = True       # False keeps the [B, S, V] logits in
    # the compute dtype: at GPT-2 scale the f32 materialization is
    # 1.65 GB at b8 s1024 of pure HBM traffic, and the loss functions
    # compute their reductions in f32 regardless (fused elementwise
    # upcast — no full-size f32 array). Opt-in lever, A/B'd per window
    # like remat/scan_layers.

    @staticmethod
    def base() -> "BertConfig":
        return BertConfig()

    @staticmethod
    def tiny(**kw) -> "BertConfig":
        defaults = dict(
            vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
            intermediate_size=128, max_position=128,
        )
        defaults.update(kw)
        return BertConfig(**defaults)


class SelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        head_dim = c.hidden_size // c.num_heads
        qkv = nn.DenseGeneral(
            (3, c.num_heads, head_dim), axis=-1, dtype=c.dtype, name="qkv"
        )(x)                                   # [b, l, 3, h, d]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if c.attention == "ring":
            out = ring_attention(q, k, v, c.seq_axis, causal=c.causal)
        elif c.attention == "ulysses":
            out = ulysses_attention(q, k, v, c.seq_axis, causal=c.causal)
        elif c.attention in ("full", "flash", "einsum"):
            # 'flash': always the Pallas kernel (interpret mode off-TPU —
            # for tests). 'full': on TPU the kernel for sequences of
            # FLASH_MIN_SEQ (512) and up that tile, the dense einsum
            # otherwise (ops/attention_pallas.flash_auto_ok). 'einsum':
            # force the dense path. Read on a v5e: at 1024 the kernel
            # wins (127,316 tokens/s against 100,636, gpt2-small), at 512
            # the einsum still does (159,413 against 149,534,
            # bert-base.mlm512, PR 32): the floor is ROADMAP D4 (b)'s.
            from pytorch_ps_mpi_tpu.ops.attention_pallas import (
                flash_attention,
                flash_auto_ok,
                flash_supported,
            )

            l = q.shape[1]
            if c.attention == "flash" and not flash_supported(l, l, dtype=c.dtype):
                # the explicit mode must fail loudly, not silently hand
                # an f32 dense fallback to a 'flash'-labeled A/B
                raise ValueError(
                    f"attention='flash' cannot tile seq={l} (needs a "
                    "power-of-two block >= 8 dividing it); use 'full' "
                    "for automatic fallback"
                )
            # 'full' takes the kernel from FLASH_MIN_SEQ up, where the
            # O(L^2) score matrix dominates; below it XLA's fused dense
            # attention batches the heads' matmuls on the MXU (a layer
            # of 16 x 12 heads of 512 x 64, forward and backward: 0.796
            # ms against the kernels' 0.848 plus 0.577 of transposes and
            # logsumexp rides around them; PERF.md section 6, PR 32)
            use_kernel = c.attention == "flash" or (
                c.attention == "full" and flash_auto_ok(l, l, c.dtype)
            )
            if use_kernel:
                out = flash_attention(q, k, v, causal=c.causal)
            else:
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / head_dim ** 0.5
                if c.causal:
                    mask = jnp.tril(jnp.ones((l, l), bool))
                    s = jnp.where(mask[None, None], s,
                                  jnp.asarray(-1e30, s.dtype))
                p = jax.nn.softmax(s, axis=-1)
                out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        else:
            # a typo'd mode must not silently run shard-local dense
            # attention (valid shapes, quietly wrong model under SP)
            raise ValueError(
                f"unknown attention={c.attention!r}: expected 'full', "
                "'flash', 'einsum', 'ring', or 'ulysses'"
            )
        return nn.DenseGeneral(
            c.hidden_size, axis=(-2, -1), dtype=c.dtype, name="out"
        )(out)


class EncoderLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        y = SelfAttention(c)(nn.LayerNorm(dtype=c.dtype)(x))
        x = x + y
        y = nn.LayerNorm(dtype=c.dtype)(x)
        y = nn.Dense(c.intermediate_size, dtype=c.dtype)(y)
        y = nn.gelu(y)
        y = nn.Dense(c.hidden_size, dtype=c.dtype)(y)
        return x + y


class _ScanBody(nn.Module):
    """Carry-style wrapper ``(x, None) -> (x, None)`` so ``nn.scan``
    can drive :class:`EncoderLayer` (whose call is plain ``x -> x``)."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, x, _):
        return EncoderLayer(self.cfg)(x), None


def encoder_stack(c: BertConfig, x):
    """The shared L-layer trunk: unrolled named layers (``layer_{i}``)
    by default, or ONE scanned body with stacked ``[L, ...]`` params
    under ``layers`` when ``c.scan_layers`` — same math, one layer's
    HLO to compile instead of L copies."""
    if c.scan_layers:
        body = nn.remat(_ScanBody, prevent_cse=False) if c.remat else _ScanBody
        stack = nn.scan(
            body,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            length=c.num_layers,
        )
        x, _ = stack(c, name="layers")(x, None)
        return x
    layer_cls = nn.remat(EncoderLayer) if c.remat else EncoderLayer
    for i in range(c.num_layers):
        x = layer_cls(c, name=f"layer_{i}")(x)
    return x


def stack_layer_params(params, num_layers: int):
    """Convert loop-layout params (``layer_{i}`` subtrees) to the
    ``scan_layers`` layout (one ``layers/EncoderLayer_0`` subtree with a
    stacked leading axis) — the checkpoint-migration shim and the
    numerics-equality test's bridge."""
    stacked = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[params[f"layer_{i}"] for i in range(num_layers)],
    )
    rest = {k: v for k, v in params.items()
            if not k.startswith("layer_")}
    rest["layers"] = {"EncoderLayer_0": stacked}
    return rest


class BertMLM(nn.Module):
    """Token-in, vocab-logits-out masked-LM model (pre-norm encoder)."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, tokens, position_offset: int = 0):
        c = self.cfg
        tok = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype, name="tok_emb")(
            tokens
        )
        positions = position_offset + jnp.arange(tokens.shape[-1])
        pos = nn.Embed(c.max_position, c.hidden_size, dtype=c.dtype, name="pos_emb")(
            positions
        )
        x = tok + pos[None]
        x = encoder_stack(c, x)
        x = nn.LayerNorm(dtype=c.dtype)(x)
        logits = nn.Dense(c.vocab_size, dtype=c.dtype, name="mlm_head")(x)
        return logits.astype(jnp.float32) if c.f32_logits else logits


def target_log_likelihood(logits, targets):
    """Per-position ``log p(target)`` with f32-internal reductions for
    ANY logits dtype, WITHOUT materializing an f32 ``[..., V]`` array:
    the elementwise upcast feeds straight into the exp-sum reduction,
    which XLA fuses into one pass over the (possibly bf16) logits —
    that fusion is the entire point of ``f32_logits=False``. For f32
    inputs this is log_softmax+gather to within reassociation."""
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1)).astype(jnp.float32)
    z = jnp.exp(logits.astype(jnp.float32) - m[..., None])
    lse = m + jnp.log(jnp.sum(z, axis=-1))
    tgt = jnp.take_along_axis(
        logits, targets[..., None], axis=-1
    )[..., 0].astype(jnp.float32)
    return tgt - lse


def mlm_loss(logits, targets, mask):
    """Cross-entropy over masked positions only (f32 accumulation at
    any logits dtype — see :func:`target_log_likelihood`)."""
    ll = target_log_likelihood(logits, targets)
    mask = mask.astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
