"""Switch-style MoE masked-LM encoder — the expert-parallel model family.

No reference analog (the reference ships no models at all — SURVEY: "no
models, no training loop"); this pairs with ``parallel/ep.py`` the way
``models/bert.py`` pairs with ``parallel/ring.py``: the dense encoder
stack with every other FFN replaced by a top-k mixture-of-experts layer
(``top_k=1``: Fedus et al. 2021, Switch Transformer, arXiv:2101.03961;
``top_k=2``: the classic GShard gate — public techniques).

Two execution modes, same parameters:

- ``expert_axis=None`` (default): dense routing — every token gathers its
  expert's weights (fine single-device; this is also the test oracle).
- ``expert_axis='expert'``: call ``apply`` inside ``shard_map`` with that
  mesh axis bound; the MoE layers dispatch through
  ``parallel/ep.moe_apply`` (capacity buffers + all_to_all). Expert
  weights are stacked on a leading ``[E]`` axis either way — shard them
  ``P(expert_axis)`` host-side (see :func:`moe_param_spec`).

This capacity-drop path is where the expert layer's exchange across
chips starts from; a configuration of the benchmark uses the dropless
layer instead (``parallel/dropless.py`` under ``models/sdar_moe.py``:
the experts one chip holds, no pair dropped, no exchange on one chip).

Load balancing: set ``aux_loss_weight`` and apply with
``mutable=["aux_loss"]`` — each MoE layer sows its weighted
Switch/GShard balance loss (``parallel/ep.load_balance_loss``); add the
collection's sum to the objective, or the router collapses onto a few
experts and the capacity buffers drop the rest.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.models.bert import BertConfig, SelfAttention
from pytorch_ps_mpi_tpu.parallel.ep import load_balance_loss, moe_apply


@dataclasses.dataclass(frozen=True)
class SwitchConfig:
    vocab_size: int = 1024
    hidden_size: int = 64
    num_layers: int = 2
    num_heads: int = 4
    intermediate_size: int = 128
    max_position: int = 128
    n_experts: int = 8
    capacity: int = 64          # per (expert, source device) — ep.py note
    top_k: int = 1              # 1 = Switch; 2 = classic GShard gate
    # weight of the Switch/GShard load-balancing auxiliary loss each MoE
    # layer SOWS into the "aux_loss" collection: apply with
    # mutable=["aux_loss"] and add the collection's SUM to the objective
    # as-is — the sown values already carry this weight. 0 disables.
    aux_loss_weight: float = 0.0
    expert_axis: Optional[str] = None
    dtype: Any = jnp.float32

    def bert_cfg(self) -> BertConfig:
        return BertConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.num_layers, num_heads=self.num_heads,
            intermediate_size=self.intermediate_size,
            max_position=self.max_position, dtype=self.dtype,
        )


class MoEFFN(nn.Module):
    """Top-k routed FFN over n_experts expert MLPs (cfg.top_k)."""

    cfg: SwitchConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        d, f, e = c.hidden_size, c.intermediate_size, c.n_experts
        # inside shard_map the expert-stacked leaves arrive SLICED to the
        # local e/axis_size experts; declare the local shape so flax's
        # parameter shape check matches (init is done in dense mode —
        # expert_axis=None — so the stored params are the full [E] stack)
        e_param = e
        if c.expert_axis is not None:
            e_param = e // jax.lax.axis_size(c.expert_axis)
        params = {
            "wr": self.param(
                "wr", nn.initializers.normal(0.02), (d, e), jnp.float32
            ),
            "w1": self.param(
                "w1", nn.initializers.normal(0.1), (e_param, d, f), jnp.float32
            ),
            "w2": self.param(
                "w2", nn.initializers.normal(0.1), (e_param, f, d), jnp.float32
            ),
        }
        b, l, _ = x.shape
        tok = x.reshape(b * l, d)
        if c.aux_loss_weight:
            aux = load_balance_loss(tok, params["wr"], top_k=c.top_k,
                                    expert_axis=c.expert_axis)
            self.sow("aux_loss", "load_balance", c.aux_loss_weight * aux)
        if c.expert_axis is not None:
            out = moe_apply(tok, params, c.expert_axis,
                            capacity=c.capacity, top_k=c.top_k)
        else:
            from pytorch_ps_mpi_tpu.parallel.ep import moe_dense_oracle

            out = moe_dense_oracle(tok, params, top_k=c.top_k)
        return out.reshape(b, l, d)


class SwitchEncoderLayer(nn.Module):
    cfg: SwitchConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        y = SelfAttention(c.bert_cfg())(nn.LayerNorm(dtype=c.dtype)(x))
        x = x + y
        y = MoEFFN(c)(nn.LayerNorm(dtype=c.dtype)(x))
        return x + y


class SwitchMLM(nn.Module):
    """Token-in, vocab-logits-out MoE masked-LM (pre-norm, every layer's
    FFN is a Switch MoE)."""

    cfg: SwitchConfig

    @nn.compact
    def __call__(self, tokens, position_offset: int = 0):
        c = self.cfg
        tok = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                       name="tok_emb")(tokens)
        positions = position_offset + jnp.arange(tokens.shape[-1])
        pos = nn.Embed(c.max_position, c.hidden_size, dtype=c.dtype,
                       name="pos_emb")(positions)
        x = tok + pos[None]
        for i in range(c.num_layers):
            x = SwitchEncoderLayer(c, name=f"layer_{i}")(x)
        x = nn.LayerNorm(dtype=c.dtype)(x)
        logits = nn.Dense(c.vocab_size, dtype=c.dtype, name="mlm_head")(x)
        return logits.astype(jnp.float32)


def moe_param_spec(params: Any, expert_axis: str):
    """PartitionSpec pytree for SwitchMLM parameters: expert-stacked
    leaves (``w1``/``w2`` under any ``MoEFFN``) sharded over
    ``expert_axis``; everything else replicated."""
    from jax.sharding import PartitionSpec as P

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree.structure(params)
    specs = []
    for path, _ in flat:
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        sharded = any(k in ("w1", "w2") for k in keys)
        specs.append(P(expert_axis) if sharded else P())
    return jax.tree.unflatten(treedef, specs)
