"""Config-driven pair decoder: the ``lfm2_moe`` family (LFM2-24B-A2B:
gated short-convolution layers three to one with grouped-query attention,
and two kinds of feed-forward by published index), and the layers of the
``solar_open2`` family (Solar-Open2-250B: gated delta-rule layers three
to one with gated NoPE attention, every feed-forward an expert layer
with a shared expert; its configuration and parameters are
``models/solar.py``'s). Read from the source ``config.json``'s key names.
A layer is a PAIR, (operator kind, feed-forward kind): the operator from
``layer_types`` and the feed-forward from ``num_dense_layers``, both at
the layer's PUBLISHED index, and the two vary independently (a dense
``conv`` layer, expert ``conv`` layers, expert attention layers). Head
counts are the HELD ones: a chip's share of a layer whose heads are
spread over several. Nothing here is a mechanism of its own:
``rms_norm``, ``rotary`` and the grouped-query attention with its
per-head q/k norm or its output gate are ``models/sdar_moe.py``'s, the
SwiGLU is ``models/xing.py``'s, the routed experts
``parallel/dropless.py``'s, the convolutions ``ops/short_conv.py``'s,
the delta rule ``ops/delta_rule.py``'s, the loss ``models/gpt.py``'s.

With ``x [b, T, hidden]``, every norm an RMSNorm with a gain at
``norm_eps``:

- *Layer i.* ``h = x + operator_i(RMSNorm(x))``, ``y = h +
  ffn_i(RMSNorm(h))``.
- *``conv``.* ``[B | C | u] = a W_in`` (``W_in [d, 3 d]``, no bias);
  ``out = (C * conv(B * u)) W_out`` with ``conv`` the causal depthwise
  convolution of ``conv_L_cache`` taps a channel, zero before the row's
  first position (``ops/short_conv.py``).
- *``full_attention``.* q as ``num_attention_heads`` heads, k and v as
  ``num_key_value_heads``, of ``head_dim`` each; q and k through a
  per-head RMSNorm, then rotary over the whole head at ``rope_theta``;
  causal ``softmax(q k^T / sqrt(head)) v``; ``W_o``. In ``solar``: no
  rotary (``use_rope`` false), no q/k norm, and the heads' output times
  ``sigmoid(a W_g)`` before ``W_o`` (``use_gqa_gate``).
- *``linear_attention``* (``solar``; ``ops/delta_rule.py``), for each
  held head of ``dk = dv = head_dim``: ``q, k, v = silu(conv(a W_{q,k,v}))``
  with ``conv`` the causal depthwise convolution of ``short_conv_kernel_size``
  taps; q and k L2-normalised a head (eps 1e-6), q times ``dk^-1/2``;
  ``beta = 2 sigmoid(a W_b)`` (``kda_allow_neg_eigval``); the log-decay
  ``g = -exp(A_log_h) softplus(a W_fa W_fb + dt_bias)`` a key channel;
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``
  from a zero state, ``o_t = S_t^T q_t``; ``out = [RMSNorm(o_h) *
  sigmoid(a W_ga W_gb + b_gb)_h]_h W_o``.
- *Feed-forward.* Published index under ``num_dense_layers``: a SwiGLU of
  ``intermediate_size``. Any other: ``s = sigmoid(a W_r)`` over all
  ``num_experts`` (float32), the ``num_experts_per_tok`` chosen by
  ``top_k(s + expert_bias)``, gates ``s / sum(s) *
  routed_scaling_factor`` over the chosen (no epsilon in the sum:
  ``dropless.route``); ``y = sum over the chosen AND held of gate_e
  expert_e(a)``, plus ``shared(a)`` where the layer has a ``shared``
  SwiGLU (``solar``; ``lfm2`` has none). ``expert_bias``, where a layer
  has it, is a leaf of zeros behind ``stop_gradient``: its gradient is
  exactly zero and no step moves it (the load-driven update of the source
  family is non-gradient state this package does not carry).
- After the last layer one more RMSNorm (``embedding_norm``) and the head:
  the embedding's transpose (tied), or ``lm_head`` where the parameters
  have one (untied): float32 logits.

Parameters are a plain pytree (float32). The operators' leaves carry the
names this package's functions read: attention ``q_proj, k_proj, v_proj,
o_proj, q_norm, k_norm`` (the source: ``out_proj``, ``q_layernorm``,
``k_layernorm``), SwiGLUs ``gate_proj, up_proj, down_proj`` (the source:
``w1, w3, w2``), the router ``router`` (the source: ``gate``), the taps
``conv [d, taps]`` (the source: ``conv.weight [d, 1, taps]``). The compute
dtype is ``cfg.dtype``; norms, the router, the convolution's gates and tap
sum, the softmaxes, the decays and betas (their products at full
precision), the delta rule's state and the logits are float32. Named
scopes for a device trace: ``conv.proj``, ``conv.mix``, ``kda.proj``,
``kda.conv``, ``kda.scan``, ``attn.gqa_proj``, ``attn.gqa``,
``mlp.swiglu``, ``moe.shared``, ``moe.route|dispatch|experts|combine``,
``loss.head``.
With ``remat`` a layer is a checkpoint that keeps the flash kernels'
output and per-row logsumexp and the expert layer's plan and router
choice (``ops/_common.checkpoint_layer``) and recomputes the rest: the
convolutions and the matrix products run forward twice.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.models.gpt import causal_lm_loss as next_token_loss
from pytorch_ps_mpi_tpu.models.sdar_moe import gqa_attention, rms_norm
from pytorch_ps_mpi_tpu.models.xing import swiglu
from pytorch_ps_mpi_tpu.ops._common import checkpoint_layer
from pytorch_ps_mpi_tpu.ops.delta_rule import HIGHEST, delta_rule
from pytorch_ps_mpi_tpu.ops.short_conv import gated_short_conv, silu_short_conv
from pytorch_ps_mpi_tpu.parallel.dropless import dropless_moe

OPERATORS = ("conv", "full_attention")


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int
    hidden_size: int
    intermediate_size: int             # the leading dense layers' SwiGLU
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    num_experts: int                   # the router's width (published)
    num_experts_per_tok: int
    experts_held: Tuple[int, int]      # (first, count) of the experts here
    layer_types: Tuple[str, ...]       # each held layer's operator
    layer_index: Tuple[int, ...]       # each held layer's published index
    num_dense_layers: int = 0
    conv_L_cache: int = 3              # taps of the short convolution
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    capacity_factor: float = 2.0       # parallel/dropless.py
    dtype: Any = jnp.float32
    attention: str = "full"            # 'full' | 'flash' | 'einsum' (bert.py)
    remat: bool = False                # checkpoint_layer around each layer

    def __post_init__(self):
        if len(self.layer_types) != len(self.layer_index):
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{len(self.layer_index)} layers")
        unknown = set(self.layer_types) - set(OPERATORS)
        if unknown:
            raise ValueError(f"layer_types {sorted(unknown)}: only "
                             f"{OPERATORS} are computed")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size over num_attention_heads")

    # what models/sdar_moe.py::gqa_attention reads of its configuration
    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rms_norm_eps(self) -> float:
        return self.norm_eps

    @property
    def layers(self) -> Tuple[Tuple[str, bool], ...]:
        """(operator kind, the feed-forward is dense) of each held layer."""
        return tuple((kind, i < self.num_dense_layers)
                     for kind, i in zip(self.layer_types, self.layer_index))

    @staticmethod
    def from_source(config: dict) -> "Lfm2Config":
        """From a configuration file under the source's key names.
        ``layer_types`` is the PUBLISHED list, whole; ``num_hidden_layers``
        counts the layers held and ``published_layer_index`` gives each
        one's index in that list (without it: the first
        ``num_hidden_layers``). Where a chip holds a share, ``num_experts``
        counts the experts held (first ``first_expert``) and
        ``published_num_experts`` is the router's width."""
        if config.get("conv_bias", False):
            raise ValueError("conv_bias true: no bias is computed")
        index = tuple(config.get("published_layer_index",
                                 range(config["num_hidden_layers"])))
        if len(index) != config["num_hidden_layers"]:
            raise ValueError(f"{len(index)} published_layer_index for "
                             f"{config['num_hidden_layers']} layers")
        held = int(config["num_experts"])
        rope = config.get("rope_parameters", config)
        fields = {f.name for f in dataclasses.fields(Lfm2Config)}
        kw = {k: v for k, v in config.items() if k in fields}
        kw.update(
            num_experts=int(config.get("published_num_experts", held)),
            experts_held=(int(config.get("first_expert", 0)), held),
            layer_types=tuple(config["layer_types"][i] for i in index),
            layer_index=index,
            rope_theta=float(rope.get("rope_theta", 1e6)),
            capacity_factor=float(config.get("moe_capacity_factor", 2.0)),
            dtype=jnp.dtype(config.get("dtype", "float32")).type)
        return Lfm2Config(**kw)

    @staticmethod
    def tiny(**kw) -> "Lfm2Config":
        """Both operator kinds and both feed-forward kinds: a dense
        ``conv`` layer, an expert attention layer, an expert ``conv``
        layer."""
        defaults = dict(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=16, num_attention_heads=4,
            num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
            experts_held=(0, 2),
            layer_types=("conv", "full_attention", "conv"),
            layer_index=(0, 2, 3), num_dense_layers=2, capacity_factor=4.0)
        defaults.update(kw)
        return Lfm2Config(**defaults)


def init(key, cfg: Lfm2Config, scale: float = 0.02):
    """Seeded float32 parameters: normal(0, ``scale``) matrices, taps and
    embedding rows (the head is the embedding's transpose: rows at unit
    variance would make logits of ``sqrt(hidden)``), unit norm gains, a
    zero router bias."""
    c = cfg
    d, hd, held = c.hidden_size, c.head_dim, c.experts_held[1]
    ones = lambda size: jnp.ones((size,), jnp.float32)

    def normal(k, *shape):
        return scale * jax.random.normal(k, shape, jnp.float32)

    def swiglu_of(k, width, *lead):
        k = jax.random.split(k, 3)
        return {"gate_proj": normal(k[0], *lead, d, width),
                "up_proj": normal(k[1], *lead, d, width),
                "down_proj": normal(k[2], *lead, width, d)}

    def layer(k, kind, dense):
        k = jax.random.split(k, 6)
        p = {"operator_norm": ones(d), "ffn_norm": ones(d)}
        if kind == "conv":
            p["conv"] = {"in_proj": normal(k[0], d, 3 * d),
                         "conv": normal(k[1], d, c.conv_L_cache),
                         "out_proj": normal(k[2], d, d)}
        else:
            kk = jax.random.split(k[0], 4)
            p["self_attn"] = {
                "q_proj": normal(kk[0], d, c.num_attention_heads * hd),
                "k_proj": normal(kk[1], d, c.num_key_value_heads * hd),
                "v_proj": normal(kk[2], d, c.num_key_value_heads * hd),
                "o_proj": normal(kk[3], c.num_attention_heads * hd, d),
                "q_norm": ones(hd), "k_norm": ones(hd)}
        if dense:
            p["feed_forward"] = swiglu_of(k[3], c.intermediate_size)
        else:
            p["router"] = normal(k[4], d, c.num_experts)
            if c.use_expert_bias:
                p["expert_bias"] = jnp.zeros((c.num_experts,), jnp.float32)
            p["experts"] = swiglu_of(k[5], c.moe_intermediate_size, held)
        return p

    keys = jax.random.split(key, len(c.layer_index) + 1)
    params = {"embed_tokens": normal(keys[0], c.vocab_size, d),
              "embedding_norm": ones(d)}
    for i, (kind, dense) in enumerate(c.layers):
        params[f"layer_{i}"] = layer(keys[i + 1], kind, dense)
    return params


def param_count(cfg, init_fn=None) -> int:
    """Parameters of ``init_fn(key, cfg)`` (this module's ``init`` by
    default), from its shapes alone."""
    shapes = jax.eval_shape(lambda key: (init_fn or init)(key, cfg),
                            jax.random.key(0))
    return sum(a.size for a in jax.tree.leaves(shapes))


def short_conv_operator(u, p, cfg: Lfm2Config):
    """``u [b, T, d]`` -> ``[b, T, d]``: the gated short convolution
    between its two projections."""
    with jax.named_scope("conv.proj"):
        bcu = u @ p["in_proj"].astype(cfg.dtype)
    mixed = gated_short_conv(bcu, p["conv"])
    with jax.named_scope("conv.proj"):
        return mixed @ p["out_proj"].astype(cfg.dtype)


def l2_normalize(x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + eps)


def delta_rule_operator(u, p, cfg):
    """``u [b, T, d]`` -> ``[b, T, d]``: the gated delta rule over the
    held heads between its projections (the module's docstring)."""
    c = cfg
    b, s, _ = u.shape
    h, hd, dt = c.linear_num_heads, c.linear_head_dim, c.dtype
    heads = lambda a: a.reshape(b, s, h, hd)
    with jax.named_scope("kda.proj"):
        q, k, v = (u @ p[f"{n}_proj"].astype(dt) for n in "qkv")
    q, k, v = (heads(silu_short_conv(a, p[f"{n}_conv"], scope="kda.conv"))
               for a, n in ((q, "q"), (k, "k"), (v, "v")))
    with jax.named_scope("kda.proj"):
        q = (l2_normalize(q) * hd ** -0.5).astype(dt)
        k = l2_normalize(k).astype(dt)
        a = u.astype(jnp.float32)
        beta = jax.nn.sigmoid(jnp.dot(a, p["b_proj"], precision=HIGHEST))
        if c.kda_allow_neg_eigval:
            beta = 2.0 * beta
        f = jnp.dot(jnp.dot(a, p["f_a_proj"], precision=HIGHEST),
                    p["f_b_proj"], precision=HIGHEST)
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            heads(f + p["dt_bias"]))
    o = delta_rule(q, k, v, g, beta)
    with jax.named_scope("kda.proj"):
        gate = jax.nn.sigmoid(((u @ p["g_a_proj"].astype(dt))
                               @ p["g_b_proj"].astype(dt)).astype(jnp.float32)
                              + p["g_b_bias"])
        o = rms_norm(o, p["o_norm"], c.rms_norm_eps) * heads(gate)
        return o.reshape(b, s, h * hd).astype(dt) @ p["o_proj"].astype(dt)


def expert_ffn(u, lp, cfg: Lfm2Config):
    """``u [b, T, d]`` -> (this share's routed part, plus the shared
    expert where the layer has one; pairs per held expert ``[count]``)."""
    c = cfg
    b, s, d = u.shape
    ex = lp["experts"]
    routed, loads = dropless_moe(
        u.reshape(b * s, d), lp["router"], ex["gate_proj"].astype(c.dtype),
        ex["up_proj"].astype(c.dtype), ex["down_proj"].astype(c.dtype),
        top_k=c.num_experts_per_tok, experts_held=c.experts_held,
        capacity_factor=c.capacity_factor, norm_topk_prob=c.norm_topk_prob,
        scoring="sigmoid", router_bias=lp.get("expert_bias"),
        routed_scaling_factor=c.routed_scaling_factor)
    routed = routed.reshape(b, s, d)
    if "shared" in lp:
        routed = swiglu(u, lp["shared"], c.dtype, "moe.shared") + routed
    return routed, loads


def decoder_layer(x, lp, cfg: Lfm2Config, positions, kind: str, dense: bool):
    """One layer of the pair (``kind``, ``dense``): (x ``[b, T, d]``,
    pairs per held expert ``[count]``: zeros from a dense layer)."""
    c = cfg
    u = rms_norm(x, lp["operator_norm"], c.norm_eps)
    if kind == "conv":
        x = x + short_conv_operator(u, lp["conv"], c)
    elif kind == "linear_attention":
        x = x + delta_rule_operator(u, lp["linear_attn"], c)
    else:
        x = x + gqa_attention(u, lp["self_attn"], c, positions, "causal",
                              scope="attn.gqa", proj_scope="attn.gqa_proj")
    u = rms_norm(x, lp["ffn_norm"], c.norm_eps)
    if dense:
        return x + swiglu(u, lp["feed_forward"], c.dtype, "mlp.swiglu"), \
            jnp.zeros((c.experts_held[1],), jnp.int32)
    y, loads = expert_ffn(u, lp, c)
    return x + y, loads


def hidden_states(params, tokens, cfg: Lfm2Config):
    """``tokens [b, T]`` -> (hidden ``[b, T, d]`` before the final norm,
    pairs per held expert ``[expert layers, count]``)."""
    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(cfg.dtype)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    loads = []
    for i, (kind, dense) in enumerate(cfg.layers):
        def layer(x, lp, kind=kind, dense=dense):
            return decoder_layer(x, lp, cfg, positions, kind, dense)

        x, n = (checkpoint_layer(layer) if cfg.remat else layer)(
            x, params[f"layer_{i}"])
        if not dense:
            loads.append(n)
    return x, jnp.stack(loads) if loads else jnp.zeros(
        (0, cfg.experts_held[1]), jnp.int32)


def logits_of(params, x, cfg: Lfm2Config):
    """The final norm and the head (``lm_head`` where there is one, else
    the embedding's transpose): float32 logits."""
    with jax.named_scope("loss.head"):
        x = rms_norm(x, params["embedding_norm"], cfg.norm_eps)
        head = (params["lm_head"] if "lm_head" in params
                else params["embed_tokens"].T)
        return jnp.dot(x, head.astype(cfg.dtype),
                       preferred_element_type=jnp.float32)


def apply(params, tokens, cfg: Lfm2Config):
    """(float32 next-token logits ``[b, T, vocab]``, pairs per held
    expert ``[expert layers, count]``)."""
    x, loads = hidden_states(params, tokens, cfg)
    return logits_of(params, x, cfg), loads


def causal_lm_loss(params, batch, cfg: Lfm2Config):
    """Next-token cross-entropy of ``batch["tokens"] [b, T]``."""
    tokens = batch["tokens"]
    logits, _ = apply(params, tokens, cfg)
    with jax.named_scope("loss.head"):
        return next_token_loss(logits, tokens)


def router_loads(params, batch, cfg: Lfm2Config):
    """Pairs per held expert in every expert layer ``[expert layers,
    count]`` for this batch (jit it; nothing of the training step
    computes it)."""
    return hidden_states(params, batch["tokens"], cfg)[1]
