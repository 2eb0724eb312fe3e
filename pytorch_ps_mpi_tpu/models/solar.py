"""The configuration and parameters of the ``solar_open2`` family
(Solar-Open2-250B): gated delta-rule layers (Kimi Linear's KDA form) three
to one with gated NoPE grouped-query attention, every feed-forward 320
routed experts top-8 beside one shared expert, an untied head. The layers,
the head and the loss are ``models/lfm2.py``'s pair decoder (``apply``,
``causal_lm_loss``, ``router_loads`` take a ``SolarConfig``); its
docstring writes the equations.

Head counts are the chip's share: ``num_attention_heads`` /
``num_key_value_heads`` and ``linear_attn_config.num_heads`` count the heads
held here; the router keeps its published width (``num_experts``) and
``experts_held`` says which experts live here. A layer's operator is
``full_attention`` where its PUBLISHED index is in ``gqa_layers``, else
``linear_attention``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SolarConfig:
    vocab_size: int
    hidden_size: int
    moe_intermediate_size: int
    num_attention_heads: int           # query heads held
    num_key_value_heads: int           # key-value heads held
    head_dim: int
    num_experts: int                   # the router's width (published)
    num_experts_per_tok: int
    experts_held: Tuple[int, int]      # (first, count) of the experts here
    layer_types: Tuple[str, ...]       # each held layer's operator
    layer_index: Tuple[int, ...]       # each held layer's published index
    linear_num_heads: int              # delta-rule heads held
    linear_head_dim: int
    short_conv_kernel_size: int = 4
    n_shared_experts: int = 1
    kda_allow_neg_eigval: bool = True
    use_rope: bool = False
    use_gqa_gate: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e4
    capacity_factor: float = 2.0       # parallel/dropless.py
    dtype: Any = jnp.float32
    attention: str = "full"            # 'full' | 'flash' | 'einsum' (bert.py)
    remat: bool = False                # checkpoint_layer around each layer

    def __post_init__(self):
        if len(self.layer_types) != len(self.layer_index):
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{len(self.layer_index)} layers")
        unknown = set(self.layer_types) - {"full_attention",
                                           "linear_attention"}
        if unknown:
            raise ValueError(f"layer_types {sorted(unknown)}")

    # what models/lfm2.py's pair decoder reads of its configuration
    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def layers(self) -> Tuple[Tuple[str, bool], ...]:
        """(operator kind, the feed-forward is dense: never) of each held
        layer."""
        return tuple((kind, False) for kind in self.layer_types)

    @staticmethod
    def from_source(config: dict) -> "SolarConfig":
        """From a configuration file under the source's key names.
        ``num_hidden_layers`` counts the layers held and
        ``published_layer_index`` gives each one's published index
        (without it: the first ``num_hidden_layers``); ``n_routed_experts``
        counts the experts held (first ``first_expert``) and
        ``published_n_routed_experts`` is the router's width."""
        for key, built in (("first_k_dense_replace", 0),
                           ("kda_use_full_proj", False)):
            if config.get(key, built) != built:
                raise ValueError(f"{key} {config[key]!r}: only {built!r} is "
                                 "built")
        linear = config["linear_attn_config"]
        if linear.get("num_kv_heads") not in (None, linear["num_heads"]):
            raise ValueError("linear_attn_config.num_kv_heads: the delta "
                             "rule's heads are not grouped")
        index = tuple(config.get("published_layer_index",
                                 range(config["num_hidden_layers"])))
        if len(index) != config["num_hidden_layers"]:
            raise ValueError(f"{len(index)} published_layer_index for "
                             f"{config['num_hidden_layers']} layers")
        full = set(config["gqa_layers"])
        held = int(config["n_routed_experts"])
        fields = {f.name for f in dataclasses.fields(SolarConfig)}
        kw = {k: v for k, v in config.items() if k in fields}
        kw.update(
            num_experts=int(config.get("published_n_routed_experts", held)),
            experts_held=(int(config.get("first_expert", 0)), held),
            layer_types=tuple("full_attention" if i in full
                              else "linear_attention" for i in index),
            layer_index=index,
            linear_num_heads=int(linear["num_heads"]),
            linear_head_dim=int(linear["head_dim"]),
            short_conv_kernel_size=int(linear["short_conv_kernel_size"]),
            capacity_factor=float(config.get("moe_capacity_factor", 2.0)),
            dtype=jnp.dtype(config.get("dtype", "float32")).type)
        return SolarConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "SolarConfig":
        """Both operator kinds: a GQA layer, then two delta-rule layers."""
        defaults = dict(
            vocab_size=96, hidden_size=32, moe_intermediate_size=16,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            num_experts=8, num_experts_per_tok=2, experts_held=(0, 2),
            layer_types=("full_attention", "linear_attention",
                         "linear_attention"),
            layer_index=(0, 1, 2), linear_num_heads=2, linear_head_dim=8,
            capacity_factor=4.0)
        defaults.update(kw)
        return SolarConfig(**defaults)


def init(key, cfg: SolarConfig, scale: float = 0.02):
    """Seeded float32 parameters: normal(0, ``scale``) matrices and taps,
    unit gains, zero biases, embedding rows at unit variance under the
    untied head, and the delta rule's decay as the family initialises it
    (``init_layer``)."""
    c = cfg
    keys = jax.random.split(key, len(c.layer_index) + 2)
    params = {"embed_tokens": jax.random.normal(
                  keys[0], (c.vocab_size, c.hidden_size), jnp.float32),
              "embedding_norm": jnp.ones((c.hidden_size,), jnp.float32),
              "lm_head": scale * jax.random.normal(
                  keys[1], (c.hidden_size, c.vocab_size), jnp.float32)}
    for i, kind in enumerate(c.layer_types):
        params[f"layer_{i}"] = init_layer(keys[i + 2], kind, c, scale)
    return params


def init_layer(key, kind: str, cfg: SolarConfig, scale: float = 0.02):
    """One layer's parameters. The delta rule's ``A_log = log U(1, 16)`` a
    head and ``dt_bias = softplus^-1(dt)`` a channel with ``dt`` log-uniform
    in [1e-3, 1e-1] (Kimi Linear's initialisation)."""
    c = cfg
    d, f, held = c.hidden_size, c.moe_intermediate_size, c.experts_held[1]
    ones = lambda size: jnp.ones((size,), jnp.float32)

    def normal(k, *shape):
        return scale * jax.random.normal(k, shape, jnp.float32)

    def swiglu_of(k, width, *lead):
        k = jax.random.split(k, 3)
        return {"gate_proj": normal(k[0], *lead, d, width),
                "up_proj": normal(k[1], *lead, d, width),
                "down_proj": normal(k[2], *lead, width, d)}

    k = jax.random.split(key, 4)
    p = {"operator_norm": ones(d), "ffn_norm": ones(d),
         "router": normal(k[0], d, c.num_experts),
         "experts": swiglu_of(k[1], f, held),
         "shared": swiglu_of(k[2], f * c.n_shared_experts)}
    if kind == "full_attention":
        kk = jax.random.split(k[3], 5)
        q, kv = (c.num_attention_heads * c.head_dim,
                 c.num_key_value_heads * c.head_dim)
        p["self_attn"] = {"q_proj": normal(kk[0], d, q),
                          "k_proj": normal(kk[1], d, kv),
                          "v_proj": normal(kk[2], d, kv),
                          "o_proj": normal(kk[3], q, d)}
        if c.use_gqa_gate:
            p["self_attn"]["g_proj"] = normal(kk[4], d, q)
        return p
    h, hd, taps = c.linear_num_heads, c.linear_head_dim, c.short_conv_kernel_size
    w = h * hd
    kk = jax.random.split(k[3], 14)
    dt = jnp.exp(jax.random.uniform(kk[11], (w,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    p["linear_attn"] = {
        "q_proj": normal(kk[0], d, w), "k_proj": normal(kk[1], d, w),
        "v_proj": normal(kk[2], d, w),
        "q_conv": normal(kk[3], w, taps), "k_conv": normal(kk[4], w, taps),
        "v_conv": normal(kk[5], w, taps),
        "b_proj": normal(kk[6], d, h),
        "f_a_proj": normal(kk[7], d, hd), "f_b_proj": normal(kk[8], hd, w),
        "A_log": jnp.log(jax.random.uniform(kk[12], (h,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "g_a_proj": normal(kk[9], d, hd), "g_b_proj": normal(kk[10], hd, w),
        "g_b_bias": jnp.zeros((w,), jnp.float32),
        "o_norm": ones(hd), "o_proj": normal(kk[13], w, d)}
    return p
