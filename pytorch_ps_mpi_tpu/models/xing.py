"""Config-driven decoder of the DeepSeek-V3-style block — latent
attention, leading dense layers, then expert layers with a sigmoid
bias-corrected router and a shared expert, with a multi-token-prediction
module on the trunk — on either residual path, chosen by the
configuration: n residual streams mixed through Sinkhorn-projected
hyper-connections (``ops/hyper_connection.py``; ``hc_mult`` >= 2:
Xing4.0-29B-A4B, ``xing4_0``), or ONE plain pre-norm residual stream (a
source without ``hc_mult``: JoyAI-LLM-Flash, ``joyai_llm_flash``). Read
from the source ``config.json``'s key names; one function a mechanism;
``rms_norm`` and ``rotary`` are ``models/sdar_moe.py``'s, the routed
experts ``parallel/dropless.py``'s, the loss ``models/gpt.py``'s.

With d = ``hidden_size``, every layer is two sub-layers F, the attention,
then the feed-forward. On n = ``hc_mult`` streams X in R^{n x d} a
position (the embedding copied n times before layer 0, the streams
summed before the final norm) each is hyper-connected, ``X <- H_res X +
H_post^T F(RMSNorm(H_pre X))``; on the plain path (``hc_mult`` 0: no
stream axis, no hyper-connection leaf, scope or kernel; ``hc_mult`` 1
would still scale the one stream by ``H_pre`` and ``H_post`` and is
refused) it is ``x <- x + F(RMSNorm(x))``:

- *Latent attention.* ``c_q = RMSNorm(u W_qa)``; ``q = c_q W_qb`` ->
  heads of ``[q_nope | q_rope]``; ``[c_kv | k_rope] = u W_kva``;
  ``RMSNorm(c_kv) W_kvb`` -> heads of ``[k_nope | v]``; ``q_rope`` and
  the ONE ``k_rope`` (shared by all heads) rotated at YaRN's frequencies
  (the plain ones where ``rope_scaling`` is null), in pairs ``(i, i +
  rope / 2)``, or, with ``rope_interleave``, the published ``(2i, 2i +
  1)``: the pairs are brought side to side first, as the DeepSeek-V3
  modelling code does, and the scores are the interleaved rotation's;
  ``k = [k_nope | k_rope]``; causal ``softmax(q k^T scale) v`` with
  ``scale = (nope + rope)^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor)
  + 1``; then ``W_o``. Keys and values are materialised per head (the
  non-absorbed form); the kernels take 192-wide q/k over a 128-wide v.
- *Feed-forward.* A layer whose PUBLISHED index is under
  ``first_k_dense_replace`` is a SwiGLU of ``intermediate_size``; any
  other: ``s = sigmoid(u W_r)`` over all ``n_routed_experts`` (float32),
  the ``num_experts_per_tok`` chosen by ``top_k(s + e_bias)``, gates ``s
  / sum(s) * routed_scaling_factor`` over the chosen; ``y = shared(u) +
  sum over the chosen AND held of gate_e expert_e(u)``.
  ``e_score_correction_bias`` is a leaf behind ``stop_gradient``: its
  gradient is exactly zero and no step moves it (the load-driven update
  of the source family is non-gradient state this package does not
  carry).
- *Multi-token prediction, depth 1.* ``h'_i = [RMSNorm(h_i) ;
  RMSNorm(Emb(t_{i+1}))] W_eh`` with h the trunk's summed streams (the
  one stream) before the final norm; one expert layer over n copies of
  ``h'`` (over ``h'``); the module's
  own final norm and the SHARED head give logits for ``t_{i+2}``; ``loss =
  CE(main, t_{i+1}) + mtp_loss_weight * CE(mtp, t_{i+2})``. The embedding
  and the head have two readers: their gradients are sums.

Parameters are a plain pytree under the source's names (float32; the
shared expert is ``shared``, not ``shared_experts``: the benchmark's
comparison takes every path with ``experts`` in it for a stack of
experts); the compute dtype is ``cfg.dtype``; norms, the router, the
hyper-connections' weights and Sinkhorn, the softmaxes and the logits are
float32. Named scopes for a device trace: ``attn.mla_proj``,
``attn.mla``, ``hc.mix``, ``hc.sinkhorn``, ``mlp.swiglu``,
``moe.shared``, ``moe.route|dispatch|experts|combine``, ``loss.head``;
inside the prediction module the same behind ``mtp.`` (``mtp.attn.mla``,
...; ``dropless``'s own keep their names), and ``mtp.block``,
``loss.mtp``. One set-up log row ``model.plan`` a trace says what the
program holds (``record_plan``). With ``remat`` a layer is a checkpoint
with the carry (n streams, or ``x``) as its explicit input, which keeps
the flash kernels' output and per-row logsumexp and the expert layer's
plan and router choice (``ops/_common.checkpoint_layer``) and recomputes
the rest: the hyper-connections' forward pair and the matrix products
run twice.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_ps_mpi_tpu.models.gpt import causal_lm_loss as next_token_loss
from pytorch_ps_mpi_tpu.models.sdar_moe import rms_norm, rotary
from pytorch_ps_mpi_tpu.ops import hyper_connection as hc
from pytorch_ps_mpi_tpu.ops._common import checkpoint_layer
from pytorch_ps_mpi_tpu.parallel.dropless import dropless_moe
from pytorch_ps_mpi_tpu.telemetry.recorder import setup_event


@dataclasses.dataclass(frozen=True)
class XingConfig:
    vocab_size: int                    # the rows held here
    hidden_size: int
    intermediate_size: int             # the leading dense layers' SwiGLU
    moe_intermediate_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int              # the router's width (published)
    num_experts_per_tok: int
    experts_held: Tuple[int, int]      # (first, count) of the experts here
    layer_index: Tuple[int, ...]       # each held layer's published index
    first_k_dense_replace: int = 0
    n_shared_experts: int = 1
    num_nextn_predict_layers: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    hc_mult: int = 4                   # 0: the plain residual stream
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    hc_init_gate: float = 0.01         # a_* at the seed
    hc_init_bias: float = 8.0          # |b_pre|, -b_res off the diagonal
    mtp_loss_weight: float = 0.3
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None  # sorted items
    rope_interleave: bool = False      # rotary pairs (2i, 2i + 1)
    published_vocab_size: int = 0      # where vocab_size is a slice
    capacity_factor: float = 2.0       # parallel/dropless.py
    dtype: Any = jnp.float32
    attention: str = "full"            # 'full' | 'flash' | 'einsum' (bert.py)
    remat: bool = False                # checkpoint_layer around each layer

    def __post_init__(self):
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one multi-token-prediction module at most; got "
                             f"{self.num_nextn_predict_layers}")
        if self.rope_scaling and dict(self.rope_scaling).get("type") != "yarn":
            raise ValueError(f"rope_scaling {dict(self.rope_scaling)}")
        if self.hc_mult == 1 or self.hc_mult < 0:
            raise ValueError(f"hc_mult {self.hc_mult}: 0 is the plain "
                             "residual stream, hyper-connections take 2 or "
                             "more (one stream would still be scaled)")

    @property
    def layers_dense(self) -> Tuple[bool, ...]:
        return tuple(i < self.first_k_dense_replace for i in self.layer_index)

    @staticmethod
    def from_source(config: dict) -> "XingConfig":
        """From a configuration file under the source's key names. Where a
        chip holds a share, ``n_routed_experts`` counts the experts held
        (first ``first_expert``) and ``published_n_routed_experts`` is the
        router's width; ``num_hidden_layers`` counts the layers held and
        ``published_layer_index`` gives each one's index in the model. A
        source without ``hc_mult`` has one plain residual stream."""
        for key, only in (("scoring_func", "sigmoid"), ("n_group", 1),
                          ("topk_method", "noaux_tc"), ("topk_group", 1)):
            if config.get(key, only) != only:
                raise ValueError(f"{key} {config[key]!r}: only {only!r} is "
                                 "computed (no group-limited choice, no "
                                 "softmax scores here)")
        held = int(config["n_routed_experts"])
        fields = {f.name for f in dataclasses.fields(XingConfig)}
        kw = {k: v for k, v in config.items() if k in fields}
        index = tuple(config.get("published_layer_index",
                                 range(config["num_hidden_layers"])))
        if len(index) != config["num_hidden_layers"]:
            raise ValueError(f"{len(index)} published_layer_index for "
                             f"{config['num_hidden_layers']} layers")
        scaling = config.get("rope_scaling")
        kw.update(
            n_routed_experts=int(config.get("published_n_routed_experts",
                                            held)),
            experts_held=(int(config.get("first_expert", 0)), held),
            layer_index=index, hc_mult=int(config.get("hc_mult", 0)),
            rope_scaling=tuple(sorted(scaling.items())) if scaling else None,
            capacity_factor=float(config.get("moe_capacity_factor", 2.0)),
            dtype=jnp.dtype(config.get("dtype", "float32")).type)
        return XingConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "XingConfig":
        defaults = dict(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=16, num_attention_heads=4, q_lora_rank=16,
            kv_lora_rank=12, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, n_routed_experts=8, num_experts_per_tok=2,
            experts_held=(0, 2), layer_index=(0, 2, 3),
            first_k_dense_replace=2, num_nextn_predict_layers=1,
            routed_scaling_factor=2.0, hc_mult=4, capacity_factor=4.0,
            rope_scaling=tuple(sorted(dict(
                type="yarn", factor=64, beta_fast=32, beta_slow=1, mscale=1,
                mscale_all_dim=1,
                original_max_position_embeddings=16).items())))
        defaults.update(kw)
        return XingConfig(**defaults)


def init(key, cfg: XingConfig, scale: float = 0.02, embed_scale: float = 1.0):
    """Seeded float32 parameters: normal(0, ``scale``) matrices, unit norm
    gains, embedding rows at ``embed_scale`` (``models/sdar_moe.py::init``
    says why a random router wants unit rows), a zero router bias, the
    hyper-connections (where there are streams) as
    ``ops/hyper_connection.py::init``."""
    c = cfg
    d, f, n = c.hidden_size, c.moe_intermediate_size, c.hc_mult
    h, held = c.num_attention_heads, c.experts_held[1]
    ones = lambda size: jnp.ones((size,), jnp.float32)

    def normal(k, *shape):
        return scale * jax.random.normal(k, shape, jnp.float32)

    def swiglu(k, width, *lead):
        k = jax.random.split(k, 3)
        return {"gate_proj": normal(k[0], *lead, d, width),
                "up_proj": normal(k[1], *lead, d, width),
                "down_proj": normal(k[2], *lead, width, d)}

    def layer(k, dense):
        k = jax.random.split(k, 11)
        connection = lambda kk: hc.init(kk, n, d, scale=scale,
                                        gate=c.hc_init_gate,
                                        bias=c.hc_init_bias)
        p = {"input_layernorm": ones(d),
             "post_attention_layernorm": ones(d),
             "self_attn": {
                 "q_a_proj": normal(k[2], d, c.q_lora_rank),
                 "q_a_layernorm": ones(c.q_lora_rank),
                 "q_b_proj": normal(k[3], c.q_lora_rank, h * (
                     c.qk_nope_head_dim + c.qk_rope_head_dim)),
                 "kv_a_proj_with_mqa": normal(
                     k[4], d, c.kv_lora_rank + c.qk_rope_head_dim),
                 "kv_a_layernorm": ones(c.kv_lora_rank),
                 "kv_b_proj": normal(k[5], c.kv_lora_rank, h * (
                     c.qk_nope_head_dim + c.v_head_dim)),
                 "o_proj": normal(k[6], h * c.v_head_dim, d)}}
        if n:
            p.update(hc_attn=connection(k[0]), hc_mlp=connection(k[1]))
        if dense:
            p["mlp"] = swiglu(k[7], c.intermediate_size)
        else:
            p.update(router=normal(k[8], d, c.n_routed_experts),
                     e_score_correction_bias=jnp.zeros(
                         (c.n_routed_experts,), jnp.float32),
                     experts=swiglu(k[9], f, held),
                     shared=swiglu(k[10], f * c.n_shared_experts))
        return p

    keys = jax.random.split(key, len(c.layer_index) + 3)
    params = {"embed_tokens": embed_scale / scale * normal(
                  keys[0], c.vocab_size, d),
              "norm": ones(d), "lm_head": normal(keys[1], d, c.vocab_size)}
    for i, dense in enumerate(c.layers_dense):
        params[f"layer_{i}"] = layer(keys[i + 3], dense)
    if c.num_nextn_predict_layers:
        k_eh, k_layer = jax.random.split(keys[2])
        params["mtp"] = {"enorm": ones(d), "hnorm": ones(d),
                         "eh_proj": normal(k_eh, 2 * d, d),
                         "layer": layer(k_layer, False), "norm": ones(d)}
    return params


def param_count(cfg: XingConfig) -> int:
    """Parameters of ``init(key, cfg)``, from its shapes alone."""
    shapes = jax.eval_shape(lambda key: init(key, cfg), jax.random.key(0))
    return sum(a.size for a in jax.tree.leaves(shapes))


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(cfg: XingConfig):
    """(frequency of each of the ``qk_rope_head_dim / 2`` rotary pairs,
    the factor on cos and sin, the factor on the softmax scale) by the
    DeepSeek-V3 modelling code's YaRN: a pair that turns more than
    ``beta_fast`` times within the original context keeps its frequency,
    one that turns fewer than ``beta_slow`` times is interpolated by
    ``factor``, a linear ramp between."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not cfg.rope_scaling:
        return plain.astype(np.float32), 1.0, 1.0
    s = dict(cfg.rope_scaling)
    factor, original = s["factor"], s["original_max_position_embeddings"]

    def pair_turning(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(pair_turning(s["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(s["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    freq = plain / factor * ramp + plain * (1 - ramp)
    m_all = _yarn_mscale(factor, s.get("mscale_all_dim", 0))
    return (freq.astype(np.float32),
            _yarn_mscale(factor, s.get("mscale", 1)) / m_all, m_all * m_all)


def _pairs_to_halves(x):
    """The last axis's pairs ``(2i, 2i + 1)`` brought to ``(i, i + d / 2)``,
    where ``rotary`` turns them: both q and the one k take the same
    permutation, so their products are the interleaved rotation's."""
    *lead, d = x.shape
    return jnp.swapaxes(x.reshape(*lead, d // 2, 2), -1, -2).reshape(*lead, d)


def latent_attention(u, p, cfg: XingConfig, positions, tag: str = ""):
    """``u [b, s, d]`` -> ``[b, s, d]``: causal latent attention at
    ``positions [s]``."""
    from pytorch_ps_mpi_tpu.ops import attention_pallas as ap

    c = cfg
    b, s, _ = u.shape
    dt, heads = c.dtype, c.num_attention_heads
    nope, rope, rank = c.qk_nope_head_dim, c.qk_rope_head_dim, c.kv_lora_rank
    if c.attention not in ("full", "flash", "einsum"):
        raise ValueError(f"unknown attention={c.attention!r}")
    freq, on_cos_sin, on_scale = yarn_frequencies(c)
    with jax.named_scope(tag + "attn.mla_proj"):
        c_q = rms_norm(u @ p["q_a_proj"].astype(dt), p["q_a_layernorm"],
                       c.rms_norm_eps)
        q = (c_q @ p["q_b_proj"].astype(dt)).reshape(b, s, heads, nope + rope)
        kv_a = u @ p["kv_a_proj_with_mqa"].astype(dt)
        c_kv = rms_norm(kv_a[..., :rank], p["kv_a_layernorm"], c.rms_norm_eps)
        kv = (c_kv @ p["kv_b_proj"].astype(dt)).reshape(
            b, s, heads, nope + c.v_head_dim)
        side_to_side = _pairs_to_halves if c.rope_interleave else (lambda x: x)
        turn = lambda x: (on_cos_sin * rotary(
            side_to_side(x), positions, c.rope_theta,
            freq=jnp.asarray(freq))).astype(dt)
        k_rope = turn(kv_a[..., rank:].reshape(b, s, 1, rope))
        q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_rope, (b, s, heads, rope))], axis=-1)
        v = kv[..., nope:]
    scale = (nope + rope) ** -0.5 * on_scale
    # as models/bert.py: 'flash' is always the kernel, 'full' takes it
    # where ops/attention_pallas.flash_auto_ok says so, 'einsum' never
    kernel = c.attention == "flash" or (
        c.attention == "full" and ap.flash_auto_ok(s, s, dt))
    with jax.named_scope(tag + "attn.mla"):
        if kernel:
            out = ap.flash_attention(q, k, v, mask="causal", scale=scale)
        else:
            out, _ = ap._attention_jnp(q, k, v, 0, 0, ("causal",), scale)
    with jax.named_scope(tag + "attn.mla_proj"):
        return out.reshape(b, s, -1) @ p["o_proj"].astype(dt)


# ---------------------------------------------------------------------------
# feed-forward: dense, or shared + routed experts
# ---------------------------------------------------------------------------

def swiglu(u, p, dtype, scope: str):
    with jax.named_scope(scope):
        gate = u @ p["gate_proj"].astype(dtype)
        return (jax.nn.silu(gate) * (u @ p["up_proj"].astype(dtype))
                ) @ p["down_proj"].astype(dtype)


def expert_ffn(u, lp, cfg: XingConfig, tag: str = ""):
    """``u [b, s, d]`` -> (shared expert + this share's routed part, pairs
    per held expert ``[count]``)."""
    c = cfg
    b, s, d = u.shape
    ex = lp["experts"]
    routed, loads = dropless_moe(
        u.reshape(b * s, d), lp["router"], ex["gate_proj"].astype(c.dtype),
        ex["up_proj"].astype(c.dtype), ex["down_proj"].astype(c.dtype),
        top_k=c.num_experts_per_tok, experts_held=c.experts_held,
        capacity_factor=c.capacity_factor, norm_topk_prob=c.norm_topk_prob,
        scoring="sigmoid", router_bias=lp["e_score_correction_bias"],
        routed_scaling_factor=c.routed_scaling_factor)
    shared = swiglu(u, lp["shared"], c.dtype, tag + "moe.shared")
    return shared + routed.reshape(b, s, d), loads


# ---------------------------------------------------------------------------
# a layer on n streams, or on one plain residual stream
# ---------------------------------------------------------------------------

def hyper_connected(streams, p, cfg: XingConfig, sub_layer, tag: str = ""):
    """``X' = H_res X + H_post^T F(H_pre X)`` over ``streams [n, b, s,
    d]``; ``sub_layer(u)`` returns F(u), or (F(u), something more)."""
    c = cfg
    return hc.connect(
        streams, p, sub_layer, iters=c.hc_sinkhorn_iters, eps=c.hc_eps,
        clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max),
        norm_eps=c.rms_norm_eps, tag=tag)


def residual(x, sub_layer):
    """The plain path: ``x' = x + F(x)`` over ``x [b, s, d]``;
    ``sub_layer`` as ``hyper_connected``'s (it norms its input itself)."""
    out = sub_layer(x)
    y, more = out if isinstance(out, tuple) else (out, None)
    return x + y, more


def decoder_layer(carry, lp, cfg: XingConfig, positions, dense: bool,
                  tag: str = ""):
    """One layer: (the carry — streams ``[n, b, s, d]``, or ``x [b, s,
    d]`` on the plain path — and the pairs per held expert ``[count]``:
    zeros from a dense layer)."""
    c = cfg

    def attention(u):
        return latent_attention(
            rms_norm(u, lp["input_layernorm"], c.rms_norm_eps),
            lp["self_attn"], c, positions, tag)

    def feed_forward(u):
        u = rms_norm(u, lp["post_attention_layernorm"], c.rms_norm_eps)
        if dense:
            return swiglu(u, lp["mlp"], c.dtype, tag + "mlp.swiglu"), \
                jnp.zeros((c.experts_held[1],), jnp.int32)
        return expert_ffn(u, lp, c, tag)

    if not c.hc_mult:
        return residual(residual(carry, attention)[0], feed_forward)
    streams, _ = hyper_connected(carry, lp["hc_attn"], c, attention, tag)
    return hyper_connected(streams, lp["hc_mlp"], c, feed_forward, tag)


def _layer_fn(cfg: XingConfig, positions, dense: bool, tag: str = ""):
    def layer(carry, lp):
        return decoder_layer(carry, lp, cfg, positions, dense, tag)

    return checkpoint_layer(layer) if cfg.remat else layer


def _spread(x, cfg: XingConfig):
    """``x [b, s, d]`` copied into the n streams; itself on the plain
    path."""
    return jnp.broadcast_to(x[None], (cfg.hc_mult, *x.shape)) \
        if cfg.hc_mult else x


def _joined(carry, cfg: XingConfig):
    """The streams' sum ``[b, s, d]``; the one stream as it is."""
    if not cfg.hc_mult:
        return carry
    return jnp.sum(carry.astype(jnp.float32), axis=0).astype(cfg.dtype)


def record_plan(cfg: XingConfig) -> None:
    """One ``model.plan`` row in the set-up log a trace: the layers held
    by kind at their published indices, the residual path (``plain``, or
    ``hc`` with its streams), the prediction modules, the experts held of
    the router's width, the vocabulary rows held of the published."""
    c = cfg
    setup_event(
        "model.plan",
        dense_layers=[i for i, d in zip(c.layer_index, c.layers_dense) if d],
        expert_layers=[i for i, d in zip(c.layer_index, c.layers_dense)
                       if not d],
        residual="hc" if c.hc_mult else "plain", streams=int(c.hc_mult),
        prediction_modules=int(c.num_nextn_predict_layers),
        first_expert=int(c.experts_held[0]),
        experts_held=int(c.experts_held[1]), experts=int(c.n_routed_experts),
        vocab_rows=int(c.vocab_size),
        vocab_published=int(c.published_vocab_size or c.vocab_size))


def hidden_states(params, tokens, cfg: XingConfig):
    """``tokens [b, s]`` -> (the summed streams, or the one, ``[b, s, d]``
    before the final norm, pairs per held expert ``[expert layers,
    count]``)."""
    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(cfg.dtype)
    carry = _spread(x, cfg)
    record_plan(cfg)
    if cfg.hc_mult:
        hc.record_plan(carry, cfg.hc_sinkhorn_iters, 2 * (
            len(cfg.layer_index) + cfg.num_nextn_predict_layers))
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    loads = []
    for i, dense in enumerate(cfg.layers_dense):
        carry, n = _layer_fn(cfg, positions, dense)(
            carry, params[f"layer_{i}"])
        if not dense:
            loads.append(n)
    return _joined(carry, cfg), jnp.stack(loads) if loads else jnp.zeros(
        (0, cfg.experts_held[1]), jnp.int32)


def logits_of(params, x, gain, cfg: XingConfig, scope: str = "loss.head"):
    """A final norm with ``gain`` and the untied head: float32 logits."""
    with jax.named_scope(scope):
        x = rms_norm(x, gain, cfg.rms_norm_eps)
        return jnp.dot(x, params["lm_head"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)


def mtp_hidden(params, h, tokens, cfg: XingConfig):
    """The prediction module over the trunk's ``h [b, s, d]``: position i
    joins ``h_i`` with the embedding of token i + 1 (the last position, which
    has none, takes its own token: causal attention keeps it out of every
    position the loss reads) -> (``[b, s, d]`` before the module's norm,
    pairs per held expert ``[1, count]``)."""
    c, mp = cfg, params["mtp"]
    following = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    with jax.named_scope("mtp.block"):
        e = jnp.take(params["embed_tokens"], following, axis=0).astype(c.dtype)
        joined = jnp.concatenate([rms_norm(h, mp["hnorm"], c.rms_norm_eps),
                                  rms_norm(e, mp["enorm"], c.rms_norm_eps)],
                                 axis=-1)
        x = joined @ mp["eh_proj"].astype(c.dtype)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    carry, loads = _layer_fn(c, positions, False, "mtp.")(
        _spread(x, c), mp["layer"])
    with jax.named_scope("mtp.block"):
        return _joined(carry, c), loads[None]


def apply(params, tokens, cfg: XingConfig):
    """(float32 next-token logits ``[b, s, vocab]``, the module's logits
    for the token after next ``[b, s, vocab]`` or None, pairs per held
    expert ``[expert layers (+ 1), count]``)."""
    h, loads = hidden_states(params, tokens, cfg)
    main = logits_of(params, h, params["norm"], cfg)
    if not cfg.num_nextn_predict_layers:
        return main, None, loads
    x, more = mtp_hidden(params, h, tokens, cfg)
    return (main, logits_of(params, x, params["mtp"]["norm"], cfg, "loss.mtp"),
            jnp.concatenate([loads, more]))


def causal_lm_loss(params, batch, cfg: XingConfig):
    """``CE(main, t_{i+1}) + mtp_loss_weight * CE(mtp, t_{i+2})`` of
    ``batch["tokens"] [b, s]``, each a mean over its own positions."""
    tokens = batch["tokens"]
    main, mtp, _ = apply(params, tokens, cfg)
    with jax.named_scope("loss.head"):
        loss = next_token_loss(main, tokens)
    if mtp is None:
        return loss
    with jax.named_scope("loss.mtp"):
        # position i of the module predicts token i + 2
        return loss + cfg.mtp_loss_weight * next_token_loss(
            mtp[:, :-1], tokens[:, 1:])


def router_loads(params, batch, cfg: XingConfig):
    """Pairs per held expert in every expert layer, the prediction
    module's last, ``[expert layers (+ 1), count]`` for this batch (jit
    it; nothing of the training step computes it)."""
    return apply(params, batch["tokens"], cfg)[2]
