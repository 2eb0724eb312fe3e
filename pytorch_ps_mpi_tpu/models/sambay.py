"""Config-driven decoder of the ``sambay`` family (Phi-4-mini-flash-
reasoning, ``model_type`` ``phi4flash``; the SambaY architecture of
arXiv:2507.06607): a self-decoder of Mamba and sliding-window layers, one
full-attention layer, and a cross-decoder whose layers have no keys,
values or scan of their own. Read from the source ``config.json``'s key
names plus a ``layer_types`` list; one function a mechanism.

With T positions, d = ``hidden_size``, LN = LayerNorm with gain and bias,
every layer is ``h = x + Mixer(LN1(x))``, ``x' = h + MLP(LN2(h))``,
``MLP(u) = W_down(silu(g) * v)``, ``[g ; v] = W_gate_up u``. Tokens enter
through the embedding (no positional encoding of any kind), leave
through a final LN and the embedding's transpose. The mixer by the
layer's kind (``LAYER_KINDS``):

- ``mamba``: ``[a ; z] = W_in u``; ``xh = silu(conv(a))`` (causal
  depth-wise, width 4, bias); ``[dl ; B ; C] = W_x xh``; ``Dt =
  softplus(W_dt dl + b_dt)``; ``A = -exp(A_log)``; the selective scan
  ``s_t = exp(Dt_t A) s_{t-1} + (Dt_t xh_t) B_t^T``, ``y_t = s_t C_t + D
  xh_t`` (``ops/selective_scan.py``, float32); ``out = W_out(y *
  silu(z))``.
- ``mamba_memory``: the same, and its ``y`` (before the gate) is the
  MEMORY every ``gmu`` layer reads.
- ``gmu`` (gated memory unit): ``out = W_out(m * silu(W_in u))``.
- ``sliding_attention`` / ``full_attention`` / ``cross_attention``:
  differential attention. ``q = W_q u + b_q`` (heads of ``head_dim``),
  ``k``, ``v`` from ``W_kv u + b_kv`` (half as many heads each) — except
  in ``cross_attention``, which has no ``W_kv`` and reads the k and v the
  ``full_attention`` layer computed. Adjacent heads pair: query pairs
  ``(q1, q2)``, key pairs ``(k1, k2)``, value pairs joined ``V = [v1 ;
  v2]``; query pair p reads key-value pair ``p // 2``. ``P_i =
  softmax(q_i k_i^T / sqrt(head_dim) + mask)``; ``o = RMSNorm(P_1 V - lam
  P_2 V) * (1 - lam0)``, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3 l)`` with l the layer's PUBLISHED
  index; ``out = W_o concat(o) + b_o``. Causal everywhere;
  ``sliding_attention`` also needs ``q_pos - k_pos < sliding_window``.
  Each softmax map is computed ONCE, by one call of the flash kernels a
  layer: the q heads ordered ``[q1 of every pair, q2 of every pair]``
  over the k heads ``[k1.., k2..]`` and the joined values twice
  (``[V.., V..]``), so that query head g reads key-value head ``g // 2``.

Two tensors are handed ACROSS layers — the memory and the shared keys and
values — so their gradients are sums over all readers; with ``remat``
each layer is a checkpoint with the two as explicit inputs, which keeps
the flash kernels' output and per-row logsumexp
(``ops/_common.checkpoint_layer``) and recomputes the rest: the scans,
the projections and the MLPs run forward twice, the kernels once.

Parameters are a plain pytree (float32); the compute dtype is
``cfg.dtype``; norms, ``Dt``, the scan, lambda, the softmaxes and the
logits are float32. Named scopes for a device trace: ``ssm.proj``,
``ssm.scan``, ``gmu.mix``, ``attn.diff``, ``mlp.swiglu``, ``loss.head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.models.gpt import causal_lm_loss as next_token_loss
from pytorch_ps_mpi_tpu.ops._common import checkpoint_layer
from pytorch_ps_mpi_tpu.ops.selective_scan import selective_scan

ATTENTION_KINDS = ("sliding_attention", "full_attention", "cross_attention")
LAYER_KINDS = ("mamba", "mamba_memory", "gmu") + ATTENTION_KINDS


def published_layer_types(layers: int) -> Tuple[str, ...]:
    """The layout of the family's modelling code for ``layers`` layers:
    Mamba (even) and sliding-window (odd) layers up to the middle, the
    memory layer at ``layers / 2``, the full-attention layer after it,
    then gated memory units (even) and cross-attention (odd)."""
    half = layers // 2
    return tuple(
        ("mamba" if l % 2 == 0 else "sliding_attention") if l < half
        else "mamba_memory" if l == half
        else "full_attention" if l == half + 1
        else ("gmu" if l % 2 == 0 else "cross_attention")
        for l in range(layers))


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    sliding_window: int
    layer_types: Tuple[str, ...]
    layer_index: Tuple[int, ...]       # each layer's published index
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    dtype: Any = jnp.float32
    attention: str = "full"            # 'full' | 'flash' | 'einsum' (bert.py)
    remat: bool = False                # checkpoint_layer around each layer

    def __post_init__(self):
        kinds = self.layer_types
        if set(kinds) - set(LAYER_KINDS) or len(kinds) != len(self.layer_index):
            raise ValueError(f"layer_types {kinds} / layer_index "
                             f"{self.layer_index}")
        for reader, source in (("gmu", "mamba_memory"),
                               ("cross_attention", "full_attention")):
            if reader in kinds and (source not in kinds or kinds.index(source)
                                    > kinds.index(reader)):
                raise ValueError(f"a {reader} layer needs a {source} layer "
                                 "before it")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @staticmethod
    def from_source(config: dict) -> "SambaYConfig":
        """From a configuration file under the source's key names, with
        ``layer_types`` (default: the published layout for
        ``num_hidden_layers``) and ``published_layer_index``."""
        fields = {f.name for f in dataclasses.fields(SambaYConfig)}
        kw = {k: v for k, v in config.items() if k in fields}
        kinds = tuple(config.get("layer_types") or published_layer_types(
            config["num_hidden_layers"]))
        if len(kinds) != config["num_hidden_layers"]:
            raise ValueError(f"{len(kinds)} layer_types for "
                             f"{config['num_hidden_layers']} layers")
        kw.update(
            layer_types=kinds,
            layer_index=tuple(config.get("published_layer_index",
                                         range(len(kinds)))),
            dtype=jnp.dtype(config.get("dtype", "float32")).type)
        return SambaYConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "SambaYConfig":
        defaults = dict(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            num_attention_heads=4, num_key_value_heads=2, sliding_window=8,
            layer_types=published_layer_types(4) + ("gmu", "cross_attention"),
            layer_index=(0, 1, 2, 3, 4, 5), mamba_d_state=4, mamba_dt_rank=4)
        defaults.update(kw)
        return SambaYConfig(**defaults)


def init(key, cfg: SambaYConfig, scale: float = 0.02):
    """Seeded float32 parameters: normal(0, ``scale``) matrices and
    embedding rows (the head is the embedding's transpose: rows at unit
    variance would make logits of standard deviation sqrt(d)), unit
    gains, zero biases; Mamba's own: ``A_log = log(1..N)``, ``D = 1``,
    ``b_dt`` the inverse softplus of dt ~ logU[1e-3, 1e-1], the
    convolution uniform in +-1/sqrt(width) (``nn.Conv1d``'s default);
    the four lambda vectors normal(0, 0.1) (the differential
    transformer's)."""
    c = cfg
    d, f, e, hd = c.hidden_size, c.intermediate_size, c.d_inner, c.head_dim
    n, r, kv = c.mamba_d_state, c.mamba_dt_rank, c.num_key_value_heads

    def normal(k, *shape, std=scale):
        return std * jax.random.normal(k, shape, jnp.float32)

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    def mamba(k):
        k = jax.random.split(k, 6)
        dt = jnp.exp(jax.random.uniform(k[4], (e,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        bound = c.mamba_d_conv ** -0.5
        return {"in_proj": normal(k[0], d, 2 * e),
                "conv1d_weight": jax.random.uniform(
                    k[5], (c.mamba_d_conv, e), jnp.float32, -bound, bound),
                "conv1d_bias": jnp.zeros((e,), jnp.float32),
                "x_proj": normal(k[1], e, r + 2 * n),
                "dt_proj": normal(k[2], r, e),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, n + 1, dtype=jnp.float32), (e, n))),
                "D": jnp.ones((e,), jnp.float32),
                "out_proj": normal(k[3], e, d)}

    def attention(k, own_kv):
        k = jax.random.split(k, 7)
        p = {"q_proj": normal(k[0], d, d), "q_bias": jnp.zeros((d,)),
             "o_proj": normal(k[2], d, d), "o_bias": jnp.zeros((d,)),
             "subln": jnp.ones((2 * hd,), jnp.float32)}
        for i, name in enumerate(("lambda_q1", "lambda_k1", "lambda_q2",
                                  "lambda_k2")):
            p[name] = normal(k[3 + i], hd, std=0.1)
        if own_kv:
            p["kv_proj"] = normal(k[1], d, 2 * kv * hd)
            p["kv_bias"] = jnp.zeros((2 * kv * hd,))
        return p

    keys = jax.random.split(key, len(c.layer_types) + 1)
    params = {"embed_tokens": normal(keys[0], c.vocab_size, d),
              "final_layernorm": norm()}
    for i, kind in enumerate(c.layer_types):
        k_mix, k_up, k_down = jax.random.split(keys[i + 1], 3)
        if kind in ATTENTION_KINDS:
            mixer = attention(k_mix, kind != "cross_attention")
        elif kind == "gmu":
            k_in, k_out = jax.random.split(k_mix)
            mixer = {"in_proj": normal(k_in, d, e),
                     "out_proj": normal(k_out, e, d)}
        else:
            mixer = mamba(k_mix)
        params[f"layer_{i}"] = {
            "input_layernorm": norm(), "mixer": mixer,
            "post_attention_layernorm": norm(),
            "mlp": {"gate_up_proj": normal(k_up, d, 2 * f),
                    "down_proj": normal(k_down, f, d)}}
    return params


def param_count(cfg: SambaYConfig) -> int:
    """Parameters of ``init(key, cfg)``, from its shapes alone."""
    shapes = jax.eval_shape(lambda key: init(key, cfg), jax.random.key(0))
    return sum(a.size for a in jax.tree.leaves(shapes))


def layer_norm(x, p, eps: float):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * p["scale"]
            + p["bias"]).astype(x.dtype)


def _dot(x, w, dtype, out=None):
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=out or dtype)


def mlp(u, p, cfg: SambaYConfig):
    with jax.named_scope("mlp.swiglu"):
        g, v = jnp.split(_dot(u, p["gate_up_proj"], cfg.dtype), 2, axis=-1)
        return _dot(jax.nn.silu(g) * v, p["down_proj"], cfg.dtype)


def mamba_mixer(u, p, cfg: SambaYConfig):
    """``u [b, T, d]`` -> (out ``[b, T, d]``, ``y [b, T, E]`` float32
    before the gate)."""
    c = cfg
    n, r, width = c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv
    with jax.named_scope("ssm.proj"):
        a, z = jnp.split(_dot(u, p["in_proj"], c.dtype), 2, axis=-1)
        steps = a.shape[1]
        padded = jnp.pad(a.astype(jnp.float32),
                         ((0, 0), (width - 1, 0), (0, 0)))
        conv = sum(padded[:, j:j + steps] * p["conv1d_weight"][j]
                   for j in range(width)) + p["conv1d_bias"]
        xh = jax.nn.silu(conv).astype(c.dtype)
        dl, b_in, c_in = jnp.split(
            _dot(xh, p["x_proj"], c.dtype, jnp.float32), [r, r + n], axis=-1)
        dt = jax.nn.softplus(_dot(dl, p["dt_proj"], c.dtype, jnp.float32)
                             + p["dt_bias"])
    with jax.named_scope("ssm.scan"):
        y = selective_scan(xh, dt, -jnp.exp(p["A_log"]), b_in, c_in, p["D"])
    with jax.named_scope("ssm.proj"):
        out = _dot(y * jax.nn.silu(z.astype(jnp.float32)), p["out_proj"],
                   c.dtype)
    return out, y


def gmu_mixer(u, p, memory, cfg: SambaYConfig):
    with jax.named_scope("gmu.mix"):
        gate = jax.nn.silu(_dot(u, p["in_proj"], cfg.dtype, jnp.float32))
        return _dot(memory.astype(jnp.float32) * gate, p["out_proj"],
                    cfg.dtype)


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def keys_values(u, p, cfg: SambaYConfig):
    """(k ``[b, T, kv, head_dim]`` ordered ``[k1 of every pair, k2 of
    every pair]``, v ``[b, T, kv, 2 head_dim]``: the joined values,
    twice)."""
    c = cfg
    b, s, _ = u.shape
    kv, hd = c.num_key_value_heads, c.head_dim
    k, v = jnp.split(_dot(u, p["kv_proj"], c.dtype)
                     + p["kv_bias"].astype(c.dtype), 2, axis=-1)
    k = k.reshape(b, s, kv // 2, 2, hd)
    v = v.reshape(b, s, kv // 2, 2 * hd)
    return (jnp.concatenate([k[:, :, :, 0], k[:, :, :, 1]], axis=2),
            jnp.concatenate([v, v], axis=2))


def diff_attention(u, p, kv, cfg: SambaYConfig, kind: str, index: int):
    """Differential attention of ``u [b, T, d]`` over ``kv`` (this
    layer's own, or the full-attention layer's)."""
    from pytorch_ps_mpi_tpu.ops import attention_pallas as ap

    c = cfg
    b, s, d = u.shape
    heads, hd = c.num_attention_heads, c.head_dim
    if c.attention not in ("full", "flash", "einsum"):
        raise ValueError(f"unknown attention={c.attention!r}")
    q = (_dot(u, p["q_proj"], c.dtype) + p["q_bias"].astype(c.dtype)
         ).reshape(b, s, heads // 2, 2, hd)
    q = jnp.concatenate([q[:, :, :, 0], q[:, :, :, 1]], axis=2)
    k, v = kv
    window = c.sliding_window if kind == "sliding_attention" else None
    mask = "window" if window else "causal"
    kernel = c.attention == "flash" or (
        c.attention == "full" and ap.flash_auto_ok(s, s, c.dtype))
    with jax.named_scope("attn.diff"):
        if kernel:
            out = ap.flash_attention(q, k, v, mask=mask, window=window)
        else:
            out, _ = ap._attention_jnp(
                q, k, v, 0, 0, ap._mask_spec(False, mask, None, None, window),
                hd ** -0.5)
        lam0 = lambda_init(index)
        lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
               - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)
        o = out.astype(jnp.float32)
        o = o[:, :, :heads // 2] - lam * o[:, :, heads // 2:]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + c.layer_norm_eps) * p["subln"] * (1.0 - lam0)
    return (_dot(o.reshape(b, s, d), p["o_proj"], c.dtype)
            + p["o_bias"].astype(c.dtype))


def decoder_layer(kind: str, index: int, cfg: SambaYConfig, x, lp, memory, kv):
    """One layer of ``kind`` at published index ``index``: (x, memory,
    kv), the two hand-overs passed on or made here."""
    u = layer_norm(x, lp["input_layernorm"], cfg.layer_norm_eps)
    if kind in ("mamba", "mamba_memory"):
        out, y = mamba_mixer(u, lp["mixer"], cfg)
        if kind == "mamba_memory":
            memory = y.astype(cfg.dtype)
    elif kind == "gmu":
        out = gmu_mixer(u, lp["mixer"], memory, cfg)
    else:
        own = keys_values(u, lp["mixer"], cfg) \
            if kind != "cross_attention" else kv
        out = diff_attention(u, lp["mixer"], own, cfg, kind, index)
        if kind == "full_attention":
            kv = own
    x = x + out
    x = x + mlp(layer_norm(x, lp["post_attention_layernorm"],
                           cfg.layer_norm_eps), lp["mlp"], cfg)
    return x, memory, kv


def hidden_states(params, tokens, cfg: SambaYConfig):
    """``tokens [b, T]`` -> hidden ``[b, T, d]`` before the final norm."""
    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(cfg.dtype)
    memory = kv = None
    layer = decoder_layer
    if cfg.remat:
        layer = checkpoint_layer(decoder_layer, static_argnums=(0, 1, 2))
    for i, (kind, index) in enumerate(zip(cfg.layer_types, cfg.layer_index)):
        x, memory, kv = layer(kind, index, cfg, x, params[f"layer_{i}"],
                              memory, kv)
    return x


def logits_of(params, x, cfg: SambaYConfig):
    """Final norm and the tied head: float32 logits over the vocabulary
    rows ``params["embed_tokens"]`` holds."""
    with jax.named_scope("loss.head"):
        x = layer_norm(x, params["final_layernorm"], cfg.layer_norm_eps)
        return _dot(x, params["embed_tokens"].T, cfg.dtype, jnp.float32)


def apply(params, tokens, cfg: SambaYConfig):
    """Float32 logits ``[b, T, vocab]``."""
    return logits_of(params, hidden_states(params, tokens, cfg), cfg)


def causal_lm_loss(params, batch, cfg: SambaYConfig):
    """Next-token cross-entropy of ``batch["tokens"] [b, T]``."""
    logits = apply(params, batch["tokens"], cfg)
    with jax.named_scope("loss.head"):
        return next_token_loss(logits, batch["tokens"])
