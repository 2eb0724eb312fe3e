"""Plain reference for the ``lfm2`` family (LFM2-24B-A2B, ``model_type``
``lfm2_moe``).

Straightforward ``jax.numpy`` in float32 (``terms``, ``logits`` and
``router_loads`` wrap themselves in ``jax.default_matmul_precision(
"highest")``): no kernels, no sorting, no grouped products, no bf16. It
reads the parameter pytree the system trains and a configuration file's
dictionary (``cfg``) under the source's key names; nothing is imported
from the package. One row at a time; a layer, and inside it every chunk of
queries or rows, is recomputed in the backward pass, so that the float32
activations of 8,192 positions fit beside the system's state. The
equations (ISSUE 37; the ``lfm2`` / ``lfm2_moe`` modelling code of the
``transformers`` library; each departure in the configuration's
``assumed``):

- Layer i: ``h = x + operator_i(RMSNorm(x))``, ``y = h + ffn_i(RMSNorm(
  h))``; the operator is ``layer_types[published index]``, the
  feed-forward a SwiGLU of ``intermediate_size`` where the published index
  is under ``num_dense_layers``, else the expert layer. RMSNorm with a
  gain at ``norm_eps``.
- ``conv``: ``[B | C | u] = a W_in``; ``g = B * u``; ``c_t = sum_j w_j
  g_{t-(K-1)+j}`` over the ``K = conv_L_cache`` taps of each channel, ``g``
  zero before position 0 (the source: a depthwise ``Conv1d`` with padding
  ``K - 1``, its output cut to the row's length); ``out = (C * c) W_out``.
  Written as K shifted copies of ``g``, each a concatenation of zeros and
  a slice.
- ``full_attention``: q ``[T, heads, hd]``, k and v ``[T, kv_heads, hd]``
  with ``hd = hidden / heads``; q and k through a per-head RMSNorm (gain
  of ``hd``), then rotary (rotate-half) over the whole head at
  ``rope_theta``; k and v repeated ``heads / kv_heads`` times; causal
  ``softmax(q k^T / sqrt(hd)) v``, queries in chunks; ``W_o``.
- Expert layer: ``s = sigmoid(a W_r)`` over all ``published_num_experts``;
  C the 0/1 matrix of ``top_k(s + expert_bias)``; ``g = s C / sum(s C) *
  routed_scaling_factor`` (no epsilon in the sum, as the program); EVERY
  held expert is applied to every position and weighted by its gate, zero
  where it was not chosen; no shared expert. The bias takes no gradient.
- After the last layer RMSNorm (``embedding_norm``) and the tied head:
  ``logits = x E^T``. ``loss = mean over rows and the first T - 1
  positions of CE(logits_i, t_{i+1})``; ``terms`` returns (total, count)
  with count = rows x (T - 1), so that total / count is the loss and
  totals and counts add over rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_CHUNK = 128    # query positions whose scores exist at once
ROW_CHUNK = 512  # positions whose feed-forward or logits exist at once


def held(cfg: dict) -> tuple[int, int]:
    return int(cfg.get("first_expert", 0)), int(cfg["num_experts"])


def layer_kinds(cfg: dict) -> list[tuple[str, bool]]:
    """(operator, the feed-forward is dense) of each held layer."""
    index = cfg.get("published_layer_index",
                    list(range(cfg["num_hidden_layers"])))
    return [(cfg["layer_types"][i], i < cfg.get("num_dense_layers", 0))
            for i in index]


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def in_chunks(fn, size: int, *arrays):
    """``fn`` over row chunks of ``arrays``, each chunk recomputed in the
    backward pass; the results side by side. Rows that no chunk size
    divides go as one chunk."""
    rows = arrays[0].shape[0]
    size = min(size, rows)
    if rows % size:
        size = rows
    parts = [a.reshape(rows // size, size, *a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda xs: jax.checkpoint(fn)(*xs), tuple(parts))
    return jax.tree.map(lambda o: o.reshape(rows, *o.shape[2:]), out)


def rotary(x, positions, theta):
    """x [T, heads, hd], rotate-half."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


# -- the two operators ----------------------------------------------------------

def short_conv(a, p):
    """One row: a [T, d] -> [T, d]."""
    d = a.shape[1]
    bcu = a @ p["in_proj"]
    b_gate, c_gate, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    g = b_gate * u
    steps, taps = g.shape[0], p["conv"].shape[1]
    c = jnp.zeros_like(g)
    for j in range(taps):
        back = taps - 1 - j          # tap j reads the position ``back`` before
        shifted = jnp.concatenate(
            [jnp.zeros((min(back, steps), d), g.dtype), g[:max(steps - back, 0)]])
        c = c + p["conv"][:, j] * shifted
    return (c_gate * c) @ p["out_proj"]


def attention(a, p, cfg):
    """One row: a [T, hidden] -> [T, hidden], causal."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps, s = cfg["hidden_size"] // heads, cfg["norm_eps"], a.shape[0]
    theta = cfg.get("rope_parameters", cfg)["rope_theta"]
    ids = jnp.arange(s)
    q = (a @ p["q_proj"]).reshape(s, heads, hd)
    k = (a @ p["k_proj"]).reshape(s, kv, hd)
    v = (a @ p["v_proj"]).reshape(s, kv, hd)
    q = rotary(rms_norm(q, p["q_norm"], eps), ids, theta)
    k = rotary(rms_norm(k, p["k_norm"], eps), ids, theta)
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)

    def some_queries(qc, q_ids):
        scores = jnp.einsum("qhd,khd->hqk", qc, k) / hd ** 0.5
        ok = ids[None, :] <= q_ids[:, None]
        prob = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", prob, v)

    out = in_chunks(some_queries, Q_CHUNK, q, ids)
    return out.reshape(s, heads * hd) @ p["o_proj"]


# -- the two feed-forwards --------------------------------------------------------

def swiglu(b, p):
    return in_chunks(lambda c: (jax.nn.silu(c @ p["gate_proj"])
                                * (c @ p["up_proj"])) @ p["down_proj"],
                     ROW_CHUNK, b)


def router(b, lp, cfg):
    """(gate of every expert at every position [P, published experts]: the
    chosen experts' unbiased scores, normalised and scaled, zero elsewhere;
    the 0/1 choice)."""
    scores = jax.nn.sigmoid(b @ lp["router"])
    steer = scores
    if "expert_bias" in lp:
        steer = scores + jax.lax.stop_gradient(lp["expert_bias"])
    _, chosen = jax.lax.top_k(steer, cfg["num_experts_per_tok"])
    choice = jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], chosen].set(1.0)
    gates = scores * choice
    if cfg.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * cfg.get("routed_scaling_factor", 1.0), choice


def expert_layer(b, lp, cfg):
    """b [P, hidden] -> (this share's routed part, pairs per held expert
    [count]): one expert at a time, a scan over the held experts' stacked
    matrices."""
    first, count = held(cfg)
    gates, choice = router(b, lp, cfg)
    y, _ = jax.lax.scan(
        jax.checkpoint(lambda y, e: (y + e[1][:, None] * swiglu(b, e[0]),
                                     None)),
        jnp.zeros_like(b), (lp["experts"], gates[:, first:first + count].T))
    return y, jnp.sum(choice[:, first:first + count], axis=0).astype(jnp.int32)


# -- layers, rows -------------------------------------------------------------------

def layer(x, lp, cfg, kind, dense):
    """x [T, d] -> (x', pairs per held expert or None)."""
    eps = cfg["norm_eps"]
    a = rms_norm(x, lp["operator_norm"], eps)
    x = x + (short_conv(a, lp["conv"]) if kind == "conv"
             else attention(a, lp["self_attn"], cfg))
    b = rms_norm(x, lp["ffn_norm"], eps)
    if dense:
        return x + swiglu(b, lp["feed_forward"]), None
    y, loads = expert_layer(b, lp, cfg)
    return x + y, loads


def hidden_row(p, tokens, cfg):
    """One row -> (hidden [T, d] before the final norm, loads [expert
    layers, count]); each layer is recomputed in the backward pass."""
    x = p["embed_tokens"][tokens]
    loads = []
    for i, (kind, dense) in enumerate(layer_kinds(cfg)):
        x, n = jax.checkpoint(
            lambda x, lp, kind=kind, dense=dense: layer(x, lp, cfg, kind,
                                                        dense))(
            x, p[f"layer_{i}"])
        if not dense:
            loads.append(n)
    return x, loads


def head_logits(p, x, cfg):
    return rms_norm(x, p["embedding_norm"], cfg["norm_eps"]) \
        @ p["embed_tokens"].T


def _float32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


def logits(params, batch, cfg):
    """Next-token logits [rows, T, vocab]."""
    with jax.default_matmul_precision("highest"):
        p = _float32(params)
        return jnp.stack([head_logits(p, hidden_row(p, tokens, cfg)[0], cfg)
                          for tokens in batch["tokens"]])


def terms(params, batch, cfg):
    """(total, count): total / count is the loss, and both add over rows."""
    with jax.default_matmul_precision("highest"):
        p = _float32(params)

        def some_rows(xc, tc, wc):
            logp = jax.nn.log_softmax(head_logits(p, xc, cfg), axis=-1)
            return -wc * jnp.take_along_axis(logp, tc[:, None], axis=-1)[:, 0]

        total = jnp.float32(0)
        for tokens in batch["tokens"]:
            s = tokens.shape[0]
            x, _ = hidden_row(p, tokens, cfg)
            total = total + jnp.sum(in_chunks(
                some_rows, ROW_CHUNK, x, jnp.roll(tokens, -1),
                (jnp.arange(s) < s - 1).astype(jnp.float32)))
        rows, s = batch["tokens"].shape
        return total, jnp.float32(rows * (s - 1))


def router_loads(params, batch, cfg):
    """Pairs per held expert [expert layers, count], summed over the
    rows."""
    with jax.default_matmul_precision("highest"):
        p = _float32(params)
        return sum(jnp.stack(hidden_row(p, tokens, cfg)[1])
                   for tokens in batch["tokens"])
