"""Plain reference for the ``solar`` family (Solar-Open2-250B,
``model_type`` ``solar_open2``).

Straightforward ``jax.numpy`` in float32 (``terms``, ``logits`` and
``router_loads`` wrap themselves in ``jax.default_matmul_precision(
"highest")``): no kernels, no chunked scan, no sorting, no grouped
products, no bf16. It reads the parameter pytree the system trains and a
configuration file's dictionary (``cfg``) under the source's key names;
from the package nothing is imported. One row at a time; a layer, and
inside it every block of queries, rows or tokens, is recomputed in the
backward pass, so that the float32 activations of 8,192 positions fit
beside the system's state. The equations (Kimi Linear's KDA
layer for the delta rule; each departure and inference in the
configuration's ``assumed``), with ``H`` the heads held:

- Layer i: ``h = x + operator_i(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``
  (RMSNorm with a gain at ``rms_norm_eps``); the operator is
  ``full_attention`` where the published index is in ``gqa_layers``, else
  the delta rule.
- ``full_attention``: ``q = a W_q [T, H, hd]``, ``k = a W_k``, ``v = a W_v
  [T, kv, hd]``, no rotary (``use_rope`` false) and no q/k norm; k and v
  repeated ``H / kv`` times; causal ``softmax(q k^T / sqrt(hd)) v``,
  queries in blocks; the heads' output times ``sigmoid(a W_g)``
  (``use_gqa_gate``); ``W_o``.
- The delta rule, a held head of ``dk = dv = head_dim``: ``q, k, v =
  silu(conv(a W_{q,k,v}))``, ``conv`` the causal depthwise convolution of
  ``short_conv_kernel_size`` taps (K shifted copies, zero before position
  0); q and k divided by ``sqrt(sum of squares + 1e-6)`` a head, q times
  ``dk^-1/2``; ``beta = 2 sigmoid(a W_b)`` (``kda_allow_neg_eigval``);
  ``g = -exp(A_log_h) softplus(a W_fa W_fb + dt_bias)``; then TOKEN BY
  TOKEN, from ``S = 0``: ``S <- Diag(exp g_t) S``, ``S <- S - beta_t k_t
  (k_t^T S) + beta_t k_t v_t^T``, ``o_t = S^T q_t``; ``out = [RMSNorm(o_h)
  * sigmoid(a W_ga W_gb + b_gb)_h]_h W_o``.
- MoE: ``s = sigmoid(a W_r)`` over all ``published_n_routed_experts``; C
  the 0/1 matrix of ``top_k(s)``; ``gate = s C / sum(s C) *
  routed_scaling_factor``; EVERY held expert is applied to every position
  and weighted by its gate, zero where it was not chosen; plus the shared
  SwiGLU. Experts and the shared expert are SwiGLUs ``down(silu(gate(a)) *
  up(a))``.
- After the last layer RMSNorm (``embedding_norm``) and the untied head
  ``lm_head``. ``loss = mean over rows and the first T - 1 positions of
  CE(logits_i, t_{i+1})``; ``terms`` returns (total, count) with count =
  rows x (T - 1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import lfm2
from chipbench.reference.lfm2 import in_chunks, rms_norm

Q_CHUNK = 128      # query positions whose scores exist at once
TOKEN_BLOCK = 64   # tokens of the recurrence recomputed together


def held(cfg: dict) -> tuple[int, int]:
    return int(cfg.get("first_expert", 0)), int(cfg["n_routed_experts"])


def layer_kinds(cfg: dict) -> list[str]:
    index = cfg.get("published_layer_index",
                    list(range(cfg["num_hidden_layers"])))
    return ["full_attention" if i in cfg["gqa_layers"] else "linear_attention"
            for i in index]


# -- the delta rule -------------------------------------------------------------

def causal_conv(x, taps):
    """x [T, w], taps [w, K]: sum_j taps[:, j] x_{t-(K-1)+j}."""
    steps, width = x.shape
    out = jnp.zeros_like(x)
    for j in range(taps.shape[1]):
        back = taps.shape[1] - 1 - j
        out = out + taps[:, j] * jnp.concatenate(
            [jnp.zeros((min(back, steps), width), x.dtype),
             x[:max(steps - back, 0)]])
    return out


def delta_rule_steps(q, k, v, g, beta):
    """One row, token by token: q, k, g [T, H, dk], v [T, H, dv], beta [T,
    H] -> o [T, H, dv], from a zero state. The tokens go in blocks, each
    recomputed in the backward pass; the last block is filled with tokens
    that change nothing (zero k, v, g and beta)."""
    steps, heads, dk = q.shape
    dv = v.shape[-1]
    pad = -steps % TOKEN_BLOCK

    def token(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[:, :, None] * s
        s = s - bt[:, None, None] * kt[:, :, None] * jnp.einsum(
            "hk,hkv->hv", kt, s)[:, None, :]
        s = s + bt[:, None, None] * kt[:, :, None] * vt[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    def blocks(a):
        a = jnp.concatenate([a, jnp.zeros((pad, *a.shape[1:]), a.dtype)])
        return a.reshape(-1, TOKEN_BLOCK, *a.shape[1:])

    _, o = jax.lax.scan(
        jax.checkpoint(lambda s, xs: jax.lax.scan(token, s, xs)),
        jnp.zeros((heads, dk, dv), jnp.float32),
        tuple(map(blocks, (q, k, v, g, beta))))
    return o.reshape(-1, heads, dv)[:steps]


def delta_rule(a, p, cfg):
    """One row: a [T, hidden] -> [T, hidden]."""
    lin = cfg["linear_attn_config"]
    heads, hd, steps = lin["num_heads"], lin["head_dim"], a.shape[0]

    def qkv(name):
        return jax.nn.silu(causal_conv(a @ p[f"{name}_proj"],
                                       p[f"{name}_conv"])
                           ).reshape(steps, heads, hd)

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q, k, v = l2(qkv("q")) * hd ** -0.5, l2(qkv("k")), qkv("v")
    beta = jax.nn.sigmoid(a @ p["b_proj"])
    if cfg.get("kda_allow_neg_eigval", False):
        beta = 2 * beta
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        (a @ p["f_a_proj"] @ p["f_b_proj"] + p["dt_bias"]).reshape(
            steps, heads, hd))
    o = delta_rule_steps(q, k, v, g, beta)
    gate = jax.nn.sigmoid(a @ p["g_a_proj"] @ p["g_b_proj"] + p["g_b_bias"])
    o = rms_norm(o, p["o_norm"], cfg["rms_norm_eps"]) * gate.reshape(
        steps, heads, hd)
    return o.reshape(steps, heads * hd) @ p["o_proj"]


# -- attention ---------------------------------------------------------------------

def attention(a, p, cfg):
    """One row: a [T, hidden] -> [T, hidden], causal, no position
    encoding."""
    if cfg.get("use_rope", True):
        raise ValueError("the reference computes the NoPE layer only")
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    s = a.shape[0]
    ids = jnp.arange(s)
    q = (a @ p["q_proj"]).reshape(s, heads, hd)
    k = jnp.repeat((a @ p["k_proj"]).reshape(s, kv, hd), heads // kv, axis=1)
    v = jnp.repeat((a @ p["v_proj"]).reshape(s, kv, hd), heads // kv, axis=1)

    def some_queries(qc, q_ids):
        scores = jnp.einsum("qhd,khd->hqk", qc, k) / hd ** 0.5
        ok = ids[None, :] <= q_ids[:, None]
        prob = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", prob, v)

    out = in_chunks(some_queries, Q_CHUNK, q, ids).reshape(s, heads * hd)
    if cfg.get("use_gqa_gate", False):
        out = out * jax.nn.sigmoid(a @ p["g_proj"])
    return out @ p["o_proj"]


# -- the expert layer ---------------------------------------------------------------

def router(b, lp, cfg):
    """(gate of every expert at every position [P, published experts]: the
    chosen experts' scores, normalised and scaled, zero elsewhere; the 0/1
    choice)."""
    scores = jax.nn.sigmoid(b @ lp["router"])
    _, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    choice = jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], chosen].set(1.0)
    gates = scores * choice
    if cfg.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * cfg.get("routed_scaling_factor", 1.0), choice


def expert_layer(b, lp, cfg):
    """b [P, hidden] -> (the shared expert plus this share's routed part,
    pairs per held expert [count]): one routed expert at a time, a scan
    over the held experts' stacked matrices."""
    first, count = held(cfg)
    gates, choice = router(b, lp, cfg)
    y, _ = jax.lax.scan(
        jax.checkpoint(lambda y, e: (y + e[1][:, None] * lfm2.swiglu(b, e[0]),
                                     None)),
        lfm2.swiglu(b, lp["shared"]),
        (lp["experts"], gates[:, first:first + count].T))
    return y, jnp.sum(choice[:, first:first + count], axis=0).astype(jnp.int32)


# -- layers, rows ---------------------------------------------------------------------

def layer(x, lp, cfg, kind):
    """x [T, d] -> (x', pairs per held expert)."""
    eps = cfg["rms_norm_eps"]
    a = rms_norm(x, lp["operator_norm"], eps)
    x = x + (attention(a, lp["self_attn"], cfg) if kind == "full_attention"
             else delta_rule(a, lp["linear_attn"], cfg))
    y, loads = expert_layer(rms_norm(x, lp["ffn_norm"], eps), lp, cfg)
    return x + y, loads


def hidden_row(p, tokens, cfg):
    """One row -> (hidden [T, d] before the final norm, loads [layers,
    count]); each layer is recomputed in the backward pass."""
    x = p["embed_tokens"][tokens]
    loads = []
    for i, kind in enumerate(layer_kinds(cfg)):
        x, n = jax.checkpoint(
            lambda x, lp, kind=kind: layer(x, lp, cfg, kind))(
            x, p[f"layer_{i}"])
        loads.append(n)
    return x, loads


def head_logits(p, x, cfg):
    return rms_norm(x, p["embedding_norm"], cfg["rms_norm_eps"]) @ p["lm_head"]


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
                some_rows, lfm2.ROW_CHUNK, x, jnp.roll(tokens, -1),
                (jnp.arange(s) < s - 1).astype(jnp.float32)))
        rows, s = batch["tokens"].shape
        return total, jnp.float32(rows * (s - 1))


def router_loads(params, batch, cfg):
    """Pairs per held expert [layers, count], summed over the rows."""
    with jax.default_matmul_precision("highest"):
        p = _float32(params)
        return sum(jnp.stack(hidden_row(p, tokens, cfg)[1])
                   for tokens in batch["tokens"])
