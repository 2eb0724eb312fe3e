"""Plain reference for the ``xing`` family (Xing4.0-29B-A4B, ``model_type``
``xing4_0``).

Straightforward ``jax.numpy`` in float32 (``terms``, ``logits`` and
``router_loads`` wrap themselves in ``jax.default_matmul_precision(
"highest")``): no kernels, no sorting, no grouped products, no bf16. It
reads the parameter pytree the system trains, by the source's names, and a
configuration file's dictionary (``cfg``) under the source's key names;
nothing is imported from the package. One row at a time, the streams as
``[s, n, d]`` (token-major: not the program's layout). The equations
(ISSUE 33, and each departure in the configuration's ``assumed``):

- Hyper-connection around a sub-layer F, per position, X in R^{n x d}:
  ``x~ = vec(X) / sqrt(mean(vec(X)^2) + eps)``; ``H_pre = sigmoid(a_pre
  x~ W_pre + b_pre)``, ``H_post = 2 sigmoid(a_post x~ W_post + b_post)``,
  ``H_res = Sinkhorn(exp(clamp(a_res mat(x~ W_res) + b_res)))``: a Python
  loop of ``hc_sinkhorn_iters`` row-then-column normalisations with
  ``hc_eps`` in the denominators; ``X' = H_res X + H_post^T F(RMSNorm(
  H_pre X))``. The embedding is copied into the n streams; the streams
  are summed before a final norm.
- Latent attention: ``c_q = RMSNorm(a W_qa)``, ``q = c_q W_qb``; ``[c_kv
  | k_rope] = a W_kva``; ``[k_nope | v] = RMSNorm(c_kv) W_kvb``; rotary
  (rotate-half; the source family's interleaved pairs are a permutation
  of the projections' columns, which seeded random weights do not tell
  apart) at YaRN's frequencies on ``q_rope`` and the one ``k_rope``;
  scores as TWO products, ``q_nope k_nope^T + q_rope k_rope^T``, times
  ``(nope + rope)^-0.5 m^2``; causal; queries in chunks.
- Feed-forward: a layer whose published index is under
  ``first_k_dense_replace`` is a SwiGLU; any other ``shared(b) + sum over
  held e of g_e expert_e(b)``: ``s = sigmoid(b W_r)``, C the 0/1 matrix of
  ``top_k(s + e_bias)``, ``g = s C / sum(s C) * routed_scaling_factor``;
  EVERY held expert is applied to every position and weighted by its
  gate, zero where it was not chosen. The bias takes no gradient.
- MTP module: ``h'_i = [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] W_eh``, one
  expert layer on n copies of ``h'``, its own final norm, the shared head;
  the last position (no t_{i+1}) takes its own token and is in no loss.
- ``loss = mean_i CE(main_i, t_{i+1}) + mtp_loss_weight * mean_i CE(mtp_i,
  t_{i+2})``; ``terms`` returns (total, count) with count = rows x (s - 1)
  and the second mean rescaled into the total, so that total / count is
  the loss and totals and counts add over rows.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_CHUNK = 128    # query positions whose scores exist at once
ROW_CHUNK = 512  # positions whose feed-forward, mixes or logits exist at once
HEAD_CHUNK = 8   # heads whose q, k and v exist at once


def held(cfg: dict) -> tuple[int, int]:
    return int(cfg.get("first_expert", 0)), int(cfg["n_routed_experts"])


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def in_chunks(fn, size: int, *arrays):
    """``fn`` over row chunks of ``arrays``, each chunk recomputed in the
    backward pass; the results (an array or a tuple of them) side by side."""
    rows = arrays[0].shape[0]
    size = min(size, rows)
    assert rows % size == 0, (rows, size)
    parts = [a.reshape(rows // size, size, *a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda xs: jax.checkpoint(fn)(*xs), tuple(parts))
    return jax.tree.map(lambda o: o.reshape(rows, *o.shape[2:]), out)


# -- rotary embedding at YaRN's frequencies -----------------------------------

def yarn(cfg):
    """(frequencies of the rotary pairs, factor on cos and sin, factor on
    the softmax scale)."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    extrapolated = 1.0 / base ** exponent
    scaling = cfg.get("rope_scaling")
    if not scaling:
        return extrapolated.astype(np.float32), 1.0, 1.0
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]
    interpolated = 1.0 / (factor * base ** exponent)

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1 - ramp
    freq = interpolated * (1 - keep) + extrapolated * keep

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    all_dim = mscale(scaling.get("mscale_all_dim", 0))
    return (freq.astype(np.float32),
            mscale(scaling.get("mscale", 1)) / all_dim, all_dim ** 2)


def rotary(x, positions, freq, on_cos_sin):
    """x [s, heads, dim], rotate-half."""
    half = x.shape[-1] // 2
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos = on_cos_sin * jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = on_cos_sin * jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


# -- latent attention -----------------------------------------------------------

def attention(a, p, cfg):
    """One row: a [s, hidden] -> [s, hidden]. The heads go ``HEAD_CHUNK`` at
    a time (their columns of W_qb and W_kvb, their rows of W_o), each
    group's queries in chunks; a group is recomputed in the backward pass,
    so that one group's q, k and v exist at once."""
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    wide, rank, s = cfg["v_head_dim"], cfg["kv_lora_rank"], a.shape[0]
    freq, on_cos_sin, on_scale = yarn(cfg)
    ids = jnp.arange(s)
    c_q = rms_norm(a @ p["q_a_proj"], p["q_a_layernorm"], eps)
    kv_a = a @ p["kv_a_proj_with_mqa"]
    c_kv = rms_norm(kv_a[:, :rank], p["kv_a_layernorm"], eps)
    k_rope = rotary(kv_a[:, None, rank:], ids, freq, on_cos_sin)[:, 0]
    scale = (nope + rope) ** -0.5 * on_scale
    some = min(HEAD_CHUNK, heads)
    assert heads % some == 0, (heads, some)

    def some_heads(w_qb, w_kvb, w_o):
        q = (c_q @ w_qb).reshape(s, some, nope + rope)
        q_nope = q[..., :nope]
        q_rope = rotary(q[..., nope:], ids, freq, on_cos_sin)
        kv = (c_kv @ w_kvb).reshape(s, some, nope + wide)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        def some_queries(qn, qr, q_ids):
            scores = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                      + jnp.einsum("qhd,kd->hqk", qr, k_rope)) * scale
            ok = ids[None, :] <= q_ids[:, None]
            prob = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf),
                                  axis=-1)
            return jnp.einsum("hqk,khd->qhd", prob, v)

        out = in_chunks(some_queries, Q_CHUNK, q_nope, q_rope, ids)
        return out.reshape(s, some * wide) @ w_o

    by_group = lambda w, width: jnp.moveaxis(
        w.reshape(w.shape[0], heads // some, some * width), 1, 0)
    return jnp.sum(jax.lax.map(
        lambda ws: jax.checkpoint(some_heads)(*ws),
        (by_group(p["q_b_proj"], nope + rope),
         by_group(p["kv_b_proj"], nope + wide),
         p["o_proj"].reshape(heads // some, some * wide, -1))), axis=0)


# -- hyper-connections ----------------------------------------------------------

def sinkhorn(m, iters, eps):
    """m [s, n, n] positive: rows, then columns, normalised."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def mixing(x, p, cfg):
    """x [s, n, d] -> H_pre [s, n], H_post [s, n], H_res [s, n, n]."""
    s, n, d = x.shape
    flat = x.reshape(s, n * d)
    flat = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                           + cfg["rms_norm_eps"])
    w = lambda name: p[name].reshape(n * d, -1)
    h_pre = jax.nn.sigmoid(p["a_pre"] * (flat @ w("w_pre")) + p["b_pre"])
    h_post = 2 * jax.nn.sigmoid(p["a_post"] * (flat @ w("w_post"))
                                + p["b_post"])
    raw = p["a_res"] * (flat @ w("w_res")).reshape(s, n, n) + p["b_res"]
    raw = jnp.clip(raw, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    return h_pre, h_post, sinkhorn(jnp.exp(raw), cfg["hc_sinkhorn_iters"],
                                   cfg["hc_eps"])


def hyper_connected(x, p, cfg, sub_layer):
    """x [s, n, d] -> (x' [s, n, d], what ``sub_layer`` returned besides).
    Both sides of the sub-layer are position by position, so they run a
    chunk of rows at a time."""
    def before(xc):
        h_pre, h_post, h_res = mixing(xc, p, cfg)
        return jnp.einsum("sn,snd->sd", h_pre, xc), h_post, h_res

    def after(xc, yc, h_post, h_res):
        return (jnp.einsum("sij,sjd->sid", h_res, xc)
                + h_post[:, :, None] * yc[:, None, :])

    u, h_post, h_res = in_chunks(before, ROW_CHUNK, x)
    y, more = sub_layer(u)
    return in_chunks(after, ROW_CHUNK, x, y, h_post, h_res), more


# -- feed-forward -----------------------------------------------------------------

def swiglu(b, p):
    return in_chunks(lambda c: (jax.nn.silu(c @ p["gate_proj"])
                                * (c @ p["up_proj"])) @ p["down_proj"],
                     ROW_CHUNK, b)


def router(b, lp, cfg):
    """Gate of every expert at every position [P, published experts]: the
    chosen experts' unbiased scores, normalised and scaled; zero elsewhere."""
    scores = jax.nn.sigmoid(b @ lp["router"])
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(lp["e_score_correction_bias"]),
        cfg["num_experts_per_tok"])
    choice = jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], chosen].set(1.0)
    gates = scores * choice
    if cfg.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * cfg.get("routed_scaling_factor", 1.0), choice


def expert_layer(b, lp, cfg):
    """b [P, hidden] -> (shared expert + this share's routed part, pairs
    per held expert [count])."""
    first, count = held(cfg)
    gates, choice = router(b, lp, cfg)
    # one expert at a time (a scan over the held experts' stacked matrices:
    # the program holds one expert's body)
    ex = lp["experts"]
    y, _ = jax.lax.scan(
        jax.checkpoint(lambda y, e: (y + e[1][:, None] * swiglu(b, e[0]),
                                     None)),
        swiglu(b, lp["shared"]), (ex, gates[:, first:first + count].T))
    return y, jnp.sum(choice[:, first:first + count], axis=0).astype(jnp.int32)


# -- layers, trunk, module --------------------------------------------------------

def layer(x, lp, cfg, dense):
    """x [s, n, d] -> (x', pairs per held expert or None). Each of the two
    sub-layers is recomputed in the backward pass."""
    eps = cfg["rms_norm_eps"]

    def feed_forward(u):
        b = rms_norm(u, lp["post_attention_layernorm"], eps)
        return (swiglu(b, lp["mlp"]), None) if dense else expert_layer(
            b, lp, cfg)

    x, _ = jax.checkpoint(lambda x: hyper_connected(
        x, lp["hc_attn"], cfg, lambda u: (attention(rms_norm(
            u, lp["input_layernorm"], eps), lp["self_attn"], cfg), None)))(x)
    return jax.checkpoint(lambda x: hyper_connected(
        x, lp["hc_mlp"], cfg, feed_forward))(x)


def spread(x, cfg):
    return jnp.repeat(x[:, None, :], cfg["hc_mult"], axis=1)


def trunk_row(p, tokens, cfg):
    """One row -> (the summed streams [s, d], loads [expert layers, count]);
    each layer is recomputed in the backward pass."""
    index = cfg.get("published_layer_index",
                    list(range(cfg["num_hidden_layers"])))
    x = p["embed_tokens"][tokens]
    loads = []
    for i, published in enumerate(index):
        dense = published < cfg.get("first_k_dense_replace", 0)
        # the n copies of the embedding are made inside the first layer
        x, n = jax.checkpoint(lambda x, lp, dense=dense, first=i == 0: layer(
            spread(x, cfg) if first else x, lp, cfg, dense))(
                x, p[f"layer_{i}"])
        if not dense:
            loads.append(n)
    return jnp.sum(x, axis=1), loads


def module_row(p, h, tokens, cfg):
    """The prediction module over one row's trunk output h [s, d]."""
    mp, eps = p["mtp"], cfg["rms_norm_eps"]
    following = jnp.concatenate([tokens[1:], tokens[-1:]])
    joined = jnp.concatenate([
        rms_norm(h, mp["hnorm"], eps),
        rms_norm(p["embed_tokens"][following], mp["enorm"], eps)], axis=-1)
    x, n = jax.checkpoint(lambda x, lp: layer(spread(x, cfg), lp, cfg, False))(
        joined @ mp["eh_proj"], mp["layer"])
    return jnp.sum(x, axis=1), n


def head_logits(p, x, gain, cfg):
    return rms_norm(x, gain, cfg["rms_norm_eps"]) @ p["lm_head"]


def _float32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


def logits(params, batch, cfg):
    """(next-token logits [rows, s, vocab], the module's logits for the
    token after next [rows, s, vocab] or None)."""
    with jax.default_matmul_precision("highest"):
        p = _float32(params)
        main, second = [], []
        for tokens in batch["tokens"]:
            h, _ = trunk_row(p, tokens, cfg)
            main.append(head_logits(p, h, p["norm"], cfg))
            if cfg.get("num_nextn_predict_layers"):
                x, _ = module_row(p, h, tokens, cfg)
                second.append(head_logits(p, x, p["mtp"]["norm"], cfg))
        return jnp.stack(main), jnp.stack(second) if second else None


def _neg_log_likelihood(p, x, gain, targets, weight, cfg):
    """Sum over positions of weight * -log softmax(logits)[target], the
    logits a chunk of rows at a time."""
    def some_rows(xc, tc, wc):
        logp = jax.nn.log_softmax(head_logits(p, xc, gain, cfg), axis=-1)
        return -wc * jnp.take_along_axis(logp, tc[:, None], axis=-1)[:, 0]

    return jnp.sum(in_chunks(some_rows, ROW_CHUNK, x, targets, weight))


def terms(params, batch, cfg):
    """(total, count): total / count is the loss, and both add over rows."""
    with jax.default_matmul_precision("highest"):
        p = _float32(params)
        total = jnp.float32(0)
        for tokens in batch["tokens"]:
            s = tokens.shape[0]
            ids = jnp.arange(s)
            h, _ = trunk_row(p, tokens, cfg)
            total = total + _neg_log_likelihood(
                p, h, p["norm"], jnp.roll(tokens, -1),
                (ids < s - 1).astype(jnp.float32), cfg)
            if cfg.get("num_nextn_predict_layers"):
                x, _ = module_row(p, h, tokens, cfg)
                second = _neg_log_likelihood(
                    p, x, p["mtp"]["norm"], jnp.roll(tokens, -2),
                    (ids < s - 2).astype(jnp.float32), cfg)
                total = total + cfg["mtp_loss_weight"] * second * (
                    (s - 1) / (s - 2))
        rows, s = batch["tokens"].shape
        return total, jnp.float32(rows * (s - 1))


def router_loads(params, batch, cfg):
    """Pairs per held expert [expert layers (+ 1), count], summed over the
    rows; the prediction module's layer last."""
    with jax.default_matmul_precision("highest"):
        p = _float32(params)
        total = 0
        for tokens in batch["tokens"]:
            h, loads = trunk_row(p, tokens, cfg)
            if cfg.get("num_nextn_predict_layers"):
                loads = loads + [module_row(p, h, tokens, cfg)[1]]
            total = total + jnp.stack(loads)
        return total
