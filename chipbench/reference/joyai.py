"""Plain reference for the ``joyai`` family (JoyAI-LLM-Flash, ``model_type``
``joyai_llm_flash``): the DeepSeek-V3-style latent-attention expert block
on ONE plain pre-norm residual stream, with its multi-token-prediction
module.

Straightforward ``jax.numpy`` in float32 (``terms``, ``logits`` and
``router_loads`` wrap themselves in ``jax.default_matmul_precision(
"highest")``): no kernel, no sorting, no buffer, no grouped product, no
bf16, no ``remat`` of the program's. It reads the parameter pytree the
system trains, by the source's names, and a configuration file's
dictionary (``cfg``) under the source's key names; nothing is imported
from the package. One row at a time, ``x [s, d]``. The equations (ISSUE
39, and each departure in the configuration's ``assumed``):

- A layer: ``x <- x + MLA(RMSNorm(x))``, then ``x <- x + FF(RMSNorm(x))``.
- Latent attention (the non-absorbed form): ``c_q = RMSNorm(a W_qa)``, ``q
  = c_q W_qb``; ``[c_kv | k_rope] = a W_kva``; ``[k_nope | v] = RMSNorm(
  c_kv) W_kvb``; rotary on ``q_rope`` and the one ``k_rope`` in the
  PUBLISHED pairing (``rope_interleave``: the pair ``(2i, 2i + 1)`` turns by
  ``position x theta^(-2i / rope)``, each pair where it lies; without the
  key, rotate-half), ``rope_scaling`` null: plain frequencies, no
  ``mscale``; scores as TWO products, ``q_nope k_nope^T + q_rope
  k_rope^T``, times ``(nope + rope)^-0.5``; causal; heads in groups,
  queries in row blocks, so that 8,192 positions fit beside the system's
  state.
- Feed-forward: a layer whose published index is under
  ``first_k_dense_replace`` is a SwiGLU; any other ``shared(b) + sum over
  held e of g_e expert_e(b)``: ``s = sigmoid(b W_r)`` over the PUBLISHED
  router width, C the 0/1 matrix of ``top_k(s + e_bias)``, ``g = s C /
  sum(s C) * routed_scaling_factor``; EVERY held expert is applied to every
  position and weighted by its gate, zero where it was not chosen (a mask,
  no buffer). The bias takes no gradient.
- Prediction module, depth 1: ``h'_i = [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}
  ))] W_eh`` with h the trunk's stream before the final norm, one expert
  layer, its own final norm, the SHARED embedding and head; the last
  position (no t_{i+1}) takes its own token and is in no loss.
- ``loss = mean_i CE(main_i, t_{i+1}) + mtp_loss_weight * mean_i CE(mtp_i,
  t_{i+2})``; ``terms`` returns (total, count) with count = rows x (s - 1)
  and the second mean rescaled into the total (``x (s - 1) / (s - 2)``), so
  that total / count is the loss and totals and counts add over rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Q_CHUNK = 128    # query positions whose scores exist at once
ROW_CHUNK = 512  # positions whose feed-forward or logits exist at once
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


# -- rotary embedding ------------------------------------------------------------

def frequencies(cfg):
    if cfg.get("rope_scaling"):
        raise ValueError("this family's source has rope_scaling null")
    dim = cfg["qk_rope_head_dim"]
    return (float(cfg["rope_theta"]) ** (
        -np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)


def rotary(x, positions, freq, interleave: bool):
    """x [s, heads, dim]: pair i turned by ``positions x freq[i]``. The
    pair is ``(2i, 2i + 1)`` where ``interleave``, else ``(i, i + dim /
    2)``; every value stays where it lies."""
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if not interleave:
        half = x.shape[-1] // 2
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


# -- latent attention --------------------------------------------------------------

def attention(a, p, cfg):
    """One row: a [s, hidden] -> [s, hidden]. The heads go ``HEAD_CHUNK`` at
    a time (their columns of W_qb and W_kvb, their rows of W_o), each
    group's queries in row blocks; a group is recomputed in the backward
    pass, so that one group's q, k and v exist at once."""
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    wide, rank, s = cfg["v_head_dim"], cfg["kv_lora_rank"], a.shape[0]
    freq, pairs = frequencies(cfg), bool(cfg.get("rope_interleave", False))
    ids = jnp.arange(s)
    c_q = rms_norm(a @ p["q_a_proj"], p["q_a_layernorm"], eps)
    kv_a = a @ p["kv_a_proj_with_mqa"]
    c_kv = rms_norm(kv_a[:, :rank], p["kv_a_layernorm"], eps)
    k_rope = rotary(kv_a[:, None, rank:], ids, freq, pairs)[:, 0]
    scale = (nope + rope) ** -0.5
    some = min(HEAD_CHUNK, heads)
    assert heads % some == 0, (heads, some)

    def some_heads(w_qb, w_kvb, w_o):
        q = (c_q @ w_qb).reshape(s, some, nope + rope)
        q_nope = q[..., :nope]
        q_rope = rotary(q[..., nope:], ids, freq, pairs)
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


# -- feed-forward --------------------------------------------------------------------

def swiglu(b, p):
    return in_chunks(lambda c: (jax.nn.silu(c @ p["gate_proj"])
                                * (c @ p["up_proj"])) @ p["down_proj"],
                     ROW_CHUNK, b)


def router(b, lp, cfg):
    """(gate of every expert at every position [P, published experts]: the
    chosen experts' unbiased scores, normalised and scaled, zero elsewhere;
    the 0/1 choice)."""
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
    # the program holds one expert's body), every position under its gate
    y, _ = jax.lax.scan(
        jax.checkpoint(lambda y, e: (y + e[1][:, None] * swiglu(b, e[0]),
                                     None)),
        swiglu(b, lp["shared"]),
        (lp["experts"], gates[:, first:first + count].T))
    return y, jnp.sum(choice[:, first:first + count], axis=0).astype(jnp.int32)


# -- layers, trunk, module -----------------------------------------------------------

def layer(x, lp, cfg, dense):
    """x [s, d] -> (x', pairs per held expert or None). Each of the two
    sub-layers is recomputed in the backward pass."""
    eps = cfg["rms_norm_eps"]
    x = x + jax.checkpoint(lambda x: attention(
        rms_norm(x, lp["input_layernorm"], eps), lp["self_attn"], cfg))(x)

    def feed_forward(x):
        b = rms_norm(x, lp["post_attention_layernorm"], eps)
        return (swiglu(b, lp["mlp"]), None) if dense else expert_layer(
            b, lp, cfg)

    y, n = jax.checkpoint(feed_forward)(x)
    return x + y, n


def trunk_row(p, tokens, cfg):
    """One row -> (the stream before the final norm [s, d], loads [expert
    layers][count]); each layer is recomputed in the backward pass."""
    index = cfg.get("published_layer_index",
                    list(range(cfg["num_hidden_layers"])))
    x = p["embed_tokens"][tokens]
    loads = []
    for i, published in enumerate(index):
        dense = published < cfg.get("first_k_dense_replace", 0)
        x, n = jax.checkpoint(lambda x, lp, dense=dense: layer(
            x, lp, cfg, dense))(x, p[f"layer_{i}"])
        if not dense:
            loads.append(n)
    return x, loads


def module_row(p, h, tokens, cfg):
    """The prediction module over one row's trunk output h [s, d]."""
    mp, eps = p["mtp"], cfg["rms_norm_eps"]
    following = jnp.concatenate([tokens[1:], tokens[-1:]])
    joined = jnp.concatenate([
        rms_norm(h, mp["hnorm"], eps),
        rms_norm(p["embed_tokens"][following], mp["enorm"], eps)], axis=-1)
    return jax.checkpoint(lambda x, lp: layer(x, lp, cfg, False))(
        joined @ mp["eh_proj"], mp["layer"])


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
    logits a block of rows at a time."""
    def some_rows(xc, tc, wc):
        logp = jax.nn.log_softmax(head_logits(p, xc, gain, cfg), axis=-1)
        return -wc * jnp.take_along_axis(logp, tc[:, None], axis=-1)[:, 0]

    return jnp.sum(in_chunks(some_rows, ROW_CHUNK, x, targets, weight))


def losses(params, batch, cfg):
    """(sum over rows and positions of the next-token loss, the same of the
    module's loss for the token after next, unweighted; 0 without a
    module), for a test that wants the two apart."""
    with jax.default_matmul_precision("highest"):
        p = _float32(params)
        first = second = jnp.float32(0)
        for tokens in batch["tokens"]:
            s = tokens.shape[0]
            ids = jnp.arange(s)
            h, _ = trunk_row(p, tokens, cfg)
            first = first + _neg_log_likelihood(
                p, h, p["norm"], jnp.roll(tokens, -1),
                (ids < s - 1).astype(jnp.float32), cfg)
            if cfg.get("num_nextn_predict_layers"):
                x, _ = module_row(p, h, tokens, cfg)
                second = second + _neg_log_likelihood(
                    p, x, p["mtp"]["norm"], jnp.roll(tokens, -2),
                    (ids < s - 2).astype(jnp.float32), cfg)
        return first, second


def terms(params, batch, cfg):
    """(total, count): total / count is the loss, and both add over rows."""
    first, second = losses(params, batch, cfg)
    rows, s = batch["tokens"].shape
    if cfg.get("num_nextn_predict_layers"):
        first = first + cfg["mtp_loss_weight"] * second * ((s - 1) / (s - 2))
    return first, jnp.float32(rows * (s - 1))


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
