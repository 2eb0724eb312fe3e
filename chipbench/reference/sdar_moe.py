"""Plain reference for the ``sdar_moe`` family (SDAR-30B-A3B-Chat).

Straightforward ``jax.numpy`` in float32 (callers wrap the calls in
``jax.default_matmul_precision("highest")``; ``terms`` and
``router_loads`` do so themselves): no kernels, no sorting, no bf16. It
reads the parameter pytree the system trains, by the source's names, and
a configuration file's dictionary (``cfg``) under the source's key names;
nothing is imported from the package. The equations (ISSUE 27, and each
departure in the configuration's ``assumed``):

- a = RMSNorm(x; eps); q = a W_q [P, heads, head_dim], k = a W_k, v = a
  W_v [P, kv_heads, head_dim]; q, k <- RMSNorm over the head dimension
  (per-head gain shared by the heads); rotary embedding (rotate-half,
  theta) at each position's id; query head h reads key-value head
  h // (heads / kv_heads); s = q k^T / sqrt(head_dim) + M, softmax,
  x <- x + (softmax(s) v) W_o.
- b = RMSNorm(x); r = softmax(b W_r) over all ``published_num_experts``;
  S = the ``num_experts_per_tok`` largest; w_e = r_e / sum_{S} r;
  y = sum over e in S AND held of w_e W_down^e(silu(W_gate^e b) * W_up^e
  b): EVERY held expert is applied to every position and weighted by its
  gate, zero where it was not chosen. x <- x + y.
- RMSNorm, logits = x W_head.
- Block diffusion: a row of L tokens is 2L positions (noised copy, then
  clean copy; token i at position id i in both); beta(i) = i // block;
  M allows noised->noised iff same block, noised->clean iff the clean
  block is earlier, clean->clean iff not later, clean->noised never — a
  dense boolean mask built from block indices, queries taken in chunks so
  that heads x 2L x 2L scores never exist at once. Loss = (1 / (rows x
  L)) sum over replaced i of (1 / t_beta(i)) (-log softmax(logits_i)[x_i]).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_CHUNK = 128  # query positions whose scores exist at once


def held(cfg: dict) -> tuple[int, int]:
    return int(cfg.get("first_expert", 0)), int(cfg["num_experts"])


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotary(x, positions, theta):
    """x [s, heads, head_dim]."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def block_diffusion_mask(q_pos, k_pos, length: int, block: int):
    """[len(q_pos), len(k_pos)] bool over positions 0..2*length-1."""
    q_clean, k_clean = (q_pos >= length)[:, None], (k_pos >= length)[None, :]
    qb = ((q_pos % length) // block)[:, None]
    kb = ((k_pos % length) // block)[None, :]
    return jnp.where(q_clean, k_clean & (kb <= qb),
                     jnp.where(k_clean, kb < qb, kb == qb))


def attention(a, lp, cfg, ids, length):
    """One row: a [2L, hidden] -> [2L, hidden]."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps, s = cfg["head_dim"], cfg["rms_norm_eps"], a.shape[0]
    q = (a @ lp["q_proj"]).reshape(s, heads, hd)
    k = (a @ lp["k_proj"]).reshape(s, kv, hd)
    v = (a @ lp["v_proj"]).reshape(s, kv, hd)
    q = rotary(rms_norm(q, lp["q_norm"], eps), ids, cfg["rope_theta"])
    k = rotary(rms_norm(k, lp["k_norm"], eps), ids, cfg["rope_theta"])
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    pos = jnp.arange(s)
    chunk = min(Q_CHUNK, s)

    @jax.checkpoint
    def some_queries(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, chunk, axis=0)
        sc = jnp.einsum("qhd,khd->hqk", qc, k) / hd ** 0.5
        ok = block_diffusion_mask(start + jnp.arange(chunk), pos, length,
                                  cfg["block_length"])
        p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(some_queries, jnp.arange(0, s, chunk))
    return out.reshape(s, heads * hd) @ lp["o_proj"]


def router(b, lp, cfg):
    """Gate of every expert at every position [P, published experts]:
    the renormalised probability where chosen, zero elsewhere."""
    r = jax.nn.softmax(b @ lp["router"], axis=-1)
    top, chosen = jax.lax.top_k(r, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.zeros_like(r)
    return gates.at[jnp.arange(r.shape[0])[:, None], chosen].set(top)


def experts(b, lp, cfg, gates):
    """Every held expert at every position, one expert at a time (a scan
    over the held experts' stacked matrices, its body recomputed in the
    backward pass, so that the program holds one expert's body and one
    expert's activations)."""
    first, count = held(cfg)
    ex = lp["experts"]

    @jax.checkpoint
    def one(y, e):
        gate_proj, up_proj, down_proj, g = e
        h = jax.nn.silu(b @ gate_proj) * (b @ up_proj)
        return y + g[:, None] * (h @ down_proj), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(b), (
        ex["gate_proj"], ex["up_proj"], ex["down_proj"],
        gates[:, first:first + count].T))
    return y


def moe_layer(b, lp, cfg):
    """b [P, hidden] -> (this share's part [P, hidden], pairs per held
    expert [count])."""
    first, count = held(cfg)
    gates = router(b, lp, cfg)
    loads = jnp.sum(gates[:, first:first + count] > 0, axis=0)
    return experts(b, lp, cfg, gates), loads


def hidden_row(p, tokens, ids, cfg, length):
    """One row of 2L tokens -> (hidden [2L, d], loads [layers, count])."""
    eps = cfg["rms_norm_eps"]
    x = p["embed_tokens"][tokens]

    @jax.checkpoint
    def layer(x, lp):
        x = x + attention(rms_norm(x, lp["input_layernorm"], eps), lp, cfg,
                          ids, length)
        y, loads = moe_layer(rms_norm(x, lp["post_attention_layernorm"], eps),
                             lp, cfg)
        return x + y, loads

    loads = []
    for i in range(cfg["num_hidden_layers"]):
        x, n = layer(x, p[f"layer_{i}"])
        loads.append(n)
    return x, jnp.stack(loads)


def _row_inputs(batch, r):
    length = batch["tokens"].shape[1]
    ids = jnp.arange(length)
    return (jnp.concatenate([batch["noised"][r], batch["tokens"][r]]),
            jnp.concatenate([ids, ids]), length)


def logits(params, batch, cfg):
    """[rows, L, vocab] float32 logits at the noised positions."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    rows = []
    for r in range(batch["tokens"].shape[0]):
        tokens, ids, length = _row_inputs(batch, r)
        x, _ = hidden_row(p, tokens, ids, cfg, length)
        rows.append(rms_norm(x[:length], p["norm"], cfg["rms_norm_eps"])
                    @ p["lm_head"])
    return jnp.stack(rows)


def terms(params, batch, cfg):
    """(sum over the batch's replaced positions of -log p(token) / t,
    rows x L)."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(logits(params, batch, cfg), axis=-1)
        ll = jnp.take_along_axis(logp, batch["tokens"][..., None],
                                 axis=-1)[..., 0]
        weight = batch["replaced"].astype(jnp.float32) / jnp.repeat(
            batch["t"], cfg["block_length"], axis=1)
        return -jnp.sum(ll * weight), jnp.float32(ll.size)


def router_loads(params, batch, cfg):
    """Pairs per held expert [layers, count], summed over the rows."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        total = 0
        for r in range(batch["tokens"].shape[0]):
            tokens, ids, length = _row_inputs(batch, r)
            total = total + hidden_row(p, tokens, ids, cfg, length)[1]
        return total
