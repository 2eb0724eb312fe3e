"""Plain reference for the ``sambay`` family (Phi-4-mini-flash-reasoning).

Straightforward ``jax.numpy`` in float32 (``terms`` wraps itself in
``jax.default_matmul_precision("highest")``): no kernels, no chunked
scan with a hand-written backward pass, no bf16. It reads the parameter
pytree the system trains and a configuration file's dictionary (``cfg``)
under the source's key names; nothing is imported from the package. The
equations (ISSUE 31, and each departure in the configuration's
``assumed``), with d = ``hidden_size``, LN = LayerNorm(gain, bias; eps):

every layer l: ``h = x + Mixer_l(LN1(x))``, ``x' = h + MLP(LN2(h))``,
``MLP(u) = W_down(silu(g) * v)``, ``[g ; v] = W_gate_up u``. Tokens enter
through the embedding, no positional encoding; out through a final LN and
the embedding's transpose; loss = next-token cross-entropy. The mixer by
``layer_types[l]``:

1. ``mamba``: ``[a ; z] = W_in u``; ``xh = silu(conv(a))``, a causal
   depth-wise convolution of width ``mamba_d_conv`` with bias (weight row
   ``width - 1`` meets the current position); ``[dl ; B ; C] = W_x xh``;
   ``Dt = softplus(W_dt dl + b_dt)``; ``A = -exp(A_log)``;
   ``s_t = exp(Dt_t * A) * s_{t-1} + (Dt_t * xh_t) B_t^T`` (``[E, N]``,
   ``s_0 = 0``), ONE STEP AT A TIME; ``y_t = s_t C_t + D * xh_t``;
   ``out = W_out(y * silu(z))``.
2. ``mamba_memory``: the same; hands ``m = y`` to every ``gmu`` layer.
3. ``gmu``: ``out = W_out(m * silu(W_in u))``.
4. ``sliding_attention``, ``full_attention``, ``cross_attention``:
   differential attention. ``q = W_q u + b_q``; ``k``, ``v`` from ``W_kv
   u + b_kv`` (first half k, second half v), except in
   ``cross_attention``, which reads the ``full_attention`` layer's.
   Adjacent heads pair: query pairs ``(q1, q2) = (head 2p, head 2p +
   1)``, key pairs likewise, values joined ``V_j = [v_2j ; v_2j+1]``;
   query pair p reads key-value pair ``p // (query pairs / kv pairs)``.
   ``P_i = softmax(q_i k_i^T / sqrt(head_dim) + mask)``; ``o =
   RMSNorm(P_1 V - lam P_2 V; gain, eps) * (1 - lam0)``; ``lam = exp(lq1
   . lk1) - exp(lq2 . lk2) + lam0``; ``lam0 = 0.8 - 0.6 exp(-0.3 l)``
   with l = ``published_layer_index``; ``out = W_o concat(o) + b_o``.
   Mask: causal; ``sliding_attention`` also ``q_pos - k_pos <
   sliding_window``.

Blocks, so that 8,192 positions fit beside the system's state: queries
``Q_CHUNK`` at a time, the MLP and the head ``ROW_CHUNK`` positions at a
time, the recurrence ``SCAN_CHUNK`` steps at a time — each under
``jax.checkpoint``, as is every layer, so that the backward pass holds
one block's inside at once. The blocks change nothing that is computed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_CHUNK = 32       # query positions whose scores exist at once
ROW_CHUNK = 512    # positions whose MLP activations / logits exist at once
SCAN_CHUNK = 128   # steps of the recurrence whose states the backward keeps


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def row_chunk(steps: int) -> int:
    return ROW_CHUNK if steps % ROW_CHUNK == 0 else steps


def in_row_chunks(fn, *xs):
    """``fn`` over arrays ``[T, ...]`` in chunks of ``ROW_CHUNK`` rows (the
    whole of them where that does not divide T)."""
    steps = xs[0].shape[0]
    out = jax.lax.map(jax.checkpoint(lambda c: fn(*c)), tuple(
        x.reshape(-1, row_chunk(steps), *x.shape[1:]) for x in xs))
    return out.reshape(steps, *out.shape[2:])


def mlp(u, p):
    def some_rows(rows):
        g, v = jnp.split(rows @ p["gate_up_proj"], 2, axis=-1)
        return (jax.nn.silu(g) * v) @ p["down_proj"]

    return in_row_chunks(some_rows, u)


def recurrence(s, xh, dt, a, b_in, c_in):
    """``s_t = exp(dt_t A) s_{t-1} + (dt_t xh_t) B_t^T``; ``y_t = s_t
    C_t``, from the state ``s [E, N]``: ``xh, dt [T, E]``, ``a [E, N]``,
    ``b_in, c_in [T, N]`` -> (the last state, ``y [T, E]``)."""
    steps = xh.shape[0]
    chunk = SCAN_CHUNK if steps % SCAN_CHUNK == 0 else steps

    def step(s, t):
        x_t, dt_t, b_t, c_t = t
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * x_t)[:, None] * b_t[None]
        return s, s @ c_t

    @jax.checkpoint
    def some_steps(s, ts):
        return jax.lax.scan(step, s, ts)

    chunked = tuple(v.reshape(-1, chunk, v.shape[1])
                    for v in (xh, dt, b_in, c_in))
    s, y = jax.lax.scan(some_steps, s, chunked)
    return s, y.reshape(steps, -1)


def mamba(u, p, cfg):
    """One row: u [T, d] -> (out [T, d], y [T, E]). Everything but the
    state and the convolution's last rows is position-wise, so the layer
    runs ``ROW_CHUNK`` positions at a time and carries those two."""
    n, r, width = (cfg["mamba_d_state"], cfg["mamba_dt_rank"],
                   cfg["mamba_d_conv"])
    a_neg = -jnp.exp(p["A_log"])
    chunk = row_chunk(u.shape[0])

    @jax.checkpoint
    def some_rows(carry, rows):
        s, tail = carry
        a, z = jnp.split(rows @ p["in_proj"], 2, axis=-1)
        padded = jnp.concatenate([tail, a])
        conv = p["conv1d_bias"] + sum(
            padded[j:j + chunk] * p["conv1d_weight"][j] for j in range(width))
        xh = jax.nn.silu(conv)
        dl, b_in, c_in = jnp.split(xh @ p["x_proj"], [r, r + n], axis=-1)
        dt = jax.nn.softplus(dl @ p["dt_proj"] + p["dt_bias"])
        s, y = recurrence(s, xh, dt, a_neg, b_in, c_in)
        y = y + p["D"] * xh
        return (s, padded[chunk:]), ((y * jax.nn.silu(z)) @ p["out_proj"], y)

    e = a_neg.shape[0]
    _, (out, y) = jax.lax.scan(
        some_rows, (jnp.zeros_like(a_neg), jnp.zeros((width - 1, e))),
        u.reshape(-1, chunk, u.shape[1]))
    return out.reshape(u.shape), y.reshape(u.shape[0], e)


def keys_values(u, p, cfg):
    """(k [T, kv pairs, 2, head_dim], V [T, kv pairs, 2 head_dim])."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // heads
    k, v = jnp.split(u @ p["kv_proj"] + p["kv_bias"], 2, axis=-1)
    return (k.reshape(-1, kv // 2, 2, hd), v.reshape(-1, kv // 2, 2 * hd))


def diff_attention(u, p, kv, cfg, window, index):
    heads = cfg["num_attention_heads"]
    hd, s = cfg["hidden_size"] // heads, u.shape[0]
    q = (u @ p["q_proj"] + p["q_bias"]).reshape(s, heads // 2, 2, hd)
    k, v = kv
    share = (heads // 2) // k.shape[1]      # query pairs a key-value pair
    k, v = jnp.repeat(k, share, axis=1), jnp.repeat(v, share, axis=1)
    pos = jnp.arange(s)
    chunk = min(Q_CHUNK, s)

    @jax.checkpoint
    def some_queries(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, chunk, axis=0)
        rows = (start + jnp.arange(chunk))[:, None]
        ok = pos[None, :] <= rows
        if window:
            ok = ok & (rows - pos[None, :] < window)
        sc = jnp.einsum("qpid,kpid->piqk", qc, k) / math.sqrt(hd)
        prob = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        return jnp.einsum("piqk,kpe->qpie", prob, v)

    out = jax.lax.map(some_queries, jnp.arange(0, s, chunk))
    out = out.reshape(s, heads // 2, 2, 2 * hd)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)
    o = out[:, :, 0] - lam * out[:, :, 1]
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                     + cfg["layer_norm_eps"]) * p["subln"] * (1.0 - lam0)
    return o.reshape(s, -1) @ p["o_proj"] + p["o_bias"]


def hidden_row(p, tokens, cfg):
    """One row of T tokens -> hidden [T, d] before the final norm."""
    eps = cfg["layer_norm_eps"]
    x = p["embed_tokens"][tokens]
    memory = kv = None
    for i, kind in enumerate(cfg["layer_types"]):
        index = cfg.get("published_layer_index", range(10 ** 6))[i]

        @jax.checkpoint
        def layer(x, lp, memory, kv, kind=kind, index=index):
            u = layer_norm(x, lp["input_layernorm"], eps)
            if kind in ("mamba", "mamba_memory"):
                out, y = mamba(u, lp["mixer"], cfg)
                if kind == "mamba_memory":
                    memory = y
            elif kind == "gmu":
                mix = lp["mixer"]
                out = in_row_chunks(
                    lambda rows, m: (m * jax.nn.silu(rows @ mix["in_proj"])
                                     ) @ mix["out_proj"], u, memory)
            else:
                own = kv if kind == "cross_attention" else keys_values(
                    u, lp["mixer"], cfg)
                out = diff_attention(
                    u, lp["mixer"], own, cfg,
                    cfg["sliding_window"] if kind == "sliding_attention"
                    else None, index)
                if kind == "full_attention":
                    kv = own
            h = x + out
            return (h + mlp(layer_norm(h, lp["post_attention_layernorm"],
                                       eps), lp["mlp"]), memory, kv)

        x, memory, kv = layer(x, p[f"layer_{i}"], memory, kv)
    return x


def logits(params, batch, cfg):
    """[rows, T, vocab] float32 logits (small sizes: nothing blocked)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        return jnp.stack([
            layer_norm(hidden_row(p, row, cfg), p["final_layernorm"],
                       cfg["layer_norm_eps"]) @ p["embed_tokens"].T
            for row in batch["tokens"]])


def terms(params, batch, cfg):
    """(sum over the batch's rows and positions 0..T-2 of -log p(next
    token), rows x (T - 1))."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        total = 0.0
        for row in batch["tokens"]:
            x = layer_norm(hidden_row(p, row, cfg), p["final_layernorm"],
                           cfg["layer_norm_eps"])
            # the last position has no target: its weight is zero
            targets = jnp.concatenate([row[1:], row[:1]])
            weight = (jnp.arange(row.shape[0]) < row.shape[0] - 1
                      ).astype(jnp.float32)

            def some_rows(xs, ts, ws):
                logp = jax.nn.log_softmax(xs @ p["embed_tokens"].T, axis=-1)
                return -ws * jnp.take_along_axis(logp, ts[:, None], axis=-1)[:, 0]

            total = total + jnp.sum(in_row_chunks(some_rows, x, targets,
                                                  weight))
        rows, steps = batch["tokens"].shape
        return total, jnp.float32(rows * (steps - 1))
