"""Plain reference for the transformer families (``bert_mlm``, ``gpt_lm``).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no flax, no
bf16, its own Adam. It reads the same parameter pytree the system
trains, by name, and follows ``pytorch_ps_mpi_tpu/models/bert.py`` as it
is (the departures from the papers are listed in each configuration's
``assumed``): pre-LayerNorm blocks (eps 1e-6), tanh-approximated GELU,
learned positions, an untied dense MLM head for BERT and the tied
embedding head for GPT.

The loss of a batch is computed in blocks of sequences so that a long
context never holds more than one block's activations: each block
returns the SUM of its per-position terms, the sums and the gradient of
the sums are added up, and the division by the count is done once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _layer_norm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        (2.0 / jnp.pi) ** 0.5 * (x + 0.044715 * x ** 3)))


def _attention(x, p, causal: bool):
    qkv = jnp.einsum("bld,dthe->blthe", x, p["qkv"]["kernel"]) + p["qkv"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqhe,bkhe->bhqk", q, k) / q.shape[-1] ** 0.5
    if causal:
        l = x.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((l, l), bool))[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhe->bqhe", a, v)
    return jnp.einsum("bqhe,hed->bqd", out, p["out"]["kernel"]) + p["out"]["bias"]


def logits(params, tokens, *, num_layers: int, causal: bool, tied: bool):
    """[b, l] token ids -> [b, l, vocab] float32 logits."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    x = p["tok_emb"]["embedding"][tokens]
    x = x + p["pos_emb"]["embedding"][: tokens.shape[-1]][None]
    for i in range(num_layers):
        lp = p[f"layer_{i}"]
        x = x + _attention(_layer_norm(x, lp["LayerNorm_0"]),
                           lp["SelfAttention_0"], causal)
        y = _layer_norm(x, lp["LayerNorm_1"])
        y = _gelu_tanh(y @ lp["Dense_0"]["kernel"] + lp["Dense_0"]["bias"])
        x = x + y @ lp["Dense_1"]["kernel"] + lp["Dense_1"]["bias"]
    x = _layer_norm(x, p["LayerNorm_0"])
    if tied:
        return x @ p["tok_emb"]["embedding"].T
    return x @ p["mlm_head"]["kernel"] + p["mlm_head"]["bias"]


def _log_likelihood(lg, targets):
    logp = jax.nn.log_softmax(lg, axis=-1)
    return jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def mlm_terms(params, batch, **kw):
    """(sum of -log p(target) over masked positions, their count)."""
    ll = _log_likelihood(logits(params, batch["tokens"], causal=False,
                                tied=False, **kw), batch["targets"])
    mask = batch["mask"].astype(jnp.float32)
    return -(ll * mask).sum(), mask.sum()


def lm_terms(params, batch, **kw):
    """(sum of -log p(next token) over positions 0..l-2, their count)."""
    tokens = batch["tokens"]
    ll = _log_likelihood(logits(params, tokens, causal=True, tied=True,
                                **kw)[:, :-1], tokens[:, 1:])
    return -ll.sum(), jnp.float32(ll.size)


class BlockedLoss:
    """Loss and gradient of a whole batch, block by block. ``terms_fn``
    is ``mlm_terms`` or ``lm_terms`` with its keywords bound."""

    def __init__(self, terms_fn, block_rows: int):
        self.block_rows = block_rows

        @jax.jit
        def block(params, sub):
            with jax.default_matmul_precision("highest"):
                (total, count), g = jax.value_and_grad(
                    lambda p: terms_fn(p, sub), has_aux=True)(params)
            return total, count, g

        self._block = block
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        self._scale = jax.jit(
            lambda g, d: jax.tree.map(lambda x: x / d, g))

    def __call__(self, params, batch):
        rows = jax.tree.leaves(batch)[0].shape[0]
        total = count = grads = None
        for r in range(0, rows, self.block_rows):
            sub = jax.tree.map(lambda a: a[r:r + self.block_rows], batch)
            t, c, g = self._block(params, sub)
            total, count, grads = ((t, c, g) if grads is None else
                                   (total + t, count + c, self._add(grads, g)))
        denom = jnp.maximum(count, 1.0)
        return total / denom, self._scale(grads, denom)


class Adam:
    """The update ``pytorch_ps_mpi_tpu/optim.py::adam_update`` documents
    (torch's form: eps joins sqrt(v) before the bias correction)."""

    def __init__(self, params, lr, b1=0.9, b2=0.999, eps=1e-8):
        self.lr, self.b1, self.b2 = lr, b1, b2
        self.t = 0
        self.m = jax.tree.map(jnp.zeros_like, params)
        self.v = jax.tree.map(jnp.zeros_like, params)

        @jax.jit
        def step(p, g, m, v, size):
            m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
            v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
            p = jax.tree.map(
                lambda p, m, v: p - size * m / (jnp.sqrt(v) + eps), p, m, v)
            return p, m, v

        self._step = step

    def update(self, params, grads):
        self.t += 1
        size = self.lr * (1 - self.b2 ** self.t) ** 0.5 / (1 - self.b1 ** self.t)
        params, self.m, self.v = self._step(params, grads, self.m, self.v,
                                            jnp.float32(size))
        return params
