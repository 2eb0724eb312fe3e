"""GPT (decoder-only causal LM) train-step bench — the second model
family's TPU number.

BERT-base MLM stresses flat-gradient bandwidth (``bert_bench.py``); the
causal LM stresses the CAUSAL attention paths — on TPU, at s1024/s2048
the 'full' gate dispatches the flash kernel (seq >= FLASH_MIN_SEQ),
whose causal schedule skips fully-future tiles, so this line measures
that schedule inside a whole training step rather than a kernel
microbench. The einsum twin rides alongside at each shape as the A/B.

GPT-2-small geometry (12 layers, 12 heads, 768 hidden, 50257 vocab,
tied embeddings — ~124M params), Adam, bf16 compute, timed by the
shared step recipe (``benchmarks/_stepbench.py``).

Run on a TPU: ``python benchmarks/gpt_bench.py``; on another backend it
runs one tiny line, labeled with that backend, so the script proves
itself runnable.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._stepbench import step_timing_fields
from pytorch_ps_mpi_tpu.models.bert import BertConfig
from pytorch_ps_mpi_tpu.models.gpt import GPTLM, causal_lm_loss
from pytorch_ps_mpi_tpu.optim import AdamHyper, adam_update, init_adam_state
from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache


def emit(**rec):
    rec.setdefault("backend", jax.default_backend())
    print(json.dumps(rec), flush=True)


def _suffix(attention: str, remat: bool = False) -> str:
    s = "" if attention == "full" else f"_attn-{attention}"
    return s + ("_remat" if remat else "")


def metric_name(batch: int, seq: int, attention: str, cfg_kw: dict,
                remat: bool = False) -> str:
    """Metric name derived from the config alone (abstract eval, no
    device work)."""
    cfg = BertConfig(causal=True, attention=attention, remat=remat,
                     max_position=max(1024, seq), **cfg_kw)
    model = GPTLM(cfg)
    shapes = jax.eval_shape(
        model.init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, seq), jnp.int32))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    return (f"gpt2s_{n_params//10**6}M_lm_train_step_b{batch}_s{seq}"
            f"{_suffix(attention, remat)}")


def bench_line(batch: int, seq: int, attention: str, cfg_kw: dict,
               metric: str, remat: bool = False, reps: int = 5) -> None:
    cfg = BertConfig(causal=True, attention=attention, remat=remat,
                     max_position=max(1024, seq), **cfg_kw)
    model = GPTLM(cfg)
    h = AdamHyper(lr=1e-4)

    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0,
                                cfg.vocab_size)

    def loss_fn(params, toks):
        return causal_lm_loss(model.apply(params, toks), toks)

    def train_step(params, state, toks):
        loss, grads = jax.value_and_grad(loss_fn)(params, toks)
        p2, s2 = adam_update(params, grads, state, h)
        return p2, s2, loss

    params = jax.jit(model.init)(jax.random.key(0), tokens[:1])
    state = init_adam_state(params)
    fields = step_timing_fields(train_step, params, state, tokens,
                                reps=reps)
    emit(metric=metric, attention=attention, remat=remat, **fields)


def main() -> None:
    enable_compilation_cache()
    if jax.default_backend() != "tpu":
        # tiny geometry, one line, runnable anywhere
        tiny = dict(dtype=jnp.float32, num_layers=2, num_heads=2,
                    hidden_size=64, intermediate_size=128, vocab_size=512)
        bench_line(2, 64, "full", tiny,
                   metric=metric_name(2, 64, "full", tiny), reps=2)
        return
    gpt2s = dict(dtype=jnp.bfloat16, num_layers=12, num_heads=12,
                 hidden_size=768, intermediate_size=3072, vocab_size=50257)
    names = {}
    sweep = [
        (8, 1024, "full", False),   # flash via the gate (seq >= FLASH_MIN_SEQ)
        (8, 1024, "einsum", False),
        (1, 2048, "full", False),   # A/B pair at a batch dense can hold
        (1, 2048, "einsum", False),  # (b4 einsum keeps ~4.8 GB of residuals)
        (4, 2048, "full", False),   # flash-only capacity line
        # remat completes the b4 s2048 A/B dense can't otherwise hold:
        # per-layer rematerialization trades recompute for the O(L^2)
        # score residuals — the HBM lever measured inside a real step
        (4, 2048, "einsum", True),
        (4, 2048, "full", True),    # remat tax on the flash path, same shape
    ]
    for batch, seq, attn, remat in sweep:
        name = metric_name(batch, seq, attn, gpt2s, remat)
        names[(batch, seq, attn, remat)] = name
        bench_line(batch, seq, attn, gpt2s, metric=name, remat=remat)

    # scan_layers A/B at the headline shape: same math (loop-vs-scan
    # equality tested in tests/test_models.py), different compile
    # economics — compile_s is the column this pair exists for, and
    # step_ms answers whether lax.scan costs any runtime by inhibiting
    # inter-layer fusion. The persistent compilation cache would turn
    # compile_s into a cache-load time on warm reruns, so the PAIR runs
    # with the cache disabled — the loop twin recompiles cold too (one
    # extra compile is the price of an honest column).
    base = names[(8, 1024, "full", False)]
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for kw, suffix in [
            (gpt2s, "_coldcompile"),
            (dict(gpt2s, scan_layers=True), "_scanlayers"),
        ]:
            bench_line(8, 1024, "full", kw, metric=base + suffix)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)

    # bf16-logits lever: f32_logits=False skips the 1.65 GB f32
    # materialization of the [b, s, V] logits at b8 s1024 (the loss
    # reduces in f32 through a fused upcast instead); A/B against the
    # einsum twin above under the same metric-series convention
    # (compilation cache back ON — this pair compares step time, not
    # compile time)
    bench_line(8, 1024, "einsum", dict(gpt2s, f32_logits=False),
               metric=names[(8, 1024, "einsum", False)] + "_bf16logits")


if __name__ == "__main__":
    main()
