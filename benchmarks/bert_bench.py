"""BERT-base MLM — BASELINE config #5, the large-flat-gradient stress test.

Three sections, each labeled with the backend that ran it:

1. Single-device BERT-base (~110M params) MLM train step (Adam) with
   measured-FLOPs MFU — the headline model-compute number on the
   backend JAX initialised.
2. Distributed ``MPI_PS.step`` (fused grad → encode → psum → update) for
   the full 110M-param gradient on the 8-device virtual CPU mesh — a
   host number, *relative* evidence only (``chip_smoke.py`` phase (e)
   is the run on real chips).
3. The codec wire-bytes table for the ~110M-param flat gradient
   (the compression-curve evidence the reference's codings hook existed
   for, SURVEY §2.2), analytic from ``payload_bits`` plus, on a TPU,
   measured encode+decode time.

Run: ``python benchmarks/bert_bench.py [--seq 128] [--batch 16]``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the virtual CPU mesh for section 2 must be configured before JAX inits
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_ps_mpi_tpu.mesh import make_mesh
from pytorch_ps_mpi_tpu.models.bert import BertConfig, BertMLM, mlm_loss
from pytorch_ps_mpi_tpu.optim import AdamHyper, adam_update, init_adam_state
from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache
from pytorch_ps_mpi_tpu.utils.devtime import codec_roundtrip_seconds


def emit(**rec):
    rec.setdefault("backend", jax.default_backend())
    print(json.dumps(rec), flush=True)


def make_batch(key, batch, seq, vocab):
    tokens = jax.random.randint(key, (batch, seq), 0, vocab)
    targets = jax.random.randint(jax.random.fold_in(key, 1), (batch, seq), 0, vocab)
    mask = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.15, (batch, seq))
    return tokens, targets, mask


def single_device_bench(batch: int, seq: int, reps: int = 10,
                        attention: str = "full", f32_logits: bool = True):
    cfg = BertConfig(dtype=jnp.bfloat16, max_position=max(512, seq),
                     attention=attention, f32_logits=f32_logits)
    model = BertMLM(cfg)
    h = AdamHyper(lr=1e-4)

    def loss_fn(params, b):
        tokens, targets, mask = b
        return mlm_loss(model.apply(params, tokens), targets, mask)

    def train_step(params, state, b):
        loss, grads = jax.value_and_grad(loss_fn)(params, b)
        p2, s2 = adam_update(params, grads, state, h)
        return p2, s2, loss

    b = make_batch(jax.random.key(1), batch, seq, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.key(0), b[0][:1])
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    state = init_adam_state(params)

    # shared step-timing recipe (benchmarks/_stepbench.py)
    from benchmarks._stepbench import step_timing_fields

    fields = step_timing_fields(train_step, params, state, b, reps=reps)
    suffix = "" if attention == "full" else f"_attn-{attention}"
    suffix += "" if f32_logits else "_bf16logits"
    emit(
        metric=(f"bert_base_{n_params//10**6}M_mlm_train_step"
                f"_b{batch}_s{seq}{suffix}"),
        attention=attention,
        **fields,
    )
    return n_params


def distributed_bench(seq: int, reps: int = 3):
    """Full 110M-param fused grad+aggregate+update on the 8-device CPU
    mesh (relative evidence; the same program IS the multi-chip path)."""
    from pytorch_ps_mpi_tpu import Adam

    cpu_devices = jax.devices("cpu")
    if len(cpu_devices) < 8:
        emit(metric="bert_base_mpi_ps_step_8dev", error="no 8-device cpu mesh")
        return
    mesh = make_mesh(devices=cpu_devices[:8])
    cfg = BertConfig(max_position=max(512, seq))
    model = BertMLM(cfg)
    cpu0 = cpu_devices[0]
    with jax.default_device(cpu0):
        b = make_batch(jax.random.key(1), 8, seq, cfg.vocab_size)
        params = jax.jit(model.init)(jax.random.key(0), b[0][:1])
    # rehost: single-device-committed arrays conflict with the 8-device
    # shard_map placement; numpy leaves let the jitted step shard freely
    params = jax.tree.map(np.asarray, params)
    b = jax.tree.map(np.asarray, b)
    opt = Adam(params, lr=1e-4, mesh=mesh)

    def loss_fn(p, batch):
        tokens, targets, mask = batch
        return mlm_loss(model.apply(p, tokens), targets, mask)

    opt.step(loss_fn=loss_fn, batch=b)  # compile
    times = []
    for _ in range(reps):
        loss, data = opt.step(loss_fn=loss_fn, batch=b)
        times.append(data["step_time"])
    emit(
        metric="bert_base_mpi_ps_fused_step_8dev_cpu_mesh",
        value=round(min(times) * 1e3, 1), unit="ms",
        note="relative evidence: virtual 8-device CPU mesh on one host; "
        "same XLA program runs unchanged on a real 8-chip mesh",
        per_device_batch=1, seq=seq,
    )


def codec_table(n_params: int, measure: bool):
    """Wire bytes for the flat ~110M-param gradient, per codec; with
    ``measure`` also the encode+decode device time."""
    from pytorch_ps_mpi_tpu.codecs import get_codec

    rows = []
    n = (n_params // 1024) * 1024
    shape = (n // 1024, 1024)
    for label, name, kw in [
        ("identity", "identity", {}),
        ("int8", "int8", {}),
        ("sign", "sign", {}),
        ("qsgd16", "qsgd", {"levels": 16}),
        ("terngrad", "terngrad", {}),
        ("topk-approx-1%", "topk", {"fraction": 0.01, "approx": True}),
        ("blocktopk-1%", "blocktopk", {"fraction": 0.01}),
        ("blocktopk-1%-4k", "blocktopk", {"fraction": 0.01,
                                          "block_size": 4096}),
        ("blocktopk8-1%", "blocktopk8", {"fraction": 0.01}),
        ("randomk-1%", "randomk", {"fraction": 0.01}),
        ("threshold", "threshold", {"tau": 2.0, "max_fraction": 0.05}),
        ("powersgd-r4", "powersgd", {"rank": 4}),
    ]:
        code = get_codec(name, **kw)
        wire = code.payload_bits(shape, jnp.float32) / 8
        row = {"codec": label, "wire_mb": round(wire / 1e6, 2),
               "ratio": round(n * 4 / wire, 1)}
        if measure:
            row["enc_dec_ms_device"] = round(
                codec_roundtrip_seconds(code, shape, jnp.float32) * 1e3, 2)
            if name in ("topk", "blocktopk", "blocktopk8", "randomk",
                        "threshold"):
                # encode/decode split for the sparse family: the
                # doctrine's claim that REASSEMBLY (gather/scatter),
                # not selection, is what loses on ICI must be a
                # measurement, not an inference (CODEC_ECONOMICS.md)
                row["enc_ms_device"] = round(
                    codec_roundtrip_seconds(
                        code, shape, jnp.float32, phase="encode") * 1e3, 2)
        rows.append(row)
    emit(metric="bert_base_flat_grad_codec_wire_table", n_elems=n, rows=rows)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--skip-distributed", action="store_true")
    ap.add_argument("--codec-table-only", action="store_true",
                    help="run ONLY the 13-codec table")
    ap.add_argument("--skip-codec-table", action="store_true",
                    help="train lines only: the 13-codec 132M-element "
                         "table costs most of the run's wall")
    args = ap.parse_args()

    enable_compilation_cache()
    on_tpu = jax.default_backend() == "tpu"
    # param count analytically (eval_shape — no HBM), so the codec table
    # can run first against an EMPTY device memory (a 132M-element qsgd
    # encode plus resident BERT+Adam state OOMed the 16 GB chip)
    cfg = BertConfig()
    n_params = sum(
        int(np.prod(s.shape))
        for s in jax.tree.leaves(
            jax.eval_shape(
                BertMLM(cfg).init, jax.random.key(0),
                jnp.ones((1, args.seq), jnp.int32),
            )
        )
    )
    # measuring 110M-elem encodes on the host CPU takes minutes: off-TPU
    # the table is analytic only
    if not args.skip_codec_table:
        codec_table(n_params, measure=on_tpu)
    if args.codec_table_only:
        return
    if on_tpu:
        # flash-vs-einsum A/B at the headline shape, plus the long-seq
        # line the dense path collapses on. headline = 'full' (auto ->
        # flash on TPU from FLASH_MIN_SEQ up, bare metric name); einsum
        # row suffixed. s512/s2048 pairs chart where the O(L^2) dense
        # path falls off the flash curve; token budget is held
        # ~constant per line
        for b, s, attn in [
            (args.batch, args.seq, "full"),
            (args.batch, args.seq, "einsum"),
            (max(args.batch // 4, 1), 512, "full"),
            (max(args.batch // 4, 1), 512, "einsum"),
            (1, 2048, "full"),
            (1, 2048, "einsum"),
            # bigger batches amortize fixed per-step work — chart MFU
            # vs batch at the two headline sequence lengths
            (2 * args.batch, args.seq, "full"),
            (max(args.batch // 2, 1), 512, "full"),
        ]:
            single_device_bench(b, s, attention=attn)
        # bf16-logits lever on the biggest-logits config (b32 s128:
        # 500 MB of f32 [B,S,V] skipped) — the bert twin of the
        # gpt_bench A/B row
        single_device_bench(2 * args.batch, args.seq, f32_logits=False)
    else:
        single_device_bench(4, 64)
    if not args.skip_distributed:
        distributed_bench(args.seq)


if __name__ == "__main__":
    main()
