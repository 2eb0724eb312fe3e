"""Headline benchmarks on the accelerator, labeled with the device that
ran them.

Emits one JSON line per metric, each carrying ``platform``,
``device_kind``, ``device_count`` and the jax version. The run needs a
TPU: without one it exits non-zero and prints no metric — it does not
time the host CPU in the chip's place, and it replays no earlier
number. Any failure (a kernel, a model line, XLA's cost analysis)
raises and ends the run non-zero.

Line 1 — gradient aggregation + fused SGD update latency, the reference's
entire job (encode/serialize per-parameter gradients, exchange across
workers, sum, step — ``ps.py:103-193``) for a ResNet-18-sized gradient set
(~11M params, ~60 tensors, 8 workers):

- **reference-style baseline**: the reference's host pipeline re-created
  in numpy/pickle (its wire: per-param pickle of each worker's ndarray,
  blosc level-0 = framing only so a byte-copy, ``mpi_comms.py:18-26``;
  then per-param unpickle → 8-way sum → eager momentum-SGD update loop,
  ``ps.py:161-214``). Network transfer is *excluded* — this is the purely
  local serialize/decode/sum/update cost the reference pays even on
  localhost. A sanity floor, not the TPU story.
- **ours**: the same aggregation semantics as one fused XLA program on
  the accelerator (identity codec ``decode_sum`` + fused ``sgd_update`` —
  exactly the code path ``MPI_PS.step`` runs per chip, where multi-chip
  meshes add one ICI psum).

Lines 2–3 — end-to-end ResNet-18 training step (fwd+bwd+update)
steps/sec, f32 then bf16 compute, with measured-FLOPs MFU (XLA cost
analysis / step time / bf16 peak for the device kind). ``vs_baseline``
of line 2 compares against the same XLA program compiled for the host
CPU backend — the BASELINE.md steps/sec anchor.

Line 4 — BERT-base MLM (132M params, Adam), bf16 compute.

Timing is the host clock around ``block_until_ready``
(``utils/devtime.timed``, min of ``REPS``); ``chip_smoke.py`` phase (a)
checks that clock against a known-FLOPs matmul control on every run.
The Pallas kernels are executed and compared with their references by
``chip_smoke.py`` phase (c), not here.
"""

from __future__ import annotations

import json
import pickle
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._stepbench import step_timing_fields
from pytorch_ps_mpi_tpu.codecs import IdentityCodec
from pytorch_ps_mpi_tpu.models import ResNet18
from pytorch_ps_mpi_tpu.optim import SGDHyper, init_sgd_state, sgd_update
from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache
from pytorch_ps_mpi_tpu.utils.devtime import device_kind, timed

WORKERS = 8
REPS = 20
TRAIN_BATCH = 256


def emit(metric: str, value: float, unit: str, vs_baseline: float,
         **extra) -> None:
    dev = jax.devices()[0]
    rec = {
        "metric": metric,
        "value": round(value, 4),
        "unit": unit,
        "vs_baseline": round(vs_baseline, 2),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "jax": jax.__version__,
    }
    rec.update(extra)
    print(json.dumps(rec), flush=True)


# ---------------------------------------------------------------------------
# Line 1: aggregation + update microbench
# ---------------------------------------------------------------------------

def param_structs():
    """Parameter ShapeDtypeStructs via tracing only — no device ops."""
    model = ResNet18(num_classes=10, small_inputs=True)
    return jax.eval_shape(
        lambda k: model.init(k, jnp.ones((1, 32, 32, 3), jnp.float32)),
        jax.random.key(0),
    )


def reference_style_step(np_params, np_bufs, worker_msgs, lr=0.01, momentum=0.9):
    """One aggregation+update step the reference's way: per-param unpickle
    of every worker's message, numpy sum, eager momentum SGD."""
    for i, msgs in enumerate(worker_msgs):
        grads = [pickle.loads(m) for m in msgs]          # ps.py:166, mpi_comms.py:174
        d_p = grads[0].copy()
        for g in grads[1:]:
            d_p += g                                     # ps.py:176 sum(grads)
        buf = np_bufs[i]
        buf *= momentum
        buf += d_p                                       # ps.py:207-208
        np_params[i] -= lr * buf                         # ps.py:214


def run_reference_baseline(shapes):
    rng = np.random.RandomState(0)
    stacked = [rng.randn(WORKERS, *s).astype(np.float32) for s in shapes]
    np_params = [np.zeros(s, np.float32) for s in shapes]
    np_bufs = [np.zeros_like(p) for p in np_params]
    times = []
    for _ in range(max(3, REPS // 4)):
        t0 = time.perf_counter()
        # encode/serialize side (overlapped with backprop in the reference,
        # but still CPU work it must do): pickle each worker's each tensor
        worker_msgs = [
            [pickle.dumps(s[w]) for w in range(WORKERS)] for s in stacked
        ]
        reference_style_step(np_params, np_bufs, worker_msgs)
        times.append(time.perf_counter() - t0)
    return min(times)


def run_ours(structs):
    code = IdentityCodec()
    h = SGDHyper(lr=0.01, momentum=0.9)
    leaves, treedef = jax.tree.flatten(structs)

    @jax.jit
    def materialize(key):
        keys = jax.random.split(key, len(leaves))
        grads_stacked = jax.tree.unflatten(
            treedef,
            [
                jax.random.normal(k, (WORKERS,) + s.shape, jnp.float32)
                for k, s in zip(keys, leaves)
            ],
        )
        params = jax.tree.unflatten(
            treedef, [jnp.zeros(s.shape, jnp.float32) for s in leaves]
        )
        return params, init_sgd_state(params), grads_stacked

    @jax.jit
    def step(params, state, grads_stacked):
        summed = jax.tree.map(
            lambda g, p: code.decode_sum(g, p.shape, p.dtype), grads_stacked, params
        )
        return sgd_update(params, summed, state, h)

    params, state, grads_stacked = materialize(jax.random.key(0))
    return timed(lambda: step(params, state, grads_stacked), reps=REPS)


# ---------------------------------------------------------------------------
# Line 2: end-to-end ResNet-18 train step, steps/sec + MFU
# ---------------------------------------------------------------------------

def make_train_step(dtype=jnp.float32):
    # f32 params either way; dtype is the conv/matmul compute precision
    # (bf16 is the MXU's native width — the TPU-first configuration)
    model = ResNet18(num_classes=10, small_inputs=True, dtype=dtype)
    h = SGDHyper(lr=0.01, momentum=0.9)

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    def train_step(params, state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, new_state = sgd_update(params, grads, state, h)
        return new_params, new_state, loss

    return model, train_step


def run_train_bench(dtype=jnp.float32, cpu_anchor=True):
    """Returns (step-timing fields, cpu_step_seconds_or_None)."""
    model, train_step = make_train_step(dtype)
    x = jax.random.normal(jax.random.key(1), (TRAIN_BATCH, 32, 32, 3))
    y = jax.random.randint(jax.random.key(2), (TRAIN_BATCH,), 0, 10)
    params = jax.jit(model.init)(jax.random.key(0), x[:1])
    state = init_sgd_state(params)
    fields = step_timing_fields(train_step, params, state, (x, y), reps=REPS)

    # CPU anchor: the identical program on the host backend
    cpu_s = None
    if cpu_anchor:
        cpu = jax.devices("cpu")[0]
        on_cpu = jax.device_put((params, state, (x, y)), cpu)
        fn = jax.jit(train_step)
        cpu_s = timed(lambda: fn(*on_cpu), reps=3)
    return fields, cpu_s


def _telemetry_dir():
    """``BENCH_TELEMETRY_DIR=dir python bench.py`` arms the run-wide
    FlightRecorder (bench takes no CLI args by design — the env var is
    the flag): each bench phase records a span, and the run drops
    ``bench.jsonl`` + a per-phase ``report.txt`` in the directory."""
    import os

    tdir = os.environ.get("BENCH_TELEMETRY_DIR")
    if not tdir:
        return None
    os.makedirs(tdir, exist_ok=True)
    from pytorch_ps_mpi_tpu import telemetry

    telemetry.configure(worker="bench")
    return tdir


def _telemetry_flush(tdir):
    if not tdir:
        return
    import os

    from pytorch_ps_mpi_tpu import telemetry
    from tools.telemetry_report import format_table, summarize

    rec = telemetry.get_recorder()
    path = rec.dump_jsonl(os.path.join(tdir, "bench.jsonl"))
    report = format_table(summarize([path]))
    with open(os.path.join(tdir, "report.txt"), "w") as f:
        f.write(report + "\n")
    print(f"telemetry: {path} + report.txt", flush=True)


def main():
    enable_compilation_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU: JAX initialised platform {platform!r} "
            f"({device_kind()!r}); no metric was measured")
    tdir = _telemetry_dir()
    from pytorch_ps_mpi_tpu.telemetry import span

    structs = param_structs()
    shapes = [s.shape for s in jax.tree.leaves(structs)]
    n_params = sum(int(np.prod(s)) for s in shapes)

    with span("bench.reference_baseline"):
        ref_s = run_reference_baseline(shapes)
    with span("bench.aggregation_update"):
        ours_s = run_ours(structs)
    emit(
        f"resnet18_{n_params//10**6}M_grad_aggregation_sgd_update_ms",
        ours_s * 1e3,
        "ms",
        ref_s / ours_s,
        baseline="reference-style numpy/pickle pipeline on this host CPU",
    )

    with span("bench.train_step_f32"):
        f32, cpu_s = run_train_bench()
    emit(
        f"resnet18_train_step_b{TRAIN_BATCH}_steps_per_sec",
        vs_baseline=cpu_s * f32["value"],
        baseline="same XLA program on host CPU backend",
        **f32,
    )

    # the TPU-first configuration — bf16 compute (f32 params), the MXU's
    # native precision
    with span("bench.train_step_bf16"):
        bf16, _ = run_train_bench(jnp.bfloat16, cpu_anchor=False)
    emit(
        f"resnet18_train_step_b{TRAIN_BATCH}_bf16_steps_per_sec",
        vs_baseline=bf16["value"] / f32["value"],
        baseline="same model with f32 compute (line 2) on this device",
        **bf16,
    )

    # BASELINE config #5 — BERT-base MLM (132M params, Adam), bf16
    # compute: the large-flat-gradient stress configuration
    with span("bench.bert_mlm"):
        bert_line()
    _telemetry_flush(tdir)


BERT_BATCH, BERT_SEQ = 16, 128


def bert_line(batch: int = BERT_BATCH, seq: int = BERT_SEQ) -> None:
    from pytorch_ps_mpi_tpu.models import BertConfig, BertMLM
    from pytorch_ps_mpi_tpu.models.bert import mlm_loss
    from pytorch_ps_mpi_tpu.optim import AdamHyper, adam_update, init_adam_state

    cfg = BertConfig(dtype=jnp.bfloat16, max_position=max(512, seq))
    model = BertMLM(cfg)
    h = AdamHyper(lr=1e-4)

    def loss_fn(params, b):
        tokens, targets, mask = b
        return mlm_loss(model.apply(params, tokens), targets, mask)

    def train_step(params, state, b):
        loss, grads = jax.value_and_grad(loss_fn)(params, b)
        p2, s2 = adam_update(params, grads, state, h)
        return p2, s2, loss

    key = jax.random.key(1)
    b = (
        jax.random.randint(key, (batch, seq), 0, cfg.vocab_size),
        jax.random.randint(jax.random.fold_in(key, 1), (batch, seq), 0,
                           cfg.vocab_size),
        jax.random.bernoulli(jax.random.fold_in(key, 2), 0.15, (batch, seq)),
    )
    params = jax.jit(model.init)(jax.random.key(0), b[0][:1])
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    fields = step_timing_fields(train_step, params, init_adam_state(params),
                                b, reps=5)
    emit(
        f"bert_base_{n_params//10**6}M_mlm_train_step_b{batch}_s{seq}"
        "_bf16_steps_per_sec",
        vs_baseline=fields["mfu"],
        baseline="vs_baseline = MFU vs the chip's published bf16 peak "
                 "(BASELINE config #5, the large-flat-gradient stress "
                 "model; full codec wire table in benchmarks/bert_bench.py)",
        **fields,
    )


if __name__ == "__main__":
    main()
