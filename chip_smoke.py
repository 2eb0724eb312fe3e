#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the main path once on a TPU through the entry points a user
calls, checks what comes out by the repo's own means, and prints as its
last line of standard output

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it. Any other ending is a failure: exit
code non-zero and no such line. There is no CPU branch — without a TPU
the first phase says which platform JAX initialised and the run ends
(``JAX_PLATFORMS=cpu python chip_smoke.py`` exits non-zero).

A chip belongs to one process at a time, and phase (d) needs a child to
own it, so this parent never imports jax: the phases run as sequential
child processes (``--phase NAME``) that share one persistent compile
cache (``utils/compile_cache.py``: ``JAX_COMPILATION_CACHE_DIR`` when
set, else ``<repo>/.jax_cache``). Every phase prints one JSON line naming
platform, device_kind, device count and jax version, its cache directory
with hit / miss counts, and what it measured. A failed check raises; no
phase's exception is caught and reported as a field.

  (a) clock     a chain of 4096^3 bf16 matmuls timed by the host clock
                around block_until_ready must land between half of and
                the whole published peak — above it the clock does not
                wait for the device. Licenses ``utils/devtime.timed``.
  (b) trainer   BERT-base at its published widths (132.4M parameters)
                through ``examples/train.py::build`` -> ``MPI_PS(adam,
                donate_buffers)`` -> ``Trainer.fit``, batch 16 x 128,
                8 steps; step-1 loss against ln(30521) and against one
                forward pass of the same parameters and batch on the
                host CPU; no compilation after step 2; peak HBM.
  (c) kernels   every Pallas kernel in ``ops/`` executed at the shapes
                the models and codecs dispatch it at, compared with its
                jnp reference on the chip, its compiled program holding
                a ``tpu_custom_call``.
  (d) async_ps  the async parameter server with the chip as a worker:
                ``examples/train_async.py`` (server on the CPU backend,
                in the phase's process) and ONE worker process that owns
                the chip; ResNet-18, int8 codec, shm transport; native
                libraries built in the run from ``native/*.cpp``.
  (e) multichip phase (b)'s job over four chips, allgather and leader,
                with placement and per-device checks — or
                ``skipped: 1 device``, the only skip there is. That the
                phase can open the chip at all is (d)'s last check: no
                orphan holds it.

``--dry-run`` (passed by hand, never inferred) runs the same phases at
tiny sizes on whatever backend there is, for debugging on a CPU. It
prints ``"dry_run": true``, never ``"ok": true``, and exits 10 when
every phase passed — so it can never be taken for a pass.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES = ("clock", "trainer", "kernels", "async_ps", "multichip")
TIME_LIMIT_S = 1150.0  # the contract's 1200 s, less start-up and exit
DRY_RUN_EXIT = 10


class SmokeFailure(RuntimeError):
    """A check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# parent: no jax here
# ---------------------------------------------------------------------------

def run_phase(phase: str, dry_run: bool, deadline: float) -> dict:
    """Run one phase in its own session; echo its stdout; return the
    JSON report on its last line. The whole session is killed on the way
    out, so nothing a phase started outlives it."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    if dry_run:
        cmd.append("--dry-run")
    proc = subprocess.Popen(cmd, cwd=HERE, stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    last = ""
    try:
        # a watchdog, not a poll: the phase's own output drives the loop
        signal.signal(signal.SIGALRM, _on_deadline)
        signal.alarm(max(1, int(deadline - time.monotonic())))
        for line in proc.stdout:
            print(line, end="", flush=True)
            if line.strip():
                last = line
        rc = proc.wait()
    finally:
        signal.alarm(0)
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    if rc != 0:
        raise SmokeFailure(f"phase {phase} exited {rc}")
    report = json.loads(last)
    check(report.get("phase") == phase, f"phase {phase} printed no report")
    return report


def _on_deadline(signum, frame):
    raise SmokeFailure(f"time limit of {TIME_LIMIT_S:.0f} s reached")


def parent(dry_run: bool) -> int:
    if not os.path.isdir(os.path.join(HERE, "pytorch_ps_mpi_tpu")):
        print("chip_smoke: the pytorch_ps_mpi_tpu package is not beside "
              "this script; run it from a checkout", file=sys.stderr)
        return 1
    deadline = time.monotonic() + TIME_LIMIT_S
    reports = {}
    try:
        for phase in PHASES:
            reports[phase] = run_phase(phase, dry_run, deadline)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    first = reports[PHASES[0]]
    device = {"platform": first["platform"], "kind": first["device_kind"],
              "count": first["device_count"]}
    if dry_run:
        print(json.dumps({"dry_run": True, "ok": False, "device": device,
                          "phases_passed": list(reports)}))
        return DRY_RUN_EXIT
    print(json.dumps({"ok": True, "device": device}))
    return 0


# ---------------------------------------------------------------------------
# children: one phase each
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts compile requests (cache hit or not) through jax.monitoring:
    one per program the backend was asked for."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def device_report() -> dict:
    import jax

    from importlib import metadata

    dev = jax.devices()[0]
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # reported, not relied on
        libtpu = None
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count(), "jax": jax.__version__,
            "libtpu": libtpu}


def require_tpu(dry_run: bool) -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not dry_run:
        raise SystemExit(
            f"chip_smoke: JAX initialised platform {dev.platform!r} "
            f"({dev.device_kind!r}); this check needs 'tpu'")


# -- (a) clock --------------------------------------------------------------

def phase_clock(dry_run: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu.utils.devtime import peak_flops_for, timed

    n, links = (256, 4) if dry_run else (4096, 64)
    x = jax.random.normal(jax.random.key(0), (n, n), jnp.bfloat16)
    # variance-preserving, so 64 links stay finite
    w = (jax.random.normal(jax.random.key(1), (n, n)) / n ** 0.5
         ).astype(jnp.bfloat16)

    @jax.jit
    def chain(x, w):
        for _ in range(links):
            x = x @ w
        return x

    secs = timed(lambda: chain(x, w), reps=5)
    t0 = time.perf_counter()
    y = chain(x, w)
    enqueue_s = time.perf_counter() - t0
    jax.block_until_ready(y)
    out = {"matmul": f"{links} x {n}^3 bf16", "chain_s": round(secs, 6),
           "enqueue_only_s": round(enqueue_s, 6)}
    if dry_run:
        return out  # a host timing is not written as a device rate
    peak = peak_flops_for()
    rate = links * 2 * n ** 3 / secs
    out.update(flops_per_s=rate, peak_flops_per_s=peak,
               fraction_of_peak=round(rate / peak, 4))
    check(rate <= peak, f"matmul control implies {rate:.3e} FLOP/s, above "
          f"the {peak:.3e} peak: the clock does not wait for the device")
    check(rate >= 0.5 * peak, f"matmul control reached {rate:.3e} FLOP/s, "
          f"under half of the {peak:.3e} peak")
    return out


# -- (b) trainer, and the job (e) re-runs -------------------------------------

def trainer_job(config: str, batch: int, steps: int, *, mesh=None,
                mode: str = "allgather", place=None,
                keep_initial: bool = False):
    """``examples/train.py``'s own path: build -> MPI_PS(adam,
    donate_buffers) -> Trainer.fit, one fit call per step so every
    step's loss and wall is seen. ``place`` maps a batch onto devices;
    ``keep_initial`` keeps a host copy of the initial parameters.
    Returns a namespace: trainer, loss_fn, first (batch), host_params,
    rows (per step: loss, seconds, compiles)."""
    import itertools
    import types

    import jax
    import numpy as np

    from examples.train import build
    from pytorch_ps_mpi_tpu import MPI_PS
    from pytorch_ps_mpi_tpu.trainer import Trainer

    params, loss_fn, data = build(config, batch)
    first = next(data)
    host_params = (jax.tree.map(np.asarray, params) if keep_initial
                   else None)
    opt = MPI_PS(params, optim="adam", mode=mode, mesh=mesh, average=True,
                 donate_buffers=True, lr=1e-4)
    del params  # donation demands no outside reference
    trainer = Trainer(opt, loss_fn)
    batches = itertools.chain([first], data)
    if place is not None:
        batches = map(place, batches)
    compiles = CompileCounter()
    rows = []
    for _ in range(steps):
        c0, t0 = compiles.count, time.perf_counter()
        summary = trainer.fit(batches, 1)
        rows.append({"loss": summary["final_loss"],
                     "seconds": round(time.perf_counter() - t0, 4),
                     "compiles": compiles.count - c0})
    compiles.close()
    return types.SimpleNamespace(trainer=trainer, loss_fn=loss_fn,
                                 first=first, host_params=host_params,
                                 rows=rows)


def check_losses(rows, expect: float | None) -> None:
    losses = [r["loss"] for r in rows]
    check(all(math.isfinite(l) for l in losses), f"non-finite loss: {losses}")
    if expect is not None:
        check(abs(losses[0] - expect) <= 1.0,
              f"step-1 loss {losses[0]:.4f} not within 1.0 of {expect:.4f}")
    late = [r["compiles"] for r in rows[2:]]
    check(not any(late), f"compilation after step 2: {late}")


def phase_trainer(dry_run: bool) -> dict:
    import jax
    import numpy as np

    config, batch, steps = (("mlp_mnist", 16, 4) if dry_run
                            else ("bert_mlm", 16, 8))
    job = trainer_job(config, batch, steps, keep_initial=True)
    rows = job.rows
    # uniform random targets over ids 1..30521: the loss starts at
    # ln(30521) and does not fall — that it falls is not asserted
    check_losses(rows, None if dry_run else math.log(30521))

    cpu = jax.devices("cpu")[0]
    loss_cpu = float(jax.jit(job.loss_fn)(
        jax.device_put(job.host_params, cpu), jax.device_put(job.first, cpu)))
    rel = abs(rows[0]["loss"] - loss_cpu) / abs(loss_cpu)
    check(rel <= 2e-2, f"step-1 loss {rows[0]['loss']:.5f} vs the CPU "
          f"forward pass {loss_cpu:.5f}: {rel:.2e} relative > 2e-2")

    after = jax.tree.leaves(job.trainer.opt.params)
    before = jax.tree.leaves(job.host_params)
    unchanged = sum(bool(np.array_equal(np.asarray(a), b))
                    for a, b in zip(after, before))
    check(unchanged == 0, f"{unchanged} of {len(before)} parameter leaves "
          "did not change")
    check(all(bool(np.isfinite(np.asarray(a)).all()) for a in after),
          "non-finite parameters")

    stats = jax.devices()[0].memory_stats()
    if not dry_run:
        check(bool(stats) and stats.get("peak_bytes_in_use"),
              "memory_stats() reports no peak_bytes_in_use")
    return {
        "config": config, "batch": batch,
        "n_params": int(sum(b.size for b in before)),
        "steps": rows, "first_step_s": rows[0]["seconds"],
        "step1_loss": rows[0]["loss"], "step1_loss_cpu": loss_cpu,
        "step1_rel_diff": rel,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use") if stats else None,
    }


# -- (c) kernels ----------------------------------------------------------------

def run_kernel(name: str, fn, args, dry_run: bool, min_calls: int = 1):
    """Compile ``fn``, require Mosaic kernels in the program, and run
    THAT program — so what is compared is what was inspected."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    if not dry_run:  # off-TPU the kernels run interpreted: no custom call
        n = compiled.as_text().count("tpu_custom_call")
        check(n >= min_calls, f"{name}: {n} tpu_custom_call in the compiled "
              f"program, want >= {min_calls} (a shape-dispatch to jnp "
              "cannot pass as the kernel)")
    return compiled(*args)


def verdicts(name: str, fn, *args) -> None:
    """``fn`` is jitted (one program per comparison, not one per jnp op)
    and returns {what: scalar bool}; every one must hold."""
    import jax

    for what, ok in jax.device_get(jax.jit(fn)(*args)).items():
        check(bool(ok), f"{name}: {what}")


def phase_kernels(dry_run: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.models.bert import BertConfig, SelfAttention
    from pytorch_ps_mpi_tpu.ops import (
        attention_pallas,
        quant_pallas,
        sign_pallas,
        tern_pallas,
        topk_pallas,
    )

    done = []
    key = jax.random.key(0)
    pow2 = 2 ** jnp.arange(8, dtype=jnp.int32)
    pow4 = 4 ** jnp.arange(4, dtype=jnp.int32)

    def near(a, b, tol):  # max |a - b| relative to max |b|
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.max(jnp.abs(a - b)) <= tol * jnp.maximum(
            jnp.max(jnp.abs(b)), 1e-6)

    # codec kernels at per-leaf gradient sizes: BERT FFN / ResNet conv5
    # (2,359,296: whole blocks) and BERT qkv (1,769,472: a ragged
    # trailing block in every kernel's grid)
    for n in ([8192, 5120] if dry_run else [2_359_296, 1_769_472]):
        x = jax.random.normal(jax.random.fold_in(key, n), (n,), jnp.float32)
        u = jax.random.bits(jax.random.fold_in(key, n + 1), (n,), jnp.uint32)

        # int8: SMEM absmax accumulated across the sequential grid
        q, scale = run_kernel(f"quantize_int8[{n}]",
                              quant_pallas.quantize_int8.__wrapped__, (x,),
                              dry_run, min_calls=2)
        deq = run_kernel(f"dequantize_int8[{n}]",
                         quant_pallas.dequantize_int8.__wrapped__,
                         (q, scale), dry_run)

        def int8_ok(x, q, scale, deq):
            q_ref, scale_ref = quant_pallas._quantize_jnp(x)
            dq = jnp.abs(q.astype(jnp.int32) - q_ref.astype(jnp.int32))
            return {
                "scale": jnp.abs(scale - scale_ref) <= 1e-6 * scale_ref,
                # a division rounded one ulp apart may move a .5 tie by
                # one code, nothing more
                "codes": (dq.max() <= 1) & ((dq > 0).mean() < 1e-4),
                "dequantize": jnp.array_equal(
                    deq, q.astype(jnp.float32) * scale),
            }

        verdicts(f"int8[{n}]", int8_ok, x, q, scale, deq)

        # sign: [rows, 8, 128] -> bit s of byte [r, lane]; encode_signs
        # also sums |x| in an SMEM scalar across the grid
        packed = run_kernel(f"pack_signs[{n}]", sign_pallas.pack_signs,
                            (x,), dry_run)
        packed2, total = run_kernel(f"encode_signs[{n}]",
                                    sign_pallas.encode_signs, (x,), dry_run)
        signs = run_kernel(f"unpack_signs[{n}]", sign_pallas.unpack_signs,
                           (packed,), dry_run)

        def sign_ok(x, packed, packed2, total, signs):
            bits = ((x.reshape(-1, 8, 128) >= 0).astype(jnp.int32)
                    * pow2[None, :, None]).sum(axis=1).astype(jnp.uint8)
            ref_total = jnp.sum(jnp.abs(x))
            return {
                "pack_signs": jnp.array_equal(packed, bits.reshape(-1)),
                "encode_signs bits": jnp.array_equal(packed2,
                                                     bits.reshape(-1)),
                "encode_signs |x| sum":
                    jnp.abs(total - ref_total) <= 1e-4 * ref_total,
                "unpack_signs": jnp.array_equal(
                    signs, jnp.where(x >= 0, 1.0, -1.0)),
            }

        verdicts(f"sign[{n}]", sign_ok, x, packed, packed2, total, signs)

        # ternary: [rows, 4, 128] -> base-4 digit s of byte [r, lane]
        s = jnp.max(jnp.abs(x))
        tern = run_kernel(f"tern_pack[{n}]", tern_pallas.tern_pack,
                          (x, u, s), dry_run)
        unp = run_kernel(f"tern_unpack[{n}]", tern_pallas.tern_unpack,
                         (tern, s), dry_run)

        def tern_ok(x, u, s, tern, unp):
            keep = ((u >> 8).astype(jnp.float32)
                    < jnp.abs(x) * (16777216.0 / s))
            digit = jnp.where(keep, jnp.where(x >= 0, 2, 0), 1)
            ref = (digit.astype(jnp.int32).reshape(-1, 4, 128)
                   * pow4[None, :, None]).sum(axis=1).astype(jnp.uint8)
            digits = (tern.reshape(-1, 128).astype(jnp.int32)[:, None, :]
                      // pow4[None, :, None]) % 4
            return {
                "tern_pack": (tern != ref.reshape(-1)).mean() < 1e-4,
                "tern_unpack": jnp.array_equal(
                    unp, (digits - 1).astype(jnp.float32).reshape(-1) * s),
            }

        verdicts(f"tern[{n}]", tern_ok, x, u, s, tern, unp)
        done.append(f"int8+sign+tern[{n}]")

    # exact top-k at 8M elements: SMEM count accumulators over 31
    # passes; the same value multiset as lax.top_k
    n, k = (16384, 160) if dry_run else (1 << 23, (1 << 23) // 100)
    x = jax.random.normal(jax.random.fold_in(key, 7), (n,), jnp.float32)
    vals, idx = run_kernel(
        f"exact_topk[{n}]",
        lambda x: topk_pallas.exact_topk.__wrapped__(x, k=k, chunk=2048),
        (x,), dry_run)

    def topk_ok(x, vals, idx):
        ref_vals, _ = jax.lax.top_k(jnp.abs(x), k)
        return {
            "the k largest magnitudes": jnp.array_equal(
                jnp.sort(jnp.abs(vals)), jnp.sort(ref_vals)),
            "values are x[indices]": jnp.array_equal(jnp.take(x, idx), vals),
        }

    verdicts(f"exact_topk[{n}]", topk_ok, x, vals, idx)
    done.append(f"exact_topk[{n}]")

    # flash attention forward and backward at the models' shapes: BERT
    # b16 s512 (a head is one 512x512 tile) and GPT-2-small b8 s1024
    # (unmasked: one 512x1024 tile a grid step; causal: 1024x1024 swept
    # in 512x512)
    for shape in ([(1, 128, 2, 64)] if dry_run
                  else [(16, 512, 12, 64), (8, 1024, 12, 64)]):
        for causal in (False, True):
            ks = jax.random.split(jax.random.fold_in(key, shape[1]), 4)
            q, kk, v, w = (jax.random.normal(kx, shape, jnp.bfloat16)
                           for kx in ks)

            def flash(q, kk, v):
                return attention_pallas.flash_attention(q, kk, v,
                                                        causal=causal)

            def dense(q, kk, v):
                return attention_pallas._attention_jnp(
                    q, kk, v, 0, 0, causal, shape[-1] ** -0.5)[0]

            def out_and_grads(attn, q, kk, v, w):
                def loss(q, kk, v):
                    out = attn(q, kk, v)
                    return jnp.sum((out * w).astype(jnp.float32)), out

                (_, out), grads = jax.value_and_grad(
                    loss, (0, 1, 2), has_aux=True)(q, kk, v)
                return out, grads

            tag = f"flash{list(shape)}{'_causal' if causal else ''}"
            got = run_kernel(tag, lambda *a: out_and_grads(flash, *a),
                             (q, kk, v, w), dry_run, min_calls=2)

            def flash_ok(got, q, kk, v, w):
                (out, (dq, dk, dv)) = got
                ref, (rq, rk, rv) = out_and_grads(dense, q, kk, v, w)
                return {"forward": near(out, ref, 3e-2),
                        "dq": near(dq, rq, 5e-2), "dk": near(dk, rk, 5e-2),
                        "dv": near(dv, rv, 5e-2)}

            verdicts(tag, flash_ok, got, q, kk, v, w)
            done.append(tag)

    # the models' own dispatch: attention='full' at GPT-2-small's shape
    # must take the kernel (two custom calls: forward, and dq, dk and dv
    # from the one backward kernel)
    b, l, hidden, heads = (1, 128, 128, 2) if dry_run else (8, 1024, 768, 12)
    cfgs = {a: BertConfig(hidden_size=hidden, num_heads=heads, causal=True,
                          dtype=jnp.bfloat16, attention=a, max_position=l)
            for a in ("full", "einsum")}
    xin = jax.random.normal(jax.random.fold_in(key, 11), (b, l, hidden),
                            jnp.bfloat16)
    attn_params = jax.jit(SelfAttention(cfgs["einsum"]).init)(key, xin)

    def attn_grads(a, p, xin):
        return jax.grad(lambda p, xin: jnp.sum(
            SelfAttention(cfgs[a]).apply(p, xin).astype(jnp.float32) ** 2),
            (0, 1))(p, xin)

    g_full = run_kernel(f"attention='full' s{l}",
                        lambda p, xin: attn_grads("full", p, xin),
                        (attn_params, xin), dry_run, min_calls=2)

    def full_ok(g_full, p, xin):
        # the einsum twin's softmax is bf16 itself: a loose bound
        return {f"gradient leaf {i} vs 'einsum'": near(a, r, 1e-1)
                for i, (a, r) in enumerate(zip(
                    jax.tree.leaves(g_full),
                    jax.tree.leaves(attn_grads("einsum", p, xin))))}

    verdicts(f"attention='full' s{l}", full_ok, g_full, attn_params, xin)
    done.append(f"attention_full_s{l}")

    # ThresholdCodec picks compaction='sort' on TPU and 'scatter'
    # elsewhere: run the chip's choice once against the other
    n = 8192 if dry_run else 2_359_296
    g = jax.random.normal(jax.random.fold_in(key, 13), (n,), jnp.float32)
    code = get_codec("threshold")
    if not dry_run:
        check(code.compaction == "sort", f"threshold codec chose "
              f"{code.compaction!r} on TPU")
    other = get_codec("threshold", compaction=(
        "scatter" if code.compaction == "sort" else "sort"))

    def threshold_ok(g):
        def roundtrip(c):
            payload, _ = c.encode(g, c.init_state(g.shape, g.dtype))
            return c.decode(payload, g.shape, g.dtype)

        a, b = roundtrip(code), roundtrip(other)
        return {"sort and scatter compaction decode alike":
                jnp.array_equal(a, b),
                "something survived": jnp.any(a != 0)}

    verdicts(f"threshold[{n}]", threshold_ok, g)
    done.append(f"threshold_{code.compaction}[{n}]")
    return {"kernels_ok": done}


# -- (d) the async parameter server with the chip as a worker ------------------

def phase_async_ps(dry_run: bool) -> dict:
    """This process is the SERVER: it must stay off the chip, so nothing
    here may initialise jax before ``examples/train_async`` has pinned
    the process to the CPU backend."""
    import shutil
    import tempfile

    # the worker gets the ambient placement (on the chip machine, the
    # chip); the default for every other worker in the repo stays cpu
    worker_env = {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "")}

    from examples import train_async  # pins this process to the CPU

    # built here from native/*.cpp, from nothing: what git does not
    # commit, a checkout does not have
    shutil.rmtree(os.path.join(HERE, "native", "_build"), ignore_errors=True)
    from pytorch_ps_mpi_tpu.parallel import dcn, tcp
    from pytorch_ps_mpi_tpu.utils import native

    for name, lib in (("libpsqueue", dcn.get_lib()),
                      ("libtcpps", tcp.get_lib()),
                      ("libwirecodec", native.get_lib())):
        check(lib is not None, f"{name} did not build and load")

    model, steps = ("mlp", 4) if dry_run else ("resnet18", 8)
    argv = ["--model", model, "--workers", "1", "--steps", str(steps),
            "--codec", "int8", "--transport", "shm", "--timeout", "600"]
    # the worker says which device it computed on, on stderr: route fd 2
    # through a file for the run, then hand it on
    with tempfile.TemporaryFile(mode="w+") as log:
        sys.stderr.flush()
        saved = os.dup(2)
        os.dup2(log.fileno(), 2)
        try:
            metrics = train_async.main(argv, worker_env=worker_env)
        finally:
            sys.stderr.flush()
            os.dup2(saved, 2)
            os.close(saved)
            log.seek(0)
            err = log.read()
            sys.stderr.write(err)
    said = [json.loads(line.split(": ", 1)[1]) for line in err.splitlines()
            if line.startswith("worker 0: {")]
    check(len(said) == 1, "worker 0 did not report its device")
    worker = said[0]
    if not dry_run:
        check(worker["platform"] == "tpu",
              f"the worker computed on {worker['platform']!r}, not the chip")
    import jax

    check(jax.devices()[0].platform == "cpu", "the server took a device")
    check(worker["pushed"] == steps, f"worker pushed {worker['pushed']}")
    check(int(metrics["applied"]) == steps,
          f"server applied {metrics['applied']} of {steps} pushes")
    check(math.isfinite(metrics["loss_final"]), "non-finite server loss")
    return {"model": model, "codec": "int8", "transport": "shm",
            "pushes": steps, "applied": int(metrics["applied"]),
            "loss_initial": metrics["loss_initial"],
            "loss_final": metrics["loss_final"],
            "native": ["libpsqueue", "libtcpps", "libwirecodec"],
            "worker": worker}


# -- (e) four chips ---------------------------------------------------------------

def phase_multichip(dry_run: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.mesh import make_mesh

    n_dev = jax.device_count()
    if n_dev < 4:
        return {"result": f"skipped: {n_dev} device"}
    devices = jax.devices()[:4]
    mesh4 = make_mesh(devices=devices)
    config, batch, steps = (("mlp_mnist", 64, 3) if dry_run
                            else ("bert_mlm", 64, 4))
    shard_rows = []
    t0 = time.perf_counter()

    def note(done: str) -> None:
        # the report is one line at the very end, four compiles of a
        # BERT step away: a run cut before it still says how far it got
        print(f"chip_smoke: multichip: {done} at "
              f"{time.perf_counter() - t0:.0f} s", file=sys.stderr, flush=True)

    def place(b):
        b = jax.device_put(b, NamedSharding(mesh4, jax.sharding.PartitionSpec(
            "data")))
        leaf = jax.tree.leaves(b)[0]
        shard_rows.append(sorted((s.device.id, s.data.shape[0])
                                 for s in leaf.addressable_shards))
        return b

    out = {"result": "ok", "global_batch": batch}
    one = trainer_job(config, batch, steps,
                      mesh=make_mesh(devices=devices[:1])).rows
    check_losses(one, None)
    out["one_chip"] = one
    note("one chip")
    for mode in ("allgather", "leader"):
        job = trainer_job(config, batch, steps, mesh=mesh4, mode=mode,
                          place=place)
        opt, rows = job.trainer.opt, job.rows
        check_losses(rows, None)
        check(opt.size == 4, f"opt.size == {opt.size}")
        want = [(d.id, batch // 4) for d in devices]
        check(all(r == sorted(want) for r in shard_rows),
              f"batch shards {shard_rows[-1]}, want {want}")
        for r4, r1 in zip(rows, one):
            rel = abs(r4["loss"] - r1["loss"]) / abs(r1["loss"])
            check(rel <= 1e-3, f"{mode}: loss {r4['loss']} vs one chip "
                  f"{r1['loss']}: {rel:.2e} relative > 1e-3")
        for leaf in jax.tree.leaves(opt.params):
            check(leaf.sharding.device_set == set(devices),
                  f"{mode}: a parameter leaf lives on "
                  f"{sorted(d.id for d in leaf.sharding.device_set)}")
            copies = [np.asarray(s.data) for s in leaf.addressable_shards]
            check(all(c.shape == leaf.shape and np.array_equal(c, copies[0])
                      for c in copies),
                  f"{mode}: parameter copies differ between devices")
        if mode == "leader":
            # ZeRO-1: every device holds one quarter of the Adam state
            for leaf in jax.tree.leaves(opt.opt_state):
                if leaf.ndim == 0:
                    continue
                sizes = {s.data.size for s in leaf.addressable_shards}
                check(sizes == {leaf.size // 4},
                      f"leader: optimizer-state shards of {sizes} elements "
                      f"for a leaf of {leaf.size}")
        if not dry_run:
            in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
            check(all(b > 0 for b in in_use), f"bytes_in_use {in_use}")
            out[f"{mode}_bytes_in_use"] = in_use
        out[mode] = rows
        shard_rows.clear()
        del job, opt
        note(mode)

    # one int8 step: payloads travel by all_gather
    from examples.train import build
    from pytorch_ps_mpi_tpu import MPI_PS

    params, loss_fn, data = build(config, batch)
    opt = MPI_PS(params, optim="adam", code=get_codec("int8"), mesh=mesh4,
                 average=True, lr=1e-4)
    loss, data_row = opt.step(loss_fn=loss_fn, batch=next(data))
    check(data_row["wire_lowering"] == "allgather",
          f"int8 wire lowering {data_row['wire_lowering']!r}")
    check(bool(jnp.isfinite(loss)), "int8 step: non-finite loss")
    rel = abs(float(loss) - one[0]["loss"]) / abs(one[0]["loss"])
    check(rel <= 1e-3, f"int8 step-1 loss {float(loss)} vs one chip "
          f"{one[0]['loss']}")
    out["int8_allgather_step1_loss"] = float(loss)
    return out


PHASE_FUNCS = {"clock": phase_clock, "trainer": phase_trainer,
               "kernels": phase_kernels, "async_ps": phase_async_ps,
               "multichip": phase_multichip}


def child(phase: str, dry_run: bool) -> int:
    sys.path.insert(0, HERE)
    t0 = time.perf_counter()
    from pytorch_ps_mpi_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    cache = enable_compilation_cache()
    if phase != "async_ps":  # the server process never touches the chip
        require_tpu(dry_run)
    out = PHASE_FUNCS[phase](dry_run)
    report = {"phase": phase, **device_report(),
              "compile_cache": cache.as_dict(),
              "phase_s": round(time.perf_counter() - t0, 2), **out}
    if dry_run:
        report["dry_run"] = True
    print(json.dumps(report), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=PHASES,
                    help="run one phase in this process (what the parent "
                         "starts; also a complete check of that phase)")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny sizes on any backend, for debugging; "
                         f"exits {DRY_RUN_EXIT}, never 0")
    args = ap.parse_args()
    if args.phase:
        return child(args.phase, args.dry_run)
    return parent(args.dry_run)


if __name__ == "__main__":
    sys.exit(main())
