"""The synchronous job: ``MPI_PS`` + ``Trainer.fit`` over the cell's
devices, data-parallel, fed by a prefetching input pipeline.

One run: parameters from the seed in one jitted call on the device ->
warm-up until a step compiles nothing (the first three steps are kept
for the reference) -> the comparison with the float32 reference ->
the window -> on several chips, the equality of the parameter copies.

The window is a row of ``fit`` calls of ``steps_per_fit`` steps, each
timed by the host clock (``fit`` returns with its last loss fetched, so
a call is that many completed steps). The rate is the call's tokens
over the MEDIAN call: the host's cores are shared, and a neighbour's
burst or a stall of half a second moves the mean over the window by
percents where the median call stays within 0.6 % (PERF.md section 6,
call G). The mean is printed beside it in the row ``"check": "window"``.
``setup_s`` leaves out the seconds inside the first ``jax.devices()``,
the runtime attaching to the chip (5.8-9.1 s from run to run): nothing a
change to the repo moves.
"""

from __future__ import annotations

import importlib
import itertools
import math
import os
import threading
import time

from chipbench.jobs.common import (
    CompileCounter,
    clock_control,
    device_report,
    peak_bytes,
    say,
)
from chipbench.stats import percentile

MAX_WARM_STEPS = 8


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from pytorch_ps_mpi_tpu import MPI_PS, telemetry
    from pytorch_ps_mpi_tpu.data import prefetch
    from pytorch_ps_mpi_tpu.mesh import make_mesh
    from pytorch_ps_mpi_tpu.trainer import Trainer
    from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache

    cache = enable_compilation_cache()
    timeline = {"imported": time.perf_counter() - ctx.t0}
    devices = ctx.claim_devices(jax.devices())
    compiles = CompileCounter()
    timeline["devices"] = time.perf_counter() - ctx.t0
    attach_s = timeline["devices"] - timeline["imported"]

    def mark(what):
        timeline[what] = time.perf_counter() - ctx.t0

    config, traffic = ctx.config, ctx.traffic
    fam = importlib.import_module(
        f"chipbench.families.{config['family']}").build(config, traffic)
    rows = int(traffic["rows_per_chip"]) * len(devices)
    k_fit = int(traffic["steps_per_fit"])
    optimizer = dict(config["optimizer"])
    code = None
    if traffic.get("codec"):
        from pytorch_ps_mpi_tpu.codecs import get_codec

        code = get_codec(traffic["codec"], **traffic.get("codec_params", {}))

    params = jax.jit(fam.init)(jax.random.key(ctx.seed))
    # the reference's copies wait on the host: nothing of the benchmark's
    # stays on the device while the system's peak memory is read
    p0 = jax.device_get(params)
    mark("init")
    n_params = sum(a.size for a in jax.tree.leaves(params))
    mesh = make_mesh(devices=devices)
    opt = MPI_PS(params, optim=optimizer.pop("name"), code=code, mesh=mesh,
                 mode=traffic["mode"], average=True, donate_buffers=True,
                 bucket_mb=float(traffic["bucket_mb"]), **optimizer)
    del params  # donation demands no outside reference
    trainer = Trainer(opt, fam.loss_fn)
    mark("optimizer")

    host = fam.batches(ctx.seed, rows)
    first = [next(host) for _ in range(3)]
    sharding = NamedSharding(mesh, PartitionSpec("data"))
    annotate = jax.profiler.TraceAnnotation

    closing = threading.Event()

    def placed():
        for b in itertools.chain(first, host):
            if closing.is_set():
                return
            yield jax.device_put(b, sharding)

    def fed(stream):  # the consumer's wait for the pipeline, as a span
        while True:
            with annotate("data.next"):
                b = next(stream)
            yield b

    threads_before = set(threading.enumerate())
    pipeline = prefetch(placed(), depth=2)
    stream = fed(pipeline)

    # -- warm-up: steps 1-3 are the ones the reference repeats ---------------
    losses, p1 = [], None
    for i in range(MAX_WARM_STEPS):
        before = compiles.count
        losses.append(trainer.fit(stream, 1)["final_loss"])
        compiled = compiles.count - before
        if i == 0:
            p1 = jax.device_get(jax.tree.map(
                lambda a: a.addressable_shards[0].data, opt.params))
        mark(f"step{i + 1}")
        if i >= 2 and not compiled:
            break
    else:
        raise SystemExit(f"a step still compiles after {MAX_WARM_STEPS}")
    trainer.fit(stream, k_fit)  # the window's own call shape, once
    buffers_peak = peak_bytes(devices)
    mark("warm")

    verdict = against_reference(fam, config, optimizer["lr"], devices,
                                p0, p1, first, losses[:3])
    say(check="reference", **verdict)
    del p0, p1
    mark("reference")

    # -- the window ---------------------------------------------------------------
    rec = telemetry.configure() if ctx.trace else None
    trace_dir = os.path.join(ctx.scratch, "trace")
    jax.block_until_ready(opt.params)
    t_open_wall, t_open_mono = time.time(), time.monotonic()
    t_open = time.perf_counter()
    setup_s = t_open - ctx.t0 - attach_s
    say(check="setup", seconds_since_start=timeline, attach_s=attach_s,
        process_start_to_window_s=t_open - ctx.t0)
    steps = failed = 0
    calls = []  # host seconds of each fit call
    traced_calls = int(traffic["trace_fit_calls"]) if ctx.trace else 0
    if traced_calls:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # TraceAnnotations only
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    while True:
        t_call = time.perf_counter()
        with annotate("fit.call"):
            loss = trainer.fit(stream, k_fit)["final_loss"]
        calls.append(time.perf_counter() - t_call)
        steps += k_fit
        if not math.isfinite(loss):
            failed += k_fit  # a non-finite loss poisons every later step
        if traced_calls and steps == traced_calls * k_fit:
            jax.block_until_ready(opt.params)
            jax.profiler.stop_trace()
        if (time.perf_counter() - t_open >= ctx.seconds
                and steps >= traced_calls * k_fit):
            break
    jax.block_until_ready(opt.params)
    elapsed = time.perf_counter() - t_open
    in_window = compiles.between(t_open_wall, time.time())
    # stop the pipeline's thread before the interpreter goes: it may be in
    # the middle of a device_put
    closing.set()
    pipeline.close()
    for t in set(threading.enumerate()) - threads_before:
        t.join(timeout=10.0)

    copies_equal = replicas_equal(opt.params, devices)
    # the runtime's peak counts buffers, not what a running program holds
    # besides (PERF.md section 3): XLA's own buffer assignment says that
    memory = opt.step_memory_analysis(
        fam.loss_fn, jax.device_put(first[0], sharding))
    system_peak = buffers_peak + memory["temp_size_in_bytes"]
    say(check="memory", buffers_peak=buffers_peak, step_program=memory,
        runtime=[d.memory_stats() for d in devices[:1]])
    finite = all(math.isfinite(l) for l in losses)
    per_call = k_fit * rows * fam.units_per_row
    say(check="window", steps=steps, elapsed_s=elapsed, failed=failed,
        compiles_in_window=in_window, copies_equal=copies_equal,
        warm_up_losses=losses, compile_cache=cache.as_dict(),
        rate_over_window=steps * rows * fam.units_per_row / elapsed,
        call_s={f"p{q}": percentile(calls, q) for q in (0, 25, 50, 75, 100)},
        calls_ms=[round(1e3 * c, 2) for c in calls])

    peaks = None
    if ctx.trace and devices[0].platform == "tpu":
        from chipbench.flops import peaks_for

        peaks = peaks_for(devices[0].device_kind)
        control = clock_control(peaks["flops_bf16"])
        say(check="clock", **control)
        if not control["ok"]:
            raise SystemExit("the clock control is outside 50-100% of peak")

    spans = {}
    if rec is not None:
        spans["trainer.step"] = [e for e in rec.events()
                                 if e["name"] == "trainer.step"
                                 and e["ts"] >= t_open_mono]
        telemetry.disable()
    return {
        "correct": bool(verdict["ok"] and finite and copies_equal
                        and in_window == 0 and failed == 0),
        "attempted": steps, "failed": failed,
        "end_to_end": {f"{fam.unit}_per_s": per_call / percentile(calls, 50),
                       "setup_s": setup_s},
        "device": device_report(devices, system_peak),
        "trace_dir": trace_dir if traced_calls else None,
        "spans": spans,
        "counters": {
            "compiles_in_window": in_window,
            "cache_misses": cache.misses, "peak_hbm_bytes": system_peak,
            "steps": steps, "chips": len(devices),
            "wire_bytes_per_update": wire_bytes(opt, code, n_params,
                                                len(devices)),
        },
        "shape": dict(fam.shape, rows=rows, head_dim=fam.head_dim,
                      dtype_bytes=fam.dtype_bytes),
        "peaks": peaks,
    }


def replicas_equal(params, devices) -> bool:
    """Every device's copy of every parameter leaf, brought to the first
    device and compared there bit for bit."""
    import jax
    import jax.numpy as jnp

    if len(devices) == 1:
        return True

    def copy_on(dev):
        return jax.tree.map(lambda a: next(
            s.data for s in a.addressable_shards if s.device == dev), params)

    same = jax.jit(lambda a, b: jnp.all(jnp.stack(
        [jnp.array_equal(x, y) for x, y in zip(jax.tree.leaves(a),
                                               jax.tree.leaves(b))])))
    mine = copy_on(devices[0])
    return all(bool(same(mine, jax.device_put(copy_on(d), devices[0])))
               for d in devices[1:])


def wire_bytes(opt, code, n_params: int, chips: int):
    """Exact payload bytes one chip contributes to one update: the
    codec's ``payload_bits`` per leaf, or 4 bytes a parameter for the
    float32 all-reduce; nothing travels on one chip."""
    import jax

    if chips == 1:
        return 0
    if code is None:
        return 4 * n_params
    return sum(code.payload_bits(a.shape, a.dtype)
               for a in jax.tree.leaves(opt.params)) // 8


def against_reference(fam, config, lr, devices, p0, p1, first, losses) -> dict:
    """Steps 1-3 of the system against the plain reference on one device:
    the three losses, and step 1's parameter change against the
    reference's own Adam update (see the configuration's ``guarantees``
    and ``tolerances``). Data-parallel training means: every chip takes
    the loss of its own rows (its own masked mean), and losses and
    gradients are averaged over the chips — so the reference does that,
    chip by chip, on the same rows in the same order."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference.transformer import Adam, BlockedLoss

    tol = config["tolerances"]
    p0, p1 = jax.device_put((p0, p1), devices[0])
    ref = BlockedLoss(fam.reference_terms, fam.reference_block_rows)
    adam = Adam(p0, lr)
    p, ref_losses, g1, p1_ref = p0, [], None, None
    device, n = devices[0], len(devices)
    mean = jax.jit(lambda *xs: jax.tree.map(lambda *a: sum(a) / n, *xs))
    for i, b in enumerate(first):
        b = jax.device_put(b, device)
        per_chip = jax.tree.leaves(b)[0].shape[0] // n
        loss, g = mean(*[ref(p, jax.tree.map(
            lambda a: a[c * per_chip:(c + 1) * per_chip], b))
            for c in range(n)])
        ref_losses.append(float(loss))
        if i < 2:
            p = adam.update(p, g)
        if i == 0:
            g1, p1_ref = g, p

    @jax.jit
    def compare(p0, p1, p1_ref, g1):
        agree = n = err2 = ref2 = 0.0
        for a0, a1, ar, g in zip(*map(jax.tree.leaves, (p0, p1, p1_ref, g1))):
            mag = jnp.abs(g)
            m = mag > jnp.mean(mag)
            ds, dr = a1 - a0, ar - a0
            agree += jnp.sum(m & (jnp.sign(ds) == jnp.sign(dr)))
            n += jnp.sum(m)
            err2 += jnp.sum(jnp.where(m, (ds - dr) ** 2, 0.0))
            ref2 += jnp.sum(jnp.where(m, dr ** 2, 0.0))
        return agree / n, jnp.sqrt(err2 / ref2)

    sign_share, rel_l2 = map(float, compare(p0, p1, p1_ref, g1))
    loss_rel = max(abs(s - r) / abs(r) for s, r in zip(losses, ref_losses))
    return {"ok": (loss_rel <= tol["loss_rel"]
                   and sign_share >= tol["update_sign_share"]
                   and rel_l2 <= tol["update_rel_l2"]),
            "losses": losses, "reference_losses": ref_losses,
            "loss_rel": loss_rel, "update_sign_share": sign_share,
            "update_rel_l2": rel_l2}
