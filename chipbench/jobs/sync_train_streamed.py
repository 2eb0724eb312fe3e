"""``sync_train``'s run for a configuration whose state fills most of a
chip: the window, the rate, ``setup_s``, the traced calls and the counters
are ``sync_train.run``'s own statements — this module calls it with
``against_reference`` substituted and adds two counters — and only the
comparison with the plain reference differs.

``sync_train.against_reference`` keeps about eleven float32 copies of the
parameters on the first device. Here the comparison runs, as every
comparison does, after the window on a chip the system has left, and
holds no more of it than the system's own peak (``budget``): ONE float32
copy of the parameters (the reference's, kept on the device through the
three steps and written leaf by leaf by its Adam) and the gradient of
one group of leaves: all of them, one forward and backward pass a row,
where that fits beside the program's temporaries. Adam's moments wait
on the host, leaf by leaf. ``p0`` and ``p1`` are taken from the caller
and each leaf is dropped once it is on the device or read; a step's
gradient waits on the host only where it took several groups. The rows
of a batch are taken one at a time.

It decides the guarantees of the other synchronous cells — the loss of
steps 1-3, and step 1's parameter change against the reference's own Adam
update (share of equal signs and relative L2 error on the entries whose
reference gradient is above its leaf's mean magnitude) — and, for a
family with an expert layer (``fam.router_loads``):

- the same two numbers in the worst single expert's matrices (a skipped
  expert is 1/16 of a leaf and would hide in the sum over all leaves);
- the pairs routed to each held expert on the batches of steps 1-3: the
  program's router (``fam.router_loads``, bf16 compute) and the
  reference's (float32) on the SAME parameters (the reference's, step by
  step) must agree up to ties: in every layer the sum over held experts
  of |difference| is at most ``router_tie_share`` x positions.

Beside each limit the row ``"check": "reference"`` carries what it would
read had the system kept its parameters in bf16 (``if_bf16_params``: the
system's step-1 parameters rounded to bf16), the reading the limits are
set against (PERF.md section 4).
"""

from __future__ import annotations

import os


def run(ctx) -> dict:
    import importlib

    # a checkout whose program lacks the model fails here, before the
    # chip is attached
    importlib.import_module(f"chipbench.families.{ctx.config['family']}")
    from chipbench.jobs import sync_train

    counters, text = {}, {}

    def compare(fam, config, lr, devices, kept, first, losses, budget=None):
        if ctx.trace:
            counters["scopes"] = instruction_scopes(
                text.pop("step"), getattr(fam, "unscoped", {}))
        verdict, more = against_reference(fam, config, lr, devices, kept,
                                          first, losses, budget)
        counters.update(more)
        return verdict

    # the step program's text, taken where sync_train asks the optimizer
    # for its memory analysis (after the window, before the comparison):
    # a trace event names its instruction, the text names the
    # instruction's scope
    from pytorch_ps_mpi_tpu import MPI_PS

    def analysis(opt, *args, **kw):
        out = memory_analysis(opt, *args, **kw)
        if ctx.trace:
            text["step"] = opt.step_program_text()
        return out

    memory_analysis, MPI_PS.step_memory_analysis = (
        MPI_PS.step_memory_analysis, analysis)
    theirs, sync_train.against_reference = (sync_train.against_reference,
                                            compare)
    try:
        result = sync_train.run(ctx)
    finally:
        sync_train.against_reference = theirs
        MPI_PS.step_memory_analysis = memory_analysis
    result["counters"].update(counters)
    return result


def instruction_scopes(text: str | None, unscoped: dict) -> dict:
    """Instruction name -> the innermost ``jax.named_scope`` it was traced
    under ('moe.experts', 'attn.bd', ...), for the instructions of an
    optimized HLO text that have one: a scope is a dotted lower-case
    component of the instruction's ``op_name``, bare or inside a
    transformation's brackets (``jvp(moe.route)``,
    ``transpose(jvp(moe.combine))``). A fusion carries its root's.
    Instructions XLA makes itself lose the path (a ragged dot's
    ``op_name`` is "ragged-dot-none", a sort's "sort"): ``unscoped`` (the
    family's) maps such an ``op_name`` to the scope that owns it."""
    import re

    scopes = {}
    line = re.compile(r'^\s*(?:ROOT )?(%[\w.\-]+) = .*op_name="([^"]*)"')
    scope = re.compile(r"(?:^|[/(])([a-z_]+(?:\.[a-z_]+)+)(?=[/)]|$)")
    for m in filter(None, map(line.match, (text or "").splitlines())):
        found = scope.findall(m[2])
        if found or m[2] in unscoped:
            scopes[m[1]] = found[-1] if found else unscoped[m[2]]
    return scopes


def leaf_groups(leaves, limit: float) -> list[list[int]]:
    """Consecutive leaves packed into groups of at most ``limit`` bytes
    (a larger leaf is a group of its own)."""
    groups, size = [[]], 0
    for j, a in enumerate(leaves):
        if groups[-1] and size + a.nbytes > limit:
            groups.append([])
            size = 0
        groups[-1].append(j)
        size += a.nbytes
    return groups


def host_rss() -> int:
    """Bytes of this process resident in host memory now; 0 where
    ``/proc`` does not say."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return 0


def bytes_in_use(device):
    """Bytes the device holds now; None where its backend keeps no
    count (XLA:CPU)."""
    stats = device.memory_stats()
    return stats.get("bytes_in_use", 0) if stats else None


def against_reference(fam, config, lr, devices, kept, first, losses,
                      budget=None):
    """(verdict, counters). ``kept`` holds host copies of the system's
    parameters before and after step 1 (``p0``, ``p1``): this takes them
    out of it and drops each leaf once it is on the device or read.
    ``first`` holds the batches of steps 1-3, ``losses`` the system's
    losses of those steps, ``budget`` the system's own peak bytes on the
    device (None or 0: no limit), under which the comparison sizes its
    leaf groups."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference.transformer import Adam

    tol, ref, rcfg = config["tolerances"], fam.reference, fam.reference_cfg
    device = devices[0]
    if bytes_in_use(device) is None:
        budget = None
    p0 = kept.pop("p0")
    paths = [jax.tree_util.keystr(k)
             for k, _ in jax.tree_util.tree_leaves_with_path(p0)]
    host, treedef = jax.tree.flatten(p0)
    after = jax.tree.leaves(kept.pop("p1"))
    del p0
    put = lambda tree: jax.device_put(tree, device)

    in_use, rss = {}, {}  # the most at the comparison's points
    rss_before, peak = host_rss(), {"bytes": 0}

    def held(what, temp=0):
        """Device bytes in use and host bytes resident now; ``temp``:
        what the program that just ran held beside its arguments and
        outputs (its temporaries), for the device's peak."""
        now = bytes_in_use(device) or 0
        in_use[what] = max(in_use.get(what, 0), now)
        rss[what] = max(rss.get(what, 0), host_rss())
        peak["bytes"] = max(peak["bytes"], now + temp)

    # the reference's parameters: ONE float32 copy, on the device through
    # the three steps; step 1's Adam writes it leaf by leaf
    leaves = []
    for j, a in enumerate(host):
        leaves.append(put(a))
        host[j] = None
    del host, a
    param_bytes = sum(a.nbytes for a in leaves)
    held("the reference's parameters")

    def rows_of(batch):
        n = jax.tree.leaves(batch)[0].shape[0]
        return [put(jax.tree.map(lambda a: a[r:r + 1], batch))
                for r in range(n)]

    def compiled(fn, *args):
        """``fn`` compiled for ``args``, and the bytes it needs beside
        them: its temporaries, and its outputs that take no argument's
        place."""
        program = jax.jit(fn).lower(*args).compile()
        m = program.memory_analysis()
        if m is None:
            return program, 0, 0
        return (program, m.temp_size_in_bytes,
                m.output_size_in_bytes - m.alias_size_in_bytes)

    def grad_of(group):
        def total(part, leaves, sub):
            leaves = list(leaves)
            for j, a in zip(group, part):
                leaves[j] = a
            return ref.terms(jax.tree.unflatten(treedef, leaves), sub, rcfg)

        return compiled(jax.value_and_grad(total, has_aux=True),
                        [leaves[j] for j in group], leaves, row)

    row = rows_of(first[0])[0]
    summed = jax.tree.leaves(first[0])[0].shape[0] > 1  # rows' gradients
    base = bytes_in_use(device) or 0

    def plan(limit):
        """Leaf groups of at most ``limit`` bytes, their compiled
        gradients, and the most bytes one group's gradient needs beside
        the parameters: its program's temporaries and outputs, and the
        sum over the rows."""
        groups = leaf_groups(leaves, limit)
        grads = [grad_of(g) for g in groups]
        need = max(t + o for _, t, o in grads) + summed * max(
            sum(leaves[j].nbytes for j in g) for g in groups)
        return groups, grads, need

    def group_limit(temp):
        """The most gradient bytes a group may have where its program
        needs ``temp`` beside the parameters and the gradient: all of
        them where the budget holds that, else what it leaves, in at
        most about eight groups (each is a compile of its own, and the
        chip holds more than the system's peak)."""
        room = (budget - base - temp) / (1 + summed) if budget else param_bytes
        return param_bytes if room >= param_bytes else max(
            room, param_bytes / 8)

    # the loss alone (step 3's) is compiled first: its temporaries are
    # fewer than a gradient program's, so they tell beforehand where one
    # group cannot fit, and that group is not compiled
    terms = compiled(lambda leaves, sub: ref.terms(
        jax.tree.unflatten(treedef, leaves), sub, rcfg), leaves, row)
    groups, grads_of, need = plan(group_limit(terms[1]))
    if budget and len(groups) == 1 and base + need > budget:
        groups, grads_of, need = plan(group_limit(grads_of[0][1]))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    scale = jax.jit(lambda g, d: jax.tree.map(lambda x: x / d, g),
                    donate_argnums=0)

    # the reference's Adam: its jitted update and its constants; its
    # moments per leaf, on the host (None: zeros)
    adam = Adam((), lr)
    moments = [None] * len(leaves)

    def adam_leaf(j, p, g, t):
        size = adam.lr * (1 - adam.b2 ** t) ** 0.5 / (1 - adam.b1 ** t)
        m, v = (put(moments[j]) if moments[j] is not None
                else (jnp.zeros_like(p), jnp.zeros_like(p)))
        p, m, v = adam._step(p, g, m, v, jnp.float32(size))
        moments[j] = jax.device_get((m, v))
        return p

    @jax.jit
    def update_stats(a0, a1, ar, g):
        """Per slice of the leading axis (an expert, where the leaf is an
        expert stack): entries compared, squared reference change, and
        signs agreeing and squared error for the system's parameters as
        they are and rounded to bf16."""
        mag = jnp.abs(g)
        m = mag > jnp.mean(mag)
        dr = ar - a0
        axes = tuple(range(1, a0.ndim))
        out = {"n": jnp.sum(m, axes),
               "ref2": jnp.sum(jnp.where(m, dr ** 2, 0.0), axes)}
        # reduce_precision, not a cast there and back: XLA may keep the
        # excess precision of a convert pair
        for tag, a in (("", a1), ("_bf16", jax.lax.reduce_precision(
                a1, exponent_bits=8, mantissa_bits=7))):
            ds = a - a0
            out["agree" + tag] = jnp.sum(
                m & (jnp.sign(ds) == jnp.sign(dr)), axes)
            out["err2" + tag] = jnp.sum(jnp.where(m, (ds - dr) ** 2, 0.0), axes)
        return out

    has_router = hasattr(fam, "router_loads")
    if has_router:
        sys_loads_of = compiled(lambda leaves, b: fam.router_loads(
            jax.tree.unflatten(treedef, leaves), b), leaves, put(first[0]))
        ref_loads_of = compiled(lambda leaves, sub: ref.router_loads(
            jax.tree.unflatten(treedef, leaves), sub, rcfg), leaves, row)
    del row

    def call(what, program, *args):
        out = program[0](*args)
        jax.block_until_ready(out)
        held(what, program[1])  # its outputs are in use now
        return out

    stats = {}           # leaf path -> update_stats of step 1, on the host
    ref_losses, sys_loads, ref_loads = [], [], []
    for i, batch in enumerate(first):
        subs = rows_of(batch)
        if has_router:
            sys_loads.append(np.asarray(call(
                "the program's router", sys_loads_of, leaves, put(batch))))
            ref_loads.append(np.asarray(sum(
                call("the reference's router", ref_loads_of, leaves, s)
                for s in subs)))
        if i == 2:
            total, count = map(sum, zip(*[
                call("the reference's loss", terms, leaves, s)
                for s in subs]))
            ref_losses.append(float(total / count))
            continue
        # every group's gradient before any parameter moves: on the
        # device where it is one group, else on the host
        grads = [None] * len(leaves)
        for group, grad in zip(groups, grads_of):
            part = [leaves[j] for j in group]
            total = count = g = None
            for s in subs:
                (t, c), gs = call("a group's gradient", grad, part, leaves, s)
                total, count, g = ((t, c, gs) if g is None else
                                   (total + t, count + c, add(g, gs)))
                del gs
            g = scale(g, count)
            if len(groups) > 1:
                g = jax.device_get(g)
            for j, gj in zip(group, g):
                grads[j] = gj
            del g, part
        ref_losses.append(float(total / count))
        for j in range(len(leaves)):
            gj = put(grads[j])
            grads[j] = None
            new = adam_leaf(j, leaves[j], gj, i + 1)
            if i == 0:
                stats[paths[j]] = jax.device_get(update_stats(
                    leaves[j], put(after[j]), new, gj))
                after[j] = None
            leaves[j] = new
            del new, gj
        held("after Adam")
    del leaves, moments

    def read(rows, tag=""):
        """(share of equal signs, relative L2 error) over ``rows`` of
        statistics."""
        n, ref2, agree, err2 = (sum(float(np.sum(r[k])) for r in rows)
                                for k in ("n", "ref2", "agree" + tag,
                                          "err2" + tag))
        return agree / n, (err2 / ref2) ** 0.5

    def worst_expert(tag=""):
        """The same two numbers in the worst single expert: its three
        matrices of one layer together."""
        layers = {}
        for path, row in stats.items():
            if "experts" in path:
                layers.setdefault(path.split("experts")[0], []).append(row)
        # an expert few positions chose has a gradient far under its
        # leaf's mean magnitude: few entries to compare, each near Adam's
        # eps. Experts with under a quarter of the mean entries are left
        # to the sum over all leaves
        reads = []
        for rows in layers.values():
            n = sum(r["n"] for r in rows)
            reads += [read([{k: v[e] for k, v in r.items()} for r in rows], tag)
                      for e in range(len(n)) if n[e] >= max(1, n.mean() / 4)]
        return (min(s for s, _ in reads), max(l for _, l in reads)) \
            if reads else (1.0, 0.0)

    everything = list(stats.values())
    sign_share, rel_l2 = read(everything)
    expert_sign, expert_l2 = worst_expert()
    bf16_all, bf16_expert = read(everything, "_bf16"), worst_expert("_bf16")
    loss_rel = max(abs(s - r) / abs(r) for s, r in zip(losses, ref_losses))
    ok = (loss_rel <= tol["loss_rel"]
          and sign_share >= tol["update_sign_share"]
          and rel_l2 <= tol["update_rel_l2"]
          and expert_sign >= tol.get("worst_expert_sign_share", 0.0)
          and expert_l2 <= tol.get("worst_expert_rel_l2", float("inf")))
    verdict = {"losses": losses, "reference_losses": ref_losses,
               "loss_rel": loss_rel, "update_sign_share": sign_share,
               "update_rel_l2": rel_l2,
               "worst_expert_sign_share": expert_sign,
               "worst_expert_rel_l2": expert_l2,
               "if_bf16_params": {
                   "update_sign_share": bf16_all[0],
                   "update_rel_l2": bf16_all[1],
                   "worst_expert_sign_share": bf16_expert[0],
                   "worst_expert_rel_l2": bf16_expert[1]},
               "leaf_groups": len(groups),
               "device_bytes_in_use": in_use,
               # what the device held beside the reference's parameters
               # when the comparison began: what the system left there
               "device_beside_params_bytes": base and base - param_bytes,
               "device_peak_bytes": peak["bytes"],
               "budget_bytes": budget,
               "host_rss_bytes": rss,
               "host_rss_before_bytes": rss_before,
               "host_bytes_per_param_above_before": (
                   max(rss.values()) - rss_before) / (param_bytes / 4)}
    counters = {}
    if has_router:
        sys_loads, ref_loads = np.stack(sys_loads), np.stack(ref_loads)
        positions = 2 * fam.units_per_row * jax.tree.leaves(first[0])[0].shape[0]
        # [steps, layers]: pairs on the wrong side of a tie, over positions
        tie_share = np.abs(sys_loads - ref_loads).sum(-1) / positions
        ok = ok and bool(tie_share.max() <= tol["router_tie_share"])
        verdict.update(
            router_tie_share=float(tie_share.max()),
            router_loads_step1=sys_loads[0].tolist(),
            reference_router_loads_step1=ref_loads[0].tolist())
        counters = {
            "moe_pairs_held_per_step": float(sys_loads.sum((1, 2)).mean()),
            "moe_load_max_over_mean": float(
                (sys_loads.max(-1) / sys_loads.mean(-1)).mean())}
    return dict(verdict, ok=bool(ok)), counters
