"""``sync_train``'s run for a configuration whose state fills most of a
chip: the window, the rate, ``setup_s``, the traced calls and the counters
are ``sync_train.run``'s own statements — this module calls it with
``against_reference`` substituted and adds two counters — and only the
comparison with the plain reference differs.

``sync_train.against_reference`` keeps about eleven float32 copies of the
parameters on the first device beside the system's three. Here the
system's state stays where it is, untouched, and beside it the device
holds at most ONE float32 copy of the parameters (the reference's,
step by step), one group of leaves' gradient (``GROUP_BYTES``) and one
leaf's Adam state at a time: every other copy (``p0``, ``p1``, the
reference's Adam moments, its next parameters) waits on the host and is
streamed leaf by leaf; the gradient is taken group by group (one forward
and backward pass a group), the rows of a batch one at a time.

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

GROUP_BYTES = 0.8e9  # of gradient leaves taken in one backward pass


def run(ctx) -> dict:
    import importlib

    # a checkout whose program lacks the model fails here, before the
    # chip is attached
    importlib.import_module(f"chipbench.families.{ctx.config['family']}")
    from chipbench.jobs import sync_train

    counters, unscoped = {}, {}

    def compare(fam, config, lr, devices, p0, p1, first, losses):
        verdict, more = against_reference(fam, config, lr, devices, p0, p1,
                                          first, losses)
        counters.update(more)
        unscoped.update(getattr(fam, "unscoped", {}))
        return verdict

    # the step program's text, taken where sync_train asks the optimizer
    # for its memory analysis (after the window): a trace event names its
    # instruction, the text names the instruction's scope
    from pytorch_ps_mpi_tpu import MPI_PS

    def analysis(opt, *args, **kw):
        out = memory_analysis(opt, *args, **kw)
        if ctx.trace:
            counters["scopes"] = instruction_scopes(
                opt.step_program_text(), unscoped)
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


def leaf_groups(leaves, limit: float = GROUP_BYTES) -> list[list[int]]:
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


def against_reference(fam, config, lr, devices, p0, p1, first, losses):
    """(verdict, counters). ``p0`` / ``p1`` are host copies of the
    system's parameters before and after step 1, ``first`` the batches of
    steps 1-3, ``losses`` the system's losses of those steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference.transformer import Adam

    tol, ref, rcfg = config["tolerances"], fam.reference, fam.reference_cfg
    device = devices[0]
    host, treedef = jax.tree.flatten(p0)
    after = jax.tree.leaves(p1)
    paths = [jax.tree_util.keystr(k)
             for k, _ in jax.tree_util.tree_leaves_with_path(p0)]
    groups = leaf_groups(host)
    put = lambda tree: jax.device_put(tree, device)

    def rows_of(batch):
        n = jax.tree.leaves(batch)[0].shape[0]
        return [put(jax.tree.map(lambda a: a[r:r + 1], batch))
                for r in range(n)]

    terms = jax.jit(lambda leaves, sub: ref.terms(
        jax.tree.unflatten(treedef, leaves), sub, rcfg))

    def grad_of(group):
        def total(part, leaves, sub):
            leaves = list(leaves)
            for j, a in zip(group, part):
                leaves[j] = a
            return ref.terms(jax.tree.unflatten(treedef, leaves), sub, rcfg)

        return jax.jit(jax.value_and_grad(total, has_aux=True))

    grads_of = [grad_of(g) for g in groups]
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    scale = jax.jit(lambda g, d: jax.tree.map(lambda x: x / d, g),
                    donate_argnums=0)
    in_use = {}  # the most bytes on the device at the comparison's points

    def held(what):
        now = (device.memory_stats() or {}).get("bytes_in_use", 0)
        in_use[what] = max(in_use.get(what, 0), now)

    # the reference's Adam: its jitted update and its constants; its
    # moments are kept here, on the host, leaf by leaf (None: zeros)
    adam = Adam((), lr)
    moments = [None] * len(host)

    def adam_leaf(j, p, g, t):
        size = adam.lr * (1 - adam.b2 ** t) ** 0.5 / (1 - adam.b1 ** t)
        m, v = moments[j] or (jnp.zeros_like(p), jnp.zeros_like(p))
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
        sys_loads_of = jax.jit(lambda leaves, b: fam.router_loads(
            jax.tree.unflatten(treedef, leaves), b))
        ref_loads_of = jax.jit(lambda leaves, sub: ref.router_loads(
            jax.tree.unflatten(treedef, leaves), sub, rcfg))

    stats = {}           # leaf path -> update_stats of step 1, on the host
    ref_losses, sys_loads, ref_loads = [], [], []
    for i, batch in enumerate(first):
        subs = rows_of(batch)
        held("the system's state alone")
        leaves = put(host)                       # the one float32 copy
        if has_router:
            sys_loads.append(np.asarray(sys_loads_of(leaves, put(batch))))
            ref_loads.append(np.asarray(sum(ref_loads_of(leaves, s)
                                            for s in subs)))
        if i == 2:
            total, count = map(sum, zip(*[terms(leaves, s) for s in subs]))
            ref_losses.append(float(total / count))
            continue
        new_host = list(host)
        for group, grad in zip(groups, grads_of):
            part = [leaves[j] for j in group]
            total = count = g = None
            for s in subs:
                (t, c), gs = grad(part, leaves, s)
                held("with the reference's copy and a group's gradient")
                total, count, g = ((t, c, gs) if g is None else
                                   (total + t, count + c, add(g, gs)))
                del gs
            g = scale(g, count)
            for j, gj in zip(group, g):
                new = adam_leaf(j, leaves[j], gj, i + 1)
                if i == 0:
                    stats[paths[j]] = jax.device_get(update_stats(
                        leaves[j], put(after[j]), new, gj))
                new_host[j] = jax.device_get(new)
            del g, part
        ref_losses.append(float(total / count))
        host = new_host
        del leaves

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
               "device_bytes_in_use": in_use}
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
