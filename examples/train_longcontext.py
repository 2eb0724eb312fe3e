"""Train a causal LM at long sequence length with sequence parallelism.

The user-facing CLI for the context-parallel paths (`parallel/ring.py`,
`parallel/ulysses.py`): a GPT over a (data, seq) mesh where every device
holds one sequence shard, ring hops (or Ulysses all_to_alls) exchange
the K/V context, per-layer remat keeps activation memory flat, and the
data-parallel gradient psum rides the same fused step — the composition
`tests/test_longcontext.py` proves at seq 2048.

The reference scaled workers, never sequence (`README.md:6` "models fit
on one device" — SURVEY §5.7); this script is that missing axis as a
one-command surface.

Examples:
  # 8 sequence shards, seq 2048, ring attention (virtual CPU mesh ok):
  python examples/train_longcontext.py --seq 2048 --sp 8 --steps 3

  # 4-way data x 2-way sequence, Ulysses:
  python examples/train_longcontext.py --dp 4 --sp 2 --batch 4 \
      --attention ulysses
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--dp", type=int, default=1, help="data-parallel ways")
    ap.add_argument("--sp", type=int, default=8,
                    help="sequence-parallel ways (devices = dp * sp)")
    ap.add_argument("--attention", choices=["ring", "ulysses"],
                    default="ring")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=1,
                    help="global batch (must divide by --dp)")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--no-remat", action="store_true",
                    help="disable per-layer rematerialization")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    n_need = args.dp * args.sp

    # fail fast on pure-CLI mistakes before the backend initializes
    if args.batch % args.dp:
        print(f"--batch {args.batch} must divide by --dp {args.dp}",
              file=sys.stderr)
        sys.exit(2)
    if args.seq % args.sp:
        print(f"--seq {args.seq} must divide by --sp {args.sp}",
              file=sys.stderr)
        sys.exit(2)
    if args.attention == "ulysses" and args.heads % args.sp:
        # ulysses shards HEADS over the seq axis after its all_to_all
        print(f"--attention ulysses needs --heads {args.heads} divisible "
              f"by --sp {args.sp}", file=sys.stderr)
        sys.exit(2)

    from pytorch_ps_mpi_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()

    import jax

    if len(jax.devices()) < n_need:
        print(
            f"backend {jax.default_backend()!r} has {len(jax.devices())} "
            f"device(s) < dp*sp={n_need}; re-run under a larger slice or "
            "use the virtual CPU mesh (JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_need})",
            file=sys.stderr,
        )
        sys.exit(2)

    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.mesh import make_mesh
    from pytorch_ps_mpi_tpu.models import GPTLM, gpt_config
    from pytorch_ps_mpi_tpu.optim import (
        SGDHyper,
        init_sgd_state,
        sgd_update,
    )

    mesh = make_mesh(shape=(args.dp, args.sp), axis_names=("data", "seq"),
                     devices=jax.devices()[:n_need])
    l_local = args.seq // args.sp

    kw = dict(vocab_size=args.vocab, hidden_size=args.hidden,
              num_layers=args.layers, num_heads=args.heads,
              intermediate_size=2 * args.hidden, max_position=args.seq,
              remat=not args.no_remat)
    cfg = gpt_config(attention=args.attention, **kw)
    cfg_init = gpt_config(**kw)  # full-attention twin: same param tree,
    #                              init needs no bound mesh axis

    tokens = jax.random.randint(jax.random.key(1),
                                (args.batch, args.seq), 0, args.vocab)
    # init on a SHORT slice: parameter shapes depend only on the config
    # (vocab/max_position/hidden), and a full-length dense init forward
    # would materialize O(seq^2) scores on one device — the exact wall
    # this script exists to avoid
    init_toks = tokens[:1, : min(16, args.seq)]
    params = jax.jit(GPTLM(cfg_init).init)(jax.random.key(0), init_toks)
    opt_state = init_sgd_state(params)
    h = SGDHyper(lr=args.lr, momentum=args.momentum)
    model = GPTLM(cfg)

    def spmd(params, opt_state, toks):
        offset = lax.axis_index("seq") * l_local

        # the denominator is a compile-time constant (same local target
        # count on every shard): batch * (seq - sp) total targets
        den = float(args.batch * (args.seq - args.sp))

        def loss_fn(p):
            logits = model.apply(p, toks, position_offset=offset)
            # globally-normalized next-token CE. Targets are sliced PER
            # SHARD (position t predicts t+1 within the shard), so the
            # sp-1 cross-shard boundary predictions are excluded from
            # the objective — a deliberate simplification worth ~sp/seq
            # of the tokens (8/2048 = 0.4% at the defaults); loss values
            # are comparable across --sp only up to that. The MODEL
            # attends across shards fully (ring/ulysses); only the loss
            # slicing is shard-local.
            logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            ll = jnp.take_along_axis(logp, toks[:, 1:, None],
                                     axis=-1)[..., 0]
            num = lax.psum(ll.sum(), ("seq", "data"))
            return -num / den

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # one fused all-reduce over both mesh axes per leaf
        grads = jax.tree.map(lambda g: lax.psum(g, ("seq", "data")), grads)
        new_p, new_s = sgd_update(params, grads, opt_state, h)
        return new_p, new_s, loss

    step = jax.jit(
        jax.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), P(), P("data", "seq")),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )

    print(f"mesh=({args.dp}x{args.sp}) attention={args.attention} "
          f"seq={args.seq} (l_local={l_local}) remat={not args.no_remat} "
          f"backend={jax.default_backend()}", flush=True)
    t0 = time.time()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens)
        loss = float(loss)
        print(json.dumps({"step": i, "loss": round(loss, 4),
                          "wall_s": round(time.time() - t0, 2)}),
              flush=True)
        assert loss == loss, "loss is NaN"


if __name__ == "__main__":
    main()
