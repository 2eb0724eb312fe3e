"""Train a TP-sharded transformer block with the drop-in optimizer.

The user-facing CLI for round 5's headline composition: a (data, model)
— optionally (data, seq, model) — mesh where Megatron column/row-
parallel attention + MLP keep their weights sharded over 'model', ring
attention (with --sp) shards the sequence, and ``MPI_PS(param_specs=…)``
drives the whole thing: per-device local gradients flow through the
codec pipeline, aggregate over the data axes only, and the optimizer
state (leader/ZeRO-1 included) stays sharded alongside the weights.
The numerics behind every path are pinned in
``tests/test_ps_model_parallel.py``.

The reference scaled workers only (`README.md:6` "models fit on one
device"); this script is the model axis as a one-command surface.

Examples:
  # 2-way data x 4-way tensor parallelism (virtual CPU mesh ok):
  python examples/train_tp.py --dp 2 --tp 4 --steps 3

  # the full 3-D mesh with a bf16 wire and ZeRO-1 sharded optimizer:
  python examples/train_tp.py --dp 2 --sp 2 --tp 2 --codec bf16 \
      --mode leader --steps 3
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
    ap.add_argument("--dp", type=int, default=2, help="data-parallel ways")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel ways (ring attention)")
    ap.add_argument("--tp", type=int, default=4,
                    help="tensor-parallel ways (devices = dp * sp * tp)")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=4,
                    help="global batch (must divide by --dp)")
    ap.add_argument("--seq", type=int, default=32,
                    help="sequence length (must divide by --sp)")
    ap.add_argument("--optim", choices=["sgd", "adam"], default="sgd")
    ap.add_argument("--mode", choices=["allgather", "leader"],
                    default="allgather",
                    help="leader = ZeRO-1 sharded optimizer state")
    ap.add_argument("--codec", default=None,
                    help="gradient codec (e.g. bf16, powersgd, topk)")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--ffn", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=512)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    n_need = args.dp * args.sp * args.tp

    # fail fast on pure-CLI mistakes before the backend initializes
    if args.batch % args.dp:
        print(f"--batch {args.batch} must divide by --dp {args.dp}",
              file=sys.stderr)
        sys.exit(2)
    if args.seq % args.sp:
        print(f"--seq {args.seq} must divide by --sp {args.sp}",
              file=sys.stderr)
        sys.exit(2)
    if args.heads % args.tp:
        print(f"--heads {args.heads} must divide by --tp {args.tp}",
              file=sys.stderr)
        sys.exit(2)
    if args.hidden % args.heads:
        print(f"--hidden {args.hidden} must divide by --heads {args.heads}",
              file=sys.stderr)
        sys.exit(2)
    if args.ffn % args.tp:
        print(f"--ffn {args.ffn} must divide by --tp {args.tp}",
              file=sys.stderr)
        sys.exit(2)

    from pytorch_ps_mpi_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()

    import jax

    if len(jax.devices()) < n_need:
        print(
            f"backend {jax.default_backend()!r} has {len(jax.devices())} "
            f"device(s) < dp*sp*tp={n_need}; re-run under a larger slice "
            "or use the virtual CPU mesh (JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_need})",
            file=sys.stderr,
        )
        sys.exit(2)

    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.mesh import make_mesh
    from pytorch_ps_mpi_tpu.parallel import tp as tpmod
    from pytorch_ps_mpi_tpu.ps import MPI_PS

    mesh = make_mesh(shape=(args.dp, args.sp, args.tp),
                     axis_names=("data", "seq", "model"),
                     devices=jax.devices()[:n_need])

    d, heads, ffn, vocab = args.hidden, args.heads, args.ffn, args.vocab
    seq, batch = args.seq, args.batch
    l_local = seq // args.sp

    k = jax.random.key(0)
    k_emb, k_pos, k_attn, k_mlp, k_head, k_tok = jax.random.split(k, 6)
    params = {
        "emb": 0.02 * jax.random.normal(k_emb, (vocab, d)),
        "pos": 0.02 * jax.random.normal(k_pos, (seq, d)),
        "attn": tpmod.init_tp_attention(k_attn, d, heads, args.tp),
        "mlp": tpmod.init_tp_mlp(k_mlp, d, ffn, args.tp),
        "head": 0.02 * jax.random.normal(k_head, (d, vocab)),
    }
    specs = {
        "emb": P(), "pos": P(),
        "attn": tpmod.tp_param_spec(params["attn"], "model"),
        "mlp": tpmod.tp_param_spec(params["mlp"], "model"),
        "head": P(),
    }
    tokens = jax.random.randint(k_tok, (batch, seq), 1, vocab)

    def loss_fn(p, toks):
        offset = lax.axis_index("seq") * l_local
        x = p["emb"][toks] + p["pos"][offset + jnp.arange(l_local)][None]
        x = x + tpmod.tp_self_attention(
            x, p["attn"], "model",
            seq_axis="seq" if args.sp > 1 else None,
            causal=False, local_grads=True,
        )
        x = x + tpmod.tp_mlp(x, p["mlp"], "model", local_grads=True)
        logits = x @ p["head"]
        ll = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(ll, toks[..., None], axis=-1)[..., 0]
        # local loss, STATIC global normalizer (the param_specs contract)
        return -ll.sum() / jnp.asarray(batch * seq, jnp.float32)

    agg = ("data", "seq") if args.sp > 1 else "data"
    batch_spec = P("data", "seq") if args.sp > 1 else P("data")
    opt = MPI_PS(
        params, optim=args.optim, lr=args.lr, mode=args.mode,
        code=get_codec(args.codec) if args.codec else None,
        mesh=mesh, axis_name=agg, param_specs=specs, batch_spec=batch_spec,
    )

    for step in range(args.steps):
        t0 = time.perf_counter()
        loss, data = opt.step(loss_fn=loss_fn, batch=tokens)
        print(json.dumps({
            "step": step,
            "loss": round(float(loss), 4),
            "step_s": round(time.perf_counter() - t0, 3),
            "mesh": f"{args.dp}x{args.sp}x{args.tp}",
            "mode": args.mode,
            "codec": args.codec or "identity",
            "wire_lowering": data["wire_lowering"],
            "wire_bytes_per_worker": data["wire_bytes_per_worker"],
        }), flush=True)

    w1 = opt.params["mlp"]["w1"]
    assert "model" in str(w1.sharding.spec), w1.sharding
    print(json.dumps({"done": True,
                      "backend": jax.default_backend(),
                      "tp_leaves_sharded_over": str(w1.sharding.spec)}),
          flush=True)


if __name__ == "__main__":
    main()
