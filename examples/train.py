"""Train any BASELINE config end-to-end from the command line.

The driving script the reference kept in a sibling research repo
(SURVEY: "the driving train script ... imports this package"), made part
of the framework. Synthetic data (zero-egress environment); every knob of
the optimizer surface is exposed.

Examples:
  python examples/train.py --config mlp_mnist --steps 50
  python examples/train.py --config resnet18_cifar10 --codec topk --codec-arg fraction=0.01
  python examples/train.py --config bert_mlm --optim adam --lr 1e-3 --mode leader
  python examples/train.py --config resnet50_imagenet --steps 10 --batch 32
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu import MPI_PS
from pytorch_ps_mpi_tpu.codecs import get_codec
from pytorch_ps_mpi_tpu.data import cross_entropy_loss, synthetic_images, synthetic_mlm
from pytorch_ps_mpi_tpu.models import MLP, BertConfig, BertMLM, ResNet18, ResNet50
from pytorch_ps_mpi_tpu.models.bert import mlm_loss
from pytorch_ps_mpi_tpu.trainer import Trainer
from pytorch_ps_mpi_tpu.utils.compile_cache import enable_compilation_cache

CONFIGS = ["mlp_mnist", "resnet18_cifar10", "resnet50_imagenet", "bert_mlm",
           "switch_mlm", "gpt_lm"]


def build(config: str, batch: int, seed: int = 0, remat: bool = False,
          scan_layers: bool = False):
    """Returns (params, loss_fn, batch_iterator)."""
    key = jax.random.key(seed)
    if config == "switch_mlm":
        from pytorch_ps_mpi_tpu.models import SwitchConfig, SwitchMLM

        scfg = SwitchConfig(vocab_size=8192, hidden_size=256, num_layers=4,
                            num_heads=8, intermediate_size=512, n_experts=8,
                            max_position=128)
        model = SwitchMLM(scfg)
        data = synthetic_mlm(batch, seq_len=128, vocab_size=scfg.vocab_size)
        b0 = next(data)
        params = model.init(key, b0["tokens"])
        def loss_fn(p, b):
            return mlm_loss(model.apply(p, b["tokens"]), b["targets"], b["mask"])
        return params, loss_fn, data
    if config == "gpt_lm":
        from pytorch_ps_mpi_tpu.data import synthetic_lm
        from pytorch_ps_mpi_tpu.models import GPTLM, causal_lm_loss, gpt_config

        gcfg = gpt_config(vocab_size=8192, hidden_size=256, num_layers=4,
                          num_heads=8, intermediate_size=1024,
                          max_position=256, remat=remat,
                          scan_layers=scan_layers)
        model = GPTLM(gcfg)
        data = synthetic_lm(batch, seq_len=128, vocab_size=gcfg.vocab_size)
        b0 = next(data)
        params = model.init(key, b0["tokens"])
        def loss_fn(p, b):
            return causal_lm_loss(model.apply(p, b["tokens"]), b["tokens"])
        return params, loss_fn, data
    if config == "mlp_mnist":
        model = MLP(features=(128, 10))
        data = synthetic_images("mnist", batch)
        x0, _ = next(data)
        params = model.init(key, x0)
        def loss_fn(p, b):
            x, y = b
            return cross_entropy_loss(model.apply(p, x), y)
        return params, loss_fn, data
    if config == "resnet18_cifar10":
        model = ResNet18(num_classes=10, small_inputs=True)
    elif config == "resnet50_imagenet":
        model = ResNet50(num_classes=1000)
    else:
        cfg = dataclasses.replace(BertConfig.base(), remat=remat,
                                  scan_layers=scan_layers)
        model = BertMLM(cfg)
        data = synthetic_mlm(batch, seq_len=128, vocab_size=cfg.vocab_size)
        b0 = next(data)
        params = model.init(key, b0["tokens"])
        def loss_fn(p, b):
            return mlm_loss(model.apply(p, b["tokens"]), b["targets"], b["mask"])
        return params, loss_fn, data
    name = "cifar10" if config == "resnet18_cifar10" else "imagenet"
    data = synthetic_images(name, batch)
    x0, _ = next(data)
    params = model.init(key, x0)
    def loss_fn(p, b):
        x, y = b
        return cross_entropy_loss(model.apply(p, x), y)
    return params, loss_fn, data


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", choices=CONFIGS, default="mlp_mnist")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--optim", choices=["sgd", "adam", "adafactor"],
                    default="sgd")
    # default=None is the explicit-lr sentinel: sniffing sys.argv for the
    # literal "--lr" missed --lr=0.05 and argparse prefix forms and
    # silently discarded the user's rate on the adafactor path
    ap.add_argument("--lr", type=float, default=None,
                    help="learning rate (default 0.01; adafactor with no "
                         "explicit --lr and no schedule uses the paper's "
                         "relative step size)")
    ap.add_argument("--lr-schedule", choices=["constant", "warmup_cosine",
                                              "step_decay"], default=None,
                    help="in-program lr schedule over --lr (evaluated on "
                         "the traced step counter; no recompiles)")
    ap.add_argument("--warmup-steps", type=int, default=0)
    ap.add_argument("--decay-boundaries", default="",
                    help="comma ints for step_decay, e.g. 100,200")
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--clip-norm", type=float, default=0.0,
                    help="clip the aggregated gradient to this global "
                         "L2 norm (0 = off)")
    ap.add_argument("--weight-decay", type=float, default=0.0)
    ap.add_argument("--adamw", action="store_true",
                    help="decoupled weight decay (AdamW) instead of "
                         "torch-style coupled L2 (adam only)")
    ap.add_argument("--mode", choices=["allgather", "leader"], default="allgather")
    ap.add_argument("--codec", default=None,
                    help="identity|bf16|f16|topk|randomk|int8|qsgd|sign|terngrad|"
                         "powersgd|threshold|ef")
    ap.add_argument("--codec-arg", action="append", default=[],
                    help="k=v passed to the codec (repeatable)")
    ap.add_argument("--bucket-mb", type=float, default=0.0,
                    help="fuse per-leaf collectives into ~N MB "
                         "dtype-grouped flat buckets (0 = per-leaf; see "
                         "docs/OPERATIONS.md 'Gradient bucketing')")
    ap.add_argument("--bf16-comm", action="store_true",
                    help="bfloat16 gradient collectives")
    ap.add_argument("--donate", action="store_true",
                    help="donate params/state buffers to XLA (in-place "
                         "device update; ~one params+state copy less HBM)")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize transformer layers in backward "
                         "(bert_mlm / gpt_lm configs)")
    ap.add_argument("--scan-layers", action="store_true",
                    help="lax.scan over a stacked layer body: one "
                         "layer's HLO to compile instead of L copies "
                         "(bert_mlm / gpt_lm configs)")
    ap.add_argument("--scan-chunk", type=int, default=1,
                    help=">1 fuses N steps per XLA program")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--instrument", action="store_true",
                    help="per-stage timing metrics")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--telemetry-dir", default=None,
                    help="enable the run-wide FlightRecorder; dumps "
                         "train.jsonl (tools/telemetry_report.py reads it) "
                         "into this directory at exit")
    args = ap.parse_args(argv)
    enable_compilation_cache()
    explicit_lr = args.lr is not None
    if args.lr is None:
        args.lr = 0.01
    if args.telemetry_dir:
        import os

        from pytorch_ps_mpi_tpu import telemetry

        os.makedirs(args.telemetry_dir, exist_ok=True)
        telemetry.configure(worker="trainer")
    if args.adamw:
        if args.optim != "adam":
            ap.error("--adamw requires --optim adam")
        if not args.weight_decay:
            # decoupled decay with wd=0 would be a silent no-op; pick
            # the conventional AdamW default instead of surprising the
            # user with unregularized plain Adam
            args.weight_decay = 0.01
            print("note: --adamw without --weight-decay: using 0.01")

    code = None
    if args.codec:
        kw = {}
        for kv in args.codec_arg:
            k, v = kv.split("=", 1)
            try:
                v = json.loads(v)
            except json.JSONDecodeError:
                pass
            kw[k] = v
        code = get_codec(args.codec, **kw)

    if args.remat and args.config not in ("bert_mlm", "gpt_lm"):
        print(f"note: --remat has no effect on {args.config} "
              "(transformer configs only)")
    if args.scan_layers and args.config not in ("bert_mlm", "gpt_lm"):
        print(f"note: --scan-layers has no effect on {args.config} "
              "(transformer configs only)")
    params, loss_fn, data = build(args.config, args.batch, remat=args.remat,
                                  scan_layers=args.scan_layers)
    from pytorch_ps_mpi_tpu.data import prefetch

    data = prefetch(data)  # overlap host batch construction with the step
    lr = args.lr
    if args.lr_schedule == "warmup_cosine":
        from pytorch_ps_mpi_tpu.optim import warmup_cosine

        lr = warmup_cosine(args.lr, total_steps=args.steps,
                           warmup_steps=args.warmup_steps)
    elif args.lr_schedule == "step_decay":
        from pytorch_ps_mpi_tpu.optim import step_decay

        bounds = tuple(int(b) for b in args.decay_boundaries.split(",") if b)
        lr = step_decay(args.lr, boundaries=bounds or (args.steps // 2,))
    hyper = {"lr": lr}
    if args.optim == "sgd":
        hyper["momentum"] = args.momentum
    if args.weight_decay:
        hyper["weight_decay"] = args.weight_decay
    if args.adamw:
        hyper["decoupled_weight_decay"] = True
    if args.optim == "adafactor" and args.lr_schedule is None \
            and not explicit_lr:
        # no explicit lr and no schedule: the paper's relative step size
        hyper["lr"] = None
    opt = MPI_PS(
        params, optim=args.optim, code=code, mode=args.mode,
        average=True, instrument=args.instrument,
        comm_dtype=jnp.bfloat16 if args.bf16_comm else None,
        donate_buffers=args.donate, clip_norm=args.clip_norm,
        bucket_mb=args.bucket_mb, **hyper,
    )
    print(f"config={args.config} backend={jax.default_backend()} "
          f"device_kind={jax.devices()[0].device_kind!r} "
          f"devices={jax.device_count()} "
          f"world={opt.size} codec={args.codec or 'identity'}")
    trainer = Trainer(
        opt, loss_fn, checkpoint_dir=args.checkpoint_dir,
        scan_chunk=args.scan_chunk,
    )
    resumed = trainer.maybe_restore()
    if resumed:
        print(f"resumed from step {trainer.step_count}")
    summary = trainer.fit(data, args.steps, log_every=args.log_every)
    if args.telemetry_dir:
        import os

        from pytorch_ps_mpi_tpu import telemetry

        path = telemetry.get_recorder().dump_jsonl(
            os.path.join(args.telemetry_dir, "train.jsonl")
        )
        print(f"telemetry: {path} (summarize with "
              "tools/telemetry_report.py)")
    print(json.dumps({k: round(float(v), 6) for k, v in summary.items()}))


if __name__ == "__main__":
    main()
