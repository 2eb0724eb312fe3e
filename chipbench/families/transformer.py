"""What the two transformer families share: the model's configuration
from a file's sizes, the data stream, the FLOP shapes and the reference
terms. ``bert_mlm.py`` and ``gpt_lm.py`` say what differs."""

from __future__ import annotations

import functools
import importlib
import types


def family(*, model_cls, loss, terms, sizes: dict, causal: bool,
           config: dict, traffic: dict):
    import jax
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu.models.bert import BertConfig

    seq = int(traffic["seq"])
    if seq > sizes["max_position"]:
        raise ValueError(f"seq {seq} exceeds the configuration's "
                         f"{sizes['max_position']} positions")
    cfg = BertConfig(
        vocab_size=sizes["vocab"], hidden_size=sizes["hidden"],
        num_layers=sizes["layers"], num_heads=sizes["heads"],
        intermediate_size=sizes["ffn"], max_position=sizes["max_position"],
        dtype=jnp.dtype(config["dtype"]).type, attention=config["attention"],
        causal=causal, remat=bool(config["remat"]),
        f32_logits=bool(config["f32_logits"]))
    model = model_cls(cfg)
    gen = importlib.import_module(f"chipbench.gen.{traffic['generator']}")
    shape = dict(seq=seq, hidden=sizes["hidden"], heads=sizes["heads"],
                 ffn=sizes["ffn"], vocab=sizes["vocab"],
                 layers=sizes["layers"], causal=causal)
    return types.SimpleNamespace(
        model=model,
        init=lambda key: model.init(key, jnp.zeros((1, seq), jnp.int32)),
        loss_fn=lambda params, batch: loss(model, params, batch),
        batches=lambda seed, rows: gen.batches(
            seed, rows, seq, sizes["vocab"],
            **traffic.get("generator_params", {})),
        unit="tokens", units_per_row=seq, shape=shape,
        head_dim=sizes["hidden"] // sizes["heads"],
        dtype_bytes=jnp.dtype(config["dtype"]).itemsize,
        reference_terms=functools.partial(
            terms, num_layers=sizes["layers"]),
        # the reference holds one block's float32 activations at a time
        reference_block_rows=max(1, min(16, 2048 // seq)),
    )
