"""``sdar_moe``: ``models/sdar_moe.py`` at the sizes of an SDAR
``config.json`` (source key names), trained on the block-diffusion loss.
A chip's share is written in the file: ``num_experts`` counts the experts
held (from ``first_expert``), ``published_num_experts`` is the router's
width, ``vocab_size`` the slice of the vocabulary."""

import importlib
import types

from pytorch_ps_mpi_tpu.models import sdar_moe

from chipbench.reference import sdar_moe as reference


def build(config: dict, traffic: dict):
    import jax
    import jax.numpy as jnp

    cfg = sdar_moe.SdarMoeConfig.from_source(config)
    seq = int(traffic["seq"])
    if 2 * seq > config["max_position_embeddings"]:
        raise ValueError(f"2 x seq {seq} exceeds the configuration's "
                         f"{config['max_position_embeddings']} positions")
    if config["mask_token_id"] != cfg.vocab_size - 1:
        raise ValueError("the generator masks with the last id of the "
                         "vocabulary held")
    gen = importlib.import_module(f"chipbench.gen.{traffic['generator']}")
    first, count = cfg.experts_held
    shape = dict(
        seq=seq, block=cfg.block_length, hidden=cfg.hidden_size,
        heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        expert_width=cfg.moe_intermediate_size, experts=cfg.num_experts,
        experts_held=count, top_k=cfg.num_experts_per_tok,
        vocab=cfg.vocab_size, layers=cfg.num_hidden_layers)
    return types.SimpleNamespace(
        cfg=cfg,
        # a configuration with ``weights_seed`` is ONE checkpoint: its
        # weights come from that key and --seed draws the data alone
        # (the configuration's ``assumed`` says why)
        init=lambda key: sdar_moe.init(
            jax.random.key(config["weights_seed"])
            if "weights_seed" in config else key, cfg),
        loss_fn=lambda params, batch: sdar_moe.block_diffusion_loss(
            params, batch, cfg),
        router_loads=lambda params, batch: sdar_moe.router_loads(
            params, batch, cfg),
        batches=lambda seed, rows: gen.batches(
            seed, rows, seq, cfg.vocab_size, block=cfg.block_length,
            **traffic.get("generator_params", {})),
        # a row's DATA tokens: each runs as 2 x seq positions
        unit="tokens", units_per_row=seq, shape=shape,
        head_dim=cfg.head_dim, dtype_bytes=jnp.dtype(cfg.dtype).itemsize,
        reference=reference, reference_cfg=config,
        # XLA's own instructions of the expert layer, which lose the
        # op_name path (jobs/sync_train_streamed.py::instruction_scopes)
        unscoped={"ragged-dot-none": "moe.experts",
                  "ragged-dot-metadata": "moe.experts",
                  "sort": "moe.dispatch"})
