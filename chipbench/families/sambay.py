"""``sambay``: ``models/sambay.py`` at the sizes of a ``phi4flash``
``config.json`` (source key names) plus the keys the source lacks
(``layer_types``, ``published_layer_index``, the ``mamba_*`` sizes: the
configuration's ``assumed``), trained on the next-token loss. A chip's
share is written in the file: ``vocab_size`` is the slice of the
vocabulary held, ``num_hidden_layers`` the layers held."""

import importlib
import types

from pytorch_ps_mpi_tpu.models import sambay

from chipbench.reference import sambay as reference


def build(config: dict, traffic: dict):
    import jax
    import jax.numpy as jnp

    cfg = sambay.SambaYConfig.from_source(config)
    seq = int(traffic["seq"])
    if seq > config["max_position_embeddings"]:
        raise ValueError(f"seq {seq} exceeds the configuration's "
                         f"{config['max_position_embeddings']} positions")
    # the uncut model by the same count: the published layout at the
    # published depth and vocabulary
    uncut = sambay.param_count(sambay.SambaYConfig.from_source(dict(
        config, layer_types=None, published_layer_index=range(
            config["published_num_hidden_layers"]),
        num_hidden_layers=config["published_num_hidden_layers"],
        vocab_size=config["published_vocab_size"])))
    if uncut != config["published_parameter_count"]:
        raise ValueError(f"the uncut sizes give {uncut:,} parameters, the "
                         f"file says {config['published_parameter_count']:,}")
    gen = importlib.import_module(f"chipbench.gen.{traffic['generator']}")
    kinds = cfg.layer_types
    shape = dict(
        seq=seq, hidden=cfg.hidden_size, ffn=cfg.intermediate_size,
        heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        window=cfg.sliding_window, d_inner=cfg.d_inner,
        d_state=cfg.mamba_d_state, dt_rank=cfg.mamba_dt_rank,
        d_conv=cfg.mamba_d_conv, vocab=cfg.vocab_size,
        mamba_layers=kinds.count("mamba") + kinds.count("mamba_memory"),
        gmu_layers=kinds.count("gmu"),
        window_layers=kinds.count("sliding_attention"),
        full_layers=kinds.count("full_attention"),
        cross_layers=kinds.count("cross_attention"))
    return types.SimpleNamespace(
        cfg=cfg,
        init=lambda key: sambay.init(key, cfg),
        loss_fn=lambda params, batch: sambay.causal_lm_loss(
            params, batch, cfg),
        batches=lambda seed, rows: gen.batches(
            seed, rows, seq, cfg.vocab_size,
            **traffic.get("generator_params", {})),
        unit="tokens", units_per_row=seq, shape=shape,
        head_dim=cfg.head_dim, dtype_bytes=jnp.dtype(cfg.dtype).itemsize,
        reference=reference, reference_cfg=config)
