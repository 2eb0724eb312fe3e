"""``lfm2``: ``models/lfm2.py`` at the sizes of an ``lfm2_moe``
``config.json`` (source key names) plus the keys the source lacks (the
configuration's ``assumed``), trained on the next-token loss. A chip's
share is written in the file: ``num_experts`` counts the experts held
(from ``first_expert``), ``published_num_experts`` is the router's width,
``vocab_size`` the slice of the vocabulary, ``num_hidden_layers`` the
layers held, each at its ``published_layer_index`` into the published
``layer_types`` (one under ``num_dense_layers`` has the dense
feed-forward)."""

import importlib
import types

from pytorch_ps_mpi_tpu.models import lfm2

from chipbench.families.xing import before_its_trace
from chipbench.reference import lfm2 as reference


def build(config: dict, traffic: dict):
    import jax
    import jax.numpy as jnp

    cfg = lfm2.Lfm2Config.from_source(config)
    seq = int(traffic["seq"])
    if seq > config["max_position_embeddings"]:
        raise ValueError(f"seq {seq} exceeds the configuration's "
                         f"{config['max_position_embeddings']} positions")
    # the uncut model by the same count: every published layer, expert and
    # vocabulary row
    published = {k[len("published_"):]: v for k, v in config.items()
                 if k.startswith("published_")
                 and k[len("published_"):] in config["reduced"]}
    uncut = lfm2.param_count(lfm2.Lfm2Config.from_source(dict(
        config, **published, first_expert=0, published_layer_index=range(
            published.get("num_hidden_layers", config["num_hidden_layers"])))))
    if uncut != config["published_parameter_count"]:
        raise ValueError(f"the uncut sizes give {uncut:,} parameters, the "
                         f"file says {config['published_parameter_count']:,}")
    gen = importlib.import_module(f"chipbench.gen.{traffic['generator']}")
    kinds = cfg.layers
    dense = sum(d for _, d in kinds)
    shape = dict(
        seq=seq, hidden=cfg.hidden_size, heads=cfg.num_attention_heads,
        kv_heads=cfg.num_key_value_heads, ffn=cfg.intermediate_size,
        expert_width=cfg.moe_intermediate_size, experts=cfg.num_experts,
        experts_held=cfg.experts_held[1], top_k=cfg.num_experts_per_tok,
        taps=cfg.conv_L_cache, vocab=cfg.vocab_size,
        conv_layers=sum(k == "conv" for k, _ in kinds),
        attn_layers=sum(k == "full_attention" for k, _ in kinds),
        dense_layers=dense, expert_layers=len(kinds) - dense)
    return types.SimpleNamespace(
        cfg=cfg,
        # a configuration with ``weights_seed`` is ONE checkpoint: its
        # weights come from that key and --seed draws the data alone
        # (the configuration's ``assumed`` says why)
        init=lambda key: lfm2.init(
            jax.random.key(config["weights_seed"])
            if "weights_seed" in config else key, cfg),
        loss_fn=lambda params, batch: lfm2.causal_lm_loss(params, batch, cfg),
        router_loads=before_its_trace(
            lambda params, batch: lfm2.router_loads(params, batch, cfg)),
        batches=lambda seed, rows: gen.batches(
            seed, rows, seq, cfg.vocab_size,
            **traffic.get("generator_params", {})),
        unit="tokens", units_per_row=seq, shape=shape,
        head_dim=cfg.head_dim,
        dtype_bytes=jnp.dtype(cfg.dtype).itemsize,
        reference=types.SimpleNamespace(
            terms=before_its_trace(reference.terms),
            router_loads=before_its_trace(reference.router_loads)),
        reference_cfg=config,
        # XLA's own instructions of the expert layer, which lose the
        # op_name path (jobs/sync_train_streamed.py::instruction_scopes)
        unscoped={"ragged-dot-none": "moe.experts",
                  "ragged-dot-metadata": "moe.experts",
                  "sort": "moe.dispatch"})
