"""``xing``: ``models/xing.py`` at the sizes of a ``xing4_0``
``config.json`` (source key names) plus the keys the source lacks (the
configuration's ``assumed``), trained on the next-token loss plus the
multi-token-prediction module's. A chip's share is written in the file:
``n_routed_experts`` counts the experts held (from ``first_expert``),
``published_n_routed_experts`` is the router's width, ``vocab_size`` the
slice of the vocabulary, ``num_hidden_layers`` the layers held, each at
its ``published_layer_index`` (one under ``first_k_dense_replace`` is a
dense layer)."""

import ctypes
import importlib
import types

from pytorch_ps_mpi_tpu.models import xing

from chipbench.reference import xing as reference


def release_freed_heap() -> None:
    """Hand the heap's freed pages back to the system. The streamed
    comparison compiles its large programs in a row beside the host
    copies of the parameters on a machine of 40 GiB; glibc keeps what a
    compile freed (3.9 GB after one of the reference's gradient programs:
    6.05 -> 2.14 GB resident after ``malloc_trim``, PERF.md section 6)
    and the run's peak read 37.7 GB with it, over 40 GiB once, when the
    comparison still held six host copies."""
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def before_its_trace(fn):
    """``fn``, releasing the heap first: the job jits each of these once,
    so this runs once a program, between one compile and the next."""
    def traced(*args, **kw):
        release_freed_heap()
        return fn(*args, **kw)

    return traced


def build(config: dict, traffic: dict):
    import jax
    import jax.numpy as jnp

    cfg = xing.XingConfig.from_source(config)
    seq = int(traffic["seq"])
    if seq > config["max_position_embeddings"]:
        raise ValueError(f"seq {seq} exceeds the configuration's "
                         f"{config['max_position_embeddings']} positions")
    # the uncut model by the same count: every published layer, expert and
    # vocabulary row, the prediction module as published
    published = {k[len("published_"):]: v for k, v in config.items()
                 if k.startswith("published_")
                 and k[len("published_"):] in config["reduced"]}
    uncut = xing.param_count(xing.XingConfig.from_source(dict(
        config, **published, first_expert=0, published_layer_index=range(
            published["num_hidden_layers"]))))
    if uncut != config["published_parameter_count"]:
        raise ValueError(f"the uncut sizes give {uncut:,} parameters, the "
                         f"file says {config['published_parameter_count']:,}")
    gen = importlib.import_module(f"chipbench.gen.{traffic['generator']}")
    count = cfg.experts_held[1]
    dense = sum(cfg.layers_dense)
    shape = dict(
        seq=seq, hidden=cfg.hidden_size, heads=cfg.num_attention_heads,
        q_rank=cfg.q_lora_rank, kv_rank=cfg.kv_lora_rank,
        nope_dim=cfg.qk_nope_head_dim, rope_dim=cfg.qk_rope_head_dim,
        v_dim=cfg.v_head_dim, ffn=cfg.intermediate_size,
        expert_width=cfg.moe_intermediate_size, experts=cfg.n_routed_experts,
        experts_held=count, top_k=cfg.num_experts_per_tok,
        shared_experts=cfg.n_shared_experts, streams=cfg.hc_mult,
        vocab=cfg.vocab_size, dense_layers=dense,
        expert_layers=len(cfg.layer_index) - dense,
        mtp_modules=cfg.num_nextn_predict_layers)
    return types.SimpleNamespace(
        cfg=cfg,
        # a configuration with ``weights_seed`` is ONE checkpoint: its
        # weights come from that key and --seed draws the data alone
        # (the configuration's ``assumed`` says why)
        init=lambda key: xing.init(
            jax.random.key(config["weights_seed"])
            if "weights_seed" in config else key, cfg),
        loss_fn=lambda params, batch: xing.causal_lm_loss(params, batch, cfg),
        router_loads=before_its_trace(
            lambda params, batch: xing.router_loads(params, batch, cfg)),
        batches=lambda seed, rows: gen.batches(
            seed, rows, seq, cfg.vocab_size,
            **traffic.get("generator_params", {})),
        unit="tokens", units_per_row=seq, shape=shape,
        head_dim=cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
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
