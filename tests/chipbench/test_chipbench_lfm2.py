"""``flops_lfm2.py`` against numbers worked by hand, the configuration
``lfm2-24b-a2b`` against the catalog's row, the mix ``lm8kx2``, the new
readers on a synthetic trace and where there is nothing to read, the
appended entries of ``BENCHMARK.json`` (looked up by NAME, never by
position), and a rehearsal of the streamed job on a tiny ``lfm2`` cell."""

import json
import os
import shutil

import pytest
from test_chipbench_rehearsal import (LINE_KEYS, ROOT, rehearsal_manifest,
                                      run_cell)

from chipbench import flops_lfm2
from chipbench.run import Manifest

# lfm2-24b-a2b.lm8kx2: 2 rows of 8,192 positions (T = 16,384 tokens), hidden
# 2048, 32 query over 8 key-value heads of 64, dense SwiGLU 11,776, experts of
# 1536, 64 routed (8 held, 4 a token), 3 taps, vocabulary 8,192; 5 conv + 2
# attention layers = 1 dense + 6 expert layers. Forward, 2 operations a
# multiply-add:
#   conv_proj 2 T 2048 * 4*2048 * 5                        = 2,748,779,069,440
#   conv_mix  2 T 3 * 2048 * 5                             =     1,006,632,960
#   qkv_proj  2 T 2048 * (32 + 16)*64 * 2                  =   412,316,860,416
#   out_proj  2 T 32*64 * 2048 * 2                         =   274,877,906,944
#   pairs     2 * 32 * 8192 * 8193 / 2                     =     2,147,745,792
#   scores    2 * pairs * 64 * 2                           =   549,822,922,752
#   values    the same                                     =   549,822,922,752
#   dense_ffn 2 T 2048 * 11776 * 3                         = 2,370,821,947,392
#   router    2 T 2048 * 64 * 6                            =    25,769,803,776
#   experts   2 * (T*4*8/64 = 8192) * 3*2048*1536 * 6      =   927,712,935,936
#   head      2 T 2048 * 8192                              =   549,755,813,888
#   sum 8,410,686,816,256; a training step is 3x           = 25,232,060,448,768
CELL = dict(rows=2, seq=8192, hidden=2048, heads=32, kv_heads=8, head_dim=64,
            ffn=11776, expert_width=1536, experts=64, experts_held=8, top_k=4,
            taps=3, vocab=8192, conv_layers=5, attn_layers=2, dense_layers=1,
            expert_layers=6)
# tiny, by hand: 1 row of 8 positions, hidden 4, 2 query over 1 key-value
# head of 2, dense 6, experts of 5, 4 routed (2 held, 1 a token), 2 taps,
# vocabulary 7; 2 conv + 1 attention = 1 dense + 2 expert layers; pairs 2 * 36
TINY = dict(rows=1, seq=8, hidden=4, heads=2, kv_heads=1, head_dim=2, ffn=6,
            expert_width=5, experts=4, experts_held=2, top_k=1, taps=2,
            vocab=7, conv_layers=2, attn_layers=1, dense_layers=1,
            expert_layers=2)


@pytest.mark.parametrize("shape, klass, want", [
    (CELL, "conv_proj", 2_748_779_069_440),
    (CELL, "conv_mix", 1_006_632_960),
    (CELL, "qkv_proj", 412_316_860_416),
    (CELL, "out_proj", 274_877_906_944),
    (CELL, "attn_scores", 549_822_922_752),
    (CELL, "attn_values", 549_822_922_752),
    (CELL, "dense_ffn", 2_370_821_947_392),
    (CELL, "router", 25_769_803_776),
    (CELL, "experts", 927_712_935_936),
    (CELL, "vocab_proj", 549_755_813_888),
    (TINY, "conv_proj", 2 * 8 * 4 * 16 * 2),
    (TINY, "conv_mix", 2 * 8 * 2 * 4 * 2),
    (TINY, "qkv_proj", 2 * 8 * 4 * (2 + 2) * 2),
    (TINY, "out_proj", 2 * 8 * 2 * 2 * 4),
    (TINY, "attn_scores", 2 * 72 * 2),
    (TINY, "attn_values", 2 * 72 * 2),
    (TINY, "dense_ffn", 2 * 8 * 4 * 6 * 3),
    (TINY, "router", 2 * 8 * 4 * 4 * 2),
    (TINY, "experts", 2 * 4 * 3 * 4 * 5 * 2),
    (TINY, "vocab_proj", 2 * 8 * 4 * 7),
])
def test_forward_classes(shape, klass, want):
    assert flops_lfm2.forward_flops(**shape)[klass] == want


def test_train_step_and_pairs():
    assert flops_lfm2.train_flops(**CELL) == 25_232_060_448_768
    assert flops_lfm2.allowed_pairs(**CELL) == 2_147_745_792
    assert flops_lfm2.pairs_held(**CELL) == 8192
    forward = flops_lfm2.forward_flops(**CELL)
    # what the cell's `why` says of the cut: the ONE dense layer's SwiGLU is
    # over a quarter of the forward operations, the two attention layers
    # with their projections a fifth, the convolution itself nothing
    total = sum(forward.values())
    assert 0.27 < forward["dense_ffn"] / total < 0.30
    assert 0.20 < sum(forward[k] for k in (
        "qkv_proj", "out_proj", "attn_scores", "attn_values")) / total < 0.22
    assert forward["conv_mix"] / total < 2e-4
    with pytest.raises(ValueError, match="by operator and by feed-forward"):
        flops_lfm2.forward_flops(**dict(CELL, dense_layers=2))


def test_kernel_convolution_and_grouped_costs():
    # 7 products of 2 x 64 over the allowed pairs, two layers; q/o/do/dq
    # over 32 heads and k/v/dk/dv over 8, six tensors each, bf16
    cost = flops_lfm2.gqa_attention_kernel_cost(**CELL, dtype_bytes=2)
    assert cost["flops"] == 7 * 2 * 2_147_745_792 * 64 * 2
    assert cost["bytes"] == 6 * (32 + 8) * 2 * 8192 * 64 * 2 * 2
    assert cost["flops"] / 197e12 > 10 * cost["bytes"] / 819e9   # compute
    # five convolutions: 4 + 7 values of hidden a position in bf16
    conv = flops_lfm2.short_conv_cost(**CELL, dtype_bytes=2)
    assert conv["bytes"] == 11 * 16384 * 2048 * 2 * 5
    assert conv["flops"] == 3 * 16384 * 2048 * (2 * 3 + 2) * 5
    assert conv["bytes"] / 819e9 > 100 * conv["flops"] / 197e12   # memory
    # 9 products a layer over the 8,192 expected pairs held, six layers
    gmm = flops_lfm2.rows_grouped_matmul_cost(**CELL, dtype_bytes=2)
    assert gmm["flops"] == 9 * 2 * 8192 * 2048 * 1536 * 6
    assert gmm["bytes"] == 9 * (8192 * (2048 + 1536) + 8 * 2048 * 1536) * 2 * 6
    # through the shape it is the two accepted counts: sdar_moe's doubled
    # rows and xing's single ones
    from chipbench import flops_sdar, flops_xing

    bd4k = dict(rows=2, seq=4096, hidden=2048, expert_width=768, experts=128,
                experts_held=16, top_k=8, dtype_bytes=2)
    assert flops_lfm2.rows_grouped_matmul_cost(
        **bd4k, expert_layers=6, positions_per_row=8192
    ) == flops_sdar.grouped_matmul_cost(**bd4k, layers=6)
    lm4k = dict(rows=1, seq=4096, hidden=3584, expert_width=1024, experts=64,
                experts_held=8, top_k=4, dtype_bytes=2)
    assert flops_lfm2.rows_grouped_matmul_cost(
        **lm4k, expert_layers=4) == flops_sdar.grouped_matmul_cost(
        hidden=3584, expert_width=1024, experts_held=8, dtype_bytes=2,
        layers=4, pairs=flops_xing.pairs_held(**lm4k))


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "LFM2-24B-A2B")


def config_file():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "lfm2-24b-a2b.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_number():
    row, cfg = catalog_row(), config_file()
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published_" + key] == value
            assert cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert row["source_url"] in cfg["source"] and len(cfg["source"]) <= 200


def test_the_cut_and_what_the_file_states():
    cfg = config_file()
    assert cfg["family"] == "lfm2"
    # every published width: 2048; 32 over 8 heads (of 64: no head_dim key);
    # 11,776; 1536; 4 a token; 3 taps; the router 64 wide beside the 8 held
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["conv_L_cache"], cfg["published_num_experts"]) == (
        2048, 32, 8, 11776, 1536, 4, 3, 64)
    assert "head_dim" not in cfg
    # ONE leading dense layer, then a whole period and two layers more, at
    # their published indices; num_dense_layers stays what the source says
    assert cfg["num_hidden_layers"] == len(cfg["published_layer_index"]) == 7
    assert cfg["published_layer_index"] == [0, 2, 3, 4, 5, 6, 7]
    assert cfg["num_dense_layers"] == 2
    kinds = [cfg["layer_types"][i] for i in cfg["published_layer_index"]]
    assert kinds == ["conv", "full_attention", "conv", "conv", "conv",
                     "full_attention", "conv"]
    assert len(cfg["layer_types"]) == cfg["published_num_hidden_layers"] == 40
    assert sum(i >= 2 for i in cfg["published_layer_index"]) >= 4  # the floor
    assert cfg["num_experts"] == 8 and cfg["first_expert"] == 0
    assert cfg["vocab_size"] * 8 == cfg["published_vocab_size"]
    assert cfg["dtype"] == "bfloat16" and cfg["param_dtype"] == "float32"
    assert cfg["remat"] is True and cfg["optimizer"]["name"] == "adam"
    # 8 cannot overflow: 64 x min(4, 8) / (4 x 8)
    assert cfg["moe_capacity_factor"] == 64 * 4 / (4 * 8)
    for key in ("assumed", "deployment", "guarantees", "tolerances"):
        assert cfg[key], key
    assert len(cfg["assumed"]) >= 8 and cfg["tolerances"]["reason"]
    for word in ("tied", "expert_bias", "1e-6", "weights_seed",
                 "moe_capacity_factor", "0.02"):
        assert any(word in a for a in cfg["assumed"]), word
    for limit in ("loss_rel", "update_sign_share", "update_rel_l2",
                  "worst_expert_sign_share", "worst_expert_rel_l2",
                  "router_tie_share"):
        assert 0 < cfg["tolerances"][limit] <= 1, limit


def test_the_family_builds_the_cut_and_counts_the_uncut_model():
    import jax

    from chipbench.families import lfm2 as family
    from pytorch_ps_mpi_tpu.models import lfm2

    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cfg, traffic = man.config("lfm2-24b-a2b"), man.traffic("lm8kx2")
    fam = family.build(cfg, traffic)
    assert lfm2.param_count(fam.cfg) == 647_819_904
    assert dict(fam.shape, head_dim=fam.head_dim) == {
        k: v for k, v in CELL.items() if k != "rows"}
    assert (fam.unit, fam.units_per_row, fam.head_dim, fam.dtype_bytes) == (
        "tokens", 8192, 64, 2)
    assert fam.cfg.experts_held == (0, 8) and fam.cfg.num_experts == 64
    shapes = jax.eval_shape(fam.init, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 647_819_904
    assert shapes["layer_0"]["conv"]["in_proj"].shape == (2048, 6144)
    assert shapes["layer_0"]["conv"]["conv"].shape == (2048, 3)
    assert shapes["layer_0"]["feed_forward"]["gate_proj"].shape == (2048, 11776)
    assert shapes["layer_1"]["self_attn"]["k_proj"].shape == (2048, 512)
    assert shapes["layer_1"]["experts"]["gate_proj"].shape == (8, 2048, 1536)
    assert shapes["layer_1"]["router"].shape == (2048, 64)
    assert shapes["layer_1"]["expert_bias"].shape == (64,)
    assert "lm_head" not in shapes        # the head is the embedding's
    assert cfg["published_parameter_count"] == 23_843_661_440
    # the issue's retreat, one whole period after the dense layer
    retreat = family.build(dict(cfg, num_hidden_layers=5,
                                published_layer_index=[0, 2, 3, 4, 5]),
                           traffic)
    assert lfm2.param_count(retreat.cfg) == 469_285_248
    batch = next(fam.batches(2 ** 31 + 5, 2))
    assert batch["tokens"].shape == (2, 8192)
    assert 0 <= batch["tokens"].min() and batch["tokens"].max() < 8192
    with pytest.raises(ValueError, match="uncut sizes"):
        family.build(dict(cfg, published_parameter_count=24_000_000_000),
                     traffic)
    with pytest.raises(ValueError, match="uncut sizes"):   # a width changed
        family.build(dict(cfg, moe_intermediate_size=1024), traffic)
    with pytest.raises(ValueError, match="exceeds"):
        family.build(cfg, dict(traffic, seq=2 ** 18))


def test_the_mix():
    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    assert man.traffic("lm8kx2") == {
        "job": "sync_train_streamed", "generator": "lm_zipf",
        "generator_params": {"exponent": 1.0}, "seq": 8192,
        "rows_per_chip": 2, "mode": "allgather", "codec": None,
        "bucket_mb": 0, "steps_per_fit": 3, "trace_fit_calls": 2}


NEW_CELL = "lfm2-24b-a2b.lm8kx2"
SIBLING = "xing4-29b-a4b.lm4k"
NEW_READERS = ["model.conv_moe_mfu_pct", "conv.mix_ms",
               "conv.mix_roofline_pct", "conv.proj_ms", "attn.gqa_kernel_ms",
               "attn.gqa_roofline_pct", "moe.rows_gmm_roofline_pct"]
SIBLINGS_OWN = ["model.mla_moe_mfu_pct", "attn.mla_kernel_ms",
                "attn.mla_roofline_pct", "hc.mix_ms", "hc.mix_roofline_pct",
                "moe.lm_gmm_roofline_pct"]


def entry(doc, group, name):
    return next(m for m in doc[group] if m["name"] == name)


def test_the_appended_entries_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cell = entry(doc, "workloads", NEW_CELL)
    assert cell == dict(cell, config="lfm2-24b-a2b", traffic="lm8kx2",
                        chips=1)
    assert len(cell["why"]) <= 200
    for word in ("1/8", "attention", "dense"):   # what the cut over-represents
        assert word in cell["why"], word
    assert len(doc["workloads"]) >= 9
    assert sum(c["chips"] == 4 for c in doc["workloads"]) == 1
    config = entry(doc, "configs", "lfm2-24b-a2b")
    assert config["reduced"] == config_file()["reduced"]
    assert config["file"] == "chipbench/configs/lfm2-24b-a2b.json"
    assert config["source"] == catalog_row()["source_url"]
    for name in NEW_READERS:
        m = entry(doc, "per_layer", name)
        assert m["workloads"] == [NEW_CELL] and m["moves"] == "tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["unit"] == ("%" if name.endswith("_pct") else "ms")
        assert m["source"] == "device_trace"
    # the cell is on every list that holds its sibling, but for the six
    # that are the sibling's own
    for group in ("end_to_end", "per_layer"):
        for m in doc[group]:
            lists = m.get("workloads")
            if lists is None or m["name"] in NEW_READERS:
                continue
            want = SIBLING in lists and m["name"] not in SIBLINGS_OWN
            assert (NEW_CELL in lists) == want, m["name"]
    assert NEW_CELL in entry(doc, "end_to_end", "tokens_per_s")["workloads"]
    assert "workloads" not in entry(doc, "per_layer", "cache.misses")
    # a layer's name is one spelling
    assert entry(doc, "per_layer", "attn.gqa_kernel_ms")["layer"] == entry(
        doc, "per_layer", "attn.kernel_ms")["layer"]
    assert entry(doc, "per_layer", "moe.rows_gmm_roofline_pct")[
        "layer"] == entry(doc, "per_layer", "moe.experts_ms")["layer"]
    assert entry(doc, "per_layer", "model.conv_moe_mfu_pct")["layer"] == entry(
        doc, "per_layer", "model.mfu_pct")["layer"]
    assert len({entry(doc, "per_layer", n)["layer"] for n in (
        "conv.mix_ms", "conv.mix_roofline_pct", "conv.proj_ms")}) == 1


def synthetic():
    """A reduced trace of 2 steps with the grouped causal kernels' and the
    short convolution's events, the scope table that joins them, and the
    cell."""
    call = ("(bf16[64,8192,64]) custom-call(%c, %q), custom_call_target="
            "\"tpu_custom_call\" [tpu_custom_call]")
    by_name = {
        f"%checkpoint.7 = {call}": (8, 0.040),
        f"%transpose_jvp_.1 = {call}": (4, 0.050),
        f"%transpose_jvp_.3 = {call}": (4, 0.070),
        f"%moe_sum_rows.4 = {call}": (24, 0.5),           # another scope's
        "%fusion.21 = bf16[2,32,8192,64] fusion(%x), kind=kLoop": (8, 0.030),
        "%fusion.11 = bf16[2,8192,2048] fusion(%s), kind=kLoop": (20, 0.020),
        "%fusion.13 = bf16[2,8192,6144] fusion(%x), kind=kLoop": (10, 0.030),
        "%while.3 = (s32[], bf16[2,8192,2048]) while(%t)": (2, 0.400),
        "%fusion.15 = bf16[16384,6144] fusion(%x), kind=kOutput": (30, 0.120),
        "%fusion.12 = bf16[16384,11776] fusion(%x), kind=kOutput": (4, 0.100),
        "%ragged-dot.5 = bf16[65536,1536] ragged-dot(%a, %b, %g)": (108, 0.150),
    }
    trace = {"steps": 2, "step_device_s": 0.5, "window_s": 1.2, "busy_s": 1.0,
             "by_name": by_name}
    counters = {"chips": 1, "moe_pairs_held_per_step": 49000.0, "scopes": {
        "%checkpoint.7": "attn.gqa", "%transpose_jvp_.1": "attn.gqa",
        "%transpose_jvp_.3": "attn.gqa", "%moe_sum_rows.4": "moe.combine",
        "%fusion.21": "attn.gqa", "%fusion.11": "conv.mix",
        "%fusion.13": "conv.mix", "%while.3": "conv.mix",
        "%fusion.15": "conv.proj", "%fusion.12": "mlp.swiglu",
        "%ragged-dot.5": "moe.experts"}}
    cell = {"name": NEW_CELL, "config": config_file(),
            "shape": dict(CELL, dtype_bytes=2),
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    return trace, counters, cell


def test_the_readers_on_a_synthetic_trace(capfd):
    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    trace, counters, cell = synthetic()
    read = lambda name: man.reader(name)(trace, {}, counters, cell)
    # the Pallas calls under attn.gqa: not the transposes beside them, not
    # another scope's kernel
    assert read("attn.gqa_kernel_ms") == pytest.approx(1e3 * 0.160 / 2)
    # the loop's own event is left out beside its body's
    assert read("conv.mix_ms") == pytest.approx(1e3 * 0.050 / 2)
    assert read("conv.proj_ms") == pytest.approx(1e3 * 0.120 / 2)
    # 25.23 TFLOP over 0.5 s x 197 TFLOP/s
    assert read("model.conv_moe_mfu_pct") == pytest.approx(
        100 * 25_232_060_448_768 / 197e12 / 0.5)
    least = 7 * 2 * 2_147_745_792 * 64 * 2 / 197e12        # compute-bound
    assert read("attn.gqa_roofline_pct") == pytest.approx(100 * least / 0.080)
    assert read("conv.mix_roofline_pct") == pytest.approx(
        100 * (11 * 16384 * 2048 * 2 * 5 / 819e9) / 0.025)
    # at 1,024 rows an expert the operations bound the grouped products
    gmm = 9 * 2 * 8192 * 2048 * 1536 * 6 / 197e12
    assert gmm > 9 * (8192 * 3584 + 8 * 2048 * 1536) * 2 * 6 / 819e9
    assert read("moe.rows_gmm_roofline_pct") == pytest.approx(
        100 * gmm / 0.075)
    rows = [json.loads(l) for l in capfd.readouterr().out.splitlines()]
    assert {r["check"]: r["bound"] for r in rows} == {
        "attn.gqa_roofline_pct": "compute", "conv.mix_roofline_pct": "memory",
        "moe.rows_gmm_roofline_pct": "compute"}
    for name in NEW_READERS:      # a share stays a share
        assert 0 < read(name) <= 100 or name.endswith("_ms")


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_reader_reads_nothing_where_nothing_is(metric):
    """On a program that lacks what this PR adds (no scope table, no such
    scope, another family's shape) a reader returns None and does not
    raise."""
    read = Manifest(os.path.join(ROOT, "BENCHMARK.json")).reader(metric)
    cell = {"name": "no-such-run", "shape": {"seq": 8}, "peaks": None}
    assert read(None, {}, {}, cell) is None
    summary = {"steps": 3, "window_s": 1.0, "busy_s": 0.5}
    assert read(summary, {}, {}, cell) is None
    full = dict(summary, step_device_s=0.5, by_name={
        "%fusion.1 = f32[8] fusion(%x), kind=kLoop": (3, 0.3)})
    peaks = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}
    assert read(full, {}, {"chips": 1}, dict(cell, peaks=peaks)) is None
    # this family's cell on a program without the scopes or the kernels
    # (the whole step's share needs neither: it reads the step's time)
    trace, counters, mine = synthetic()
    if metric != "model.conv_moe_mfu_pct":
        assert read(full, {}, {"chips": 1}, mine) is None
        assert read(full, {}, {"chips": 1, "scopes": {
            "%fusion.1": "mlp.swiglu"}}, mine) is None
    # another family's cells, scope table, kernels and all (the grouped
    # products' reader goes through the shape: it reads any family that
    # carries its keys, and none that lacks one)
    for shape in ({"seq": 4096, "rows": 2, "experts_held": 16},
                  {"seq": 8192, "rows": 1, "mamba_layers": 2}):
        other = dict(cell, peaks=peaks, shape=shape)
        assert read(trace, {}, counters, other) is None


def test_the_grouped_products_reader_goes_through_the_shape():
    """Another family's shape with the keys it needs reads too; one key
    short, nothing."""
    read = Manifest(os.path.join(ROOT, "BENCHMARK.json")).reader(
        "moe.rows_gmm_roofline_pct")
    trace, counters, cell = synthetic()
    lm4k = dict(rows=1, seq=4096, hidden=3584, expert_width=1024, experts=64,
                experts_held=8, top_k=4, expert_layers=4, dtype_bytes=2,
                nope_dim=128, streams=4)
    least = 9 * (2048 * (3584 + 1024) + 8 * 3584 * 1024) * 2 * 4 / 819e9
    assert read(trace, {}, counters, dict(cell, shape=lm4k)) == pytest.approx(
        100 * least / 0.075)
    short = {k: v for k, v in lm4k.items() if k != "top_k"}
    assert read(trace, {}, counters, dict(cell, shape=short)) is None


def test_rehearsal_of_the_tiny_lfm2_cell(tmp_path, capfd):
    """``jobs/sync_train_streamed.py`` end to end on a tiny ``lfm2``
    configuration with both operator kinds and both feed-forward kinds:
    the family, the reference's ``terms`` and ``router_loads`` in the
    streamed comparison, the frozen bias through the reference's Adam, and
    the counters a CPU run may report."""
    manifest, doc = rehearsal_manifest(
        str(tmp_path),
        extra_cells={"tiny-lfm2.lm": ("tiny-lfm2", "tiny-lm-streamed", 1)})
    line, earlier = run_cell(capfd, manifest, "tiny-lfm2.lm", trace=1)
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    ref = {r["check"]: r for r in earlier if "check" in r}["reference"]
    assert ref["ok"] and ref["loss_rel"] < 1e-5
    assert ref["update_sign_share"] > 0.99 and ref["update_rel_l2"] < 1e-2
    assert ref["worst_expert_sign_share"] > 0.99
    # what the limits are set against: bf16 parameters lose the update
    assert ref["if_bf16_params"]["update_rel_l2"] > 0.3
    # two expert layers: the program's router is the reference's
    assert ref["router_tie_share"] == 0.0
    assert ref["router_loads_step1"] == ref["reference_router_loads_step1"]
    assert len(ref["router_loads_step1"]) == 2
    m = line["metrics"]
    assert m["step.compiles_in_window"]["value"] == 0
    assert m["moe.load_max_over_mean"]["value"] >= 1.0
    counts = {x["name"] for x in doc["per_layer"]
              if x["source"] == "program_counter"}
    assert set(m) <= counts
    # the program's set-up log holds the convolution's plan, a row a trace
    # of a conv layer (the check row's `plans` prints a fixed list of names,
    # chipbench/setup_phases.py::PLANS, which is not this PR's to edit)
    from pytorch_ps_mpi_tpu import telemetry

    plans = [r["attrs"] for r in telemetry.setup_rows()
             if r["name"] == "conv.plan"]
    assert plans and all(p["mover"] == "jnp" and p["taps"] == 3
                         and p["channels"] == 32 for p in plans)
    shutil.rmtree(os.path.join(ROOT, ".chipbench_run", "tiny-lfm2.lm"),
                  ignore_errors=True)
