"""``flops_kda.py`` against numbers worked by hand, the configuration
``solar-open2-250b`` against the source's config.json, the family's build of the
cut, the appended entries of ``BENCHMARK.json`` (looked up by NAME, never
by position), the three new readers on a synthetic trace and where there
is nothing to read, and a rehearsal of the streamed job on a tiny
``solar`` cell."""

import json
import os
import shutil

import pytest
from test_chipbench_rehearsal import (LINE_KEYS, ROOT, rehearsal_manifest,
                                      run_cell)

from chipbench import flops_kda
from chipbench.run import Manifest

# solar-open2-250b.lm8k: 1 row of 8,192 positions, hidden 4096; 3 delta-rule
# layers of 8 heads of 128 (chunk 64, 4 taps) and 1 GQA layer of 8 query
# over 1 key-value head of 128; every layer 8 of 320 experts of 1280, 8 a
# token, one shared; vocabulary 24,576. Forward, 2 operations a multiply-add:
#   kda_proj  2 T (4096*3072 + 1024*4096 + 4096*8 + 2*(4096*128 + 128*1024)) * 3
#                                                            =   890,668,843,008
#   kda_conv  2 T 4 * 3072 * 3                               =       603,979,776
#   kda_scan  a chunk and head 2 (2016*128 + 2080*128 + 2016*256
#             + 3*64*128*128 + 2080*128) = 8,904,704; x 128 chunks x 8 heads x 3
#                                                            =    27,355,250,688
#   qkv_proj  2 T 4096 * (2*8 + 2*1)*128                     =   154,618,822,656
#   out_proj  2 T 1024 * 4096                                =    68,719,476,736
#   pairs     8 * 8192 * 8193 / 2 = 268,468,224; scores, values 2 * pairs * 128
#                                                            =    68,727,865,344
#   router    2 T 4096 * 320 * 4                             =    85,899,345,920
#   shared    2 T 3*4096*1280 * 4                            = 1,030,792,151,040
#   experts   2 * (T*8*8/320 = 1638.4) * 3*4096*1280 * 4     =   206,158,430,208
#   head      2 T 4096 * 24576                               = 1,649,267,441,664
CELL = dict(rows=1, seq=8192, hidden=4096, heads=8, kv_heads=1, head_dim=128,
            kda_heads=8, dk=128, dv=128, chunk=64, taps=4, expert_width=1280,
            experts=320, experts_held=8, top_k=8, shared_experts=1,
            vocab=24576, kda_layers=3, attn_layers=1, expert_layers=4,
            conv_layers=0)
# tiny, by hand: 1 row of 8 positions, hidden 4; 1 delta-rule layer of 2
# heads of 2 (chunk 4: two chunks, 2 taps) and 1 GQA layer of 2 over 1 head
# of 2; 4 experts of 5 (2 held, 1 a token), one shared; vocabulary 7
TINY = dict(rows=1, seq=8, hidden=4, heads=2, kv_heads=1, head_dim=2,
            kda_heads=2, dk=2, dv=2, chunk=4, taps=2, expert_width=5,
            experts=4, experts_held=2, top_k=1, shared_experts=1, vocab=7,
            kda_layers=1, attn_layers=1, expert_layers=2)
# a chunk of 4 and a head of 2: 2 (6*2 + 10*2 + 6*4 + 3*4*2*2 + 10*2) = 240
TINY_CHUNK = 2 * (6 * 2 + 10 * 2 + 6 * 4 + 3 * 4 * 2 * 2 + 10 * 2)


@pytest.mark.parametrize("shape, klass, want", [
    (CELL, "kda_proj", 890_668_843_008),
    (CELL, "kda_conv", 603_979_776),
    (CELL, "kda_scan", 27_355_250_688),
    (CELL, "qkv_proj", 154_618_822_656),
    (CELL, "out_proj", 68_719_476_736),
    (CELL, "attn_scores", 68_727_865_344),
    (CELL, "attn_values", 68_727_865_344),
    (CELL, "router", 85_899_345_920),
    (CELL, "shared", 1_030_792_151_040),
    (CELL, "experts", 206_158_430_208),
    (CELL, "vocab_proj", 1_649_267_441_664),
    (TINY, "kda_proj", 2 * 8 * (4 * 12 + 4 * 4 + 4 * 2 + 2 * (4 * 2 + 2 * 4))),
    (TINY, "kda_conv", 2 * 8 * 2 * 12),
    (TINY, "kda_scan", TINY_CHUNK * 2 * 2),
    (TINY, "qkv_proj", 2 * 8 * 4 * 6 * 2),
    (TINY, "out_proj", 2 * 8 * 4 * 4),
    (TINY, "attn_scores", 2 * 72 * 2),
    (TINY, "router", 2 * 8 * 4 * 4 * 2),
    (TINY, "shared", 2 * 8 * 3 * 4 * 5 * 2),
    (TINY, "experts", 2 * 4 * 3 * 4 * 5 * 2),
    (TINY, "vocab_proj", 2 * 8 * 4 * 7),
])
def test_forward_classes(shape, klass, want):
    assert flops_kda.forward_flops(**shape)[klass] == pytest.approx(want)


def test_train_step_and_what_the_cell_says_of_it():
    forward = flops_kda.forward_flops(**CELL)
    total = sum(forward.values())
    assert flops_kda.train_flops(**CELL) == pytest.approx(3 * total)
    # the cell's `why`: the head over the slice is 40 % of the forward pass
    assert 0.38 < forward["vocab_proj"] / total < 0.40
    # the delta rule's own scan is under a hundredth: its time is its bytes
    assert forward["kda_scan"] / total < 0.01
    with pytest.raises(ValueError, match="every layer"):
        flops_kda.forward_flops(**dict(CELL, expert_layers=3))
    with pytest.raises(ValueError, match="every layer"):
        flops_kda.forward_flops(**dict(CELL, conv_layers=1))


def test_the_scan_cost():
    cost = flops_kda.kda_scan_cost(**CELL, dtype_bytes=2)
    assert cost["flops"] == 3 * 27_355_250_688
    # a position and head: q, k, v bf16 + the log-decays and beta float32
    # read (772 B) and the float32 output written (512), forward; those
    # read again with the output's gradient (1,284) and their gradients
    # written (772), backward; the 128 chunks' states written and read
    inputs = 3 * 128 * 2 + 128 * 4 + 4
    per_position = inputs + 512 + inputs + 512 + inputs
    assert cost["bytes"] == 8 * 3 * (8192 * per_position + 2 * 128 * 128 ** 2 * 4)
    # memory-bound on a v5e: 1.66 ms against 0.42 ms of operations
    assert cost["bytes"] / 819e9 > 3 * cost["flops"] / 197e12
    # a larger chunk does more operations and moves fewer states
    wide = flops_kda.kda_scan_cost(**dict(CELL, chunk=128), dtype_bytes=2)
    assert wide["flops"] > cost["flops"] and wide["bytes"] < cost["bytes"]


# the source's config.json, every number as published
SOURCE_URL = ("https://huggingface.co/upstage/Solar-Open2-250B/blob/main/"
              "config.json")
PUBLISHED = {
    "model_type": "solar_open2",
    "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096,
    "num_hidden_layers": 48,
    "num_attention_heads": 64,
    "head_dim": 128,
    "num_key_value_heads": 8,
    "vocab_size": 196608,
    "intermediate_size": 10240,
    "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05,
    "rope_theta": 10000,
    "tie_word_embeddings": False,
    "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0,
    "use_rope": False,
    "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True,
    "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True,
    "n_routed_experts": 320,
    "n_shared_experts": 1,
    "norm_topk_prob": True,
    "routed_scaling_factor": 1,
    "num_experts_per_tok": 8,
}


def config_file():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "solar-open2-250b.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_number():
    cfg = config_file()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "num_attention_heads",
                              "num_key_value_heads", "linear_attn_config"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published_" + key] == value, key
            assert cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    # the nested group differs only in the heads held: its widths stand
    group, published = cfg["linear_attn_config"], PUBLISHED[
        "linear_attn_config"]
    assert {k: v for k, v in group.items() if k != "num_heads"} == {
        k: v for k, v in published.items() if k != "num_heads"}
    assert SOURCE_URL in cfg["source"] and len(cfg["source"]) <= 200


def test_the_cut_and_what_the_file_states():
    cfg = config_file()
    assert cfg["family"] == "solar"
    # every published width: 4096; heads of 128; experts of 1280, 8 a
    # token; 4 taps; the router 320 wide beside the 8 held
    assert (cfg["hidden_size"], cfg["head_dim"],
            cfg["linear_attn_config"]["head_dim"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"],
            cfg["linear_attn_config"]["short_conv_kernel_size"],
            cfg["published_n_routed_experts"]) == (4096, 128, 128, 1280, 8, 4,
                                                   320)
    # heads as a 40-chip layer's share: 8 of 64 each way, 1 of 8 KV heads
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["linear_attn_config"]["num_heads"]) == (8, 1, 8)
    assert (cfg["published_num_attention_heads"]
            // cfg["num_attention_heads"]) == (
        cfg["published_num_key_value_heads"] // cfg["num_key_value_heads"]) \
        == (cfg["published_linear_attn_config"]["num_heads"]
            // cfg["linear_attn_config"]["num_heads"]) == 8
    assert cfg["published_n_routed_experts"] // cfg["n_routed_experts"] == 40
    # one whole period at its published indices: GQA, then three delta-rule
    assert cfg["num_hidden_layers"] == len(cfg["published_layer_index"]) == 4
    assert cfg["published_layer_index"] == [0, 1, 2, 3]
    assert [i in cfg["gqa_layers"] for i in cfg["published_layer_index"]] == [
        True, False, False, False]
    assert cfg["vocab_size"] * 8 == cfg["published_vocab_size"]
    assert cfg["dtype"] == "bfloat16" and cfg["param_dtype"] == "float32"
    assert cfg["remat"] is True and cfg["optimizer"] == {"name": "adam",
                                                         "lr": 1e-6}
    # 40 cannot overflow: 320 x min(8, 8) / (8 x 8)
    assert cfg["moe_capacity_factor"] == 320 * 8 / (8 * 8)
    for key in ("assumed", "deployment", "guarantees", "tolerances"):
        assert cfg[key], key
    assumed = " ".join(cfg["assumed"])
    for word in ("ELEMENTWISE", "q/k norm", "SIGMOID", "SwiGLU", "A_log",
                 "dt_bias", "chunk of 64", "weights_seed", "moe_capacity_factor",
                 "correction-bias"):
        assert word in assumed, word
    for word in ("40 chips", "8 a chip", "shared expert whole", "a 40th"):
        assert word in cfg["deployment"], word
    assert cfg["tolerances"]["reason"]
    for limit in ("loss_rel", "update_sign_share", "update_rel_l2",
                  "worst_expert_sign_share", "worst_expert_rel_l2",
                  "router_tie_share"):
        assert 0 < cfg["tolerances"][limit] <= 1, limit


def test_the_family_builds_the_cut_and_counts_the_uncut_model():
    import jax

    from chipbench.families import solar as family
    from pytorch_ps_mpi_tpu.models import lfm2, solar

    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cfg, traffic = man.config("solar-open2-250b"), man.traffic("lm8k")
    fam = family.build(cfg, traffic)
    assert lfm2.param_count(fam.cfg, solar.init) == 840_874_392
    assert dict(fam.shape, head_dim=fam.head_dim) == {
        k: v for k, v in CELL.items() if k != "rows"}
    assert (fam.unit, fam.units_per_row, fam.head_dim, fam.dtype_bytes) == (
        "tokens", 8192, 128, 2)
    shapes = jax.eval_shape(fam.init, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 840_874_392
    kda = shapes["layer_1"]["linear_attn"]
    assert kda["q_proj"].shape == (4096, 1024)
    assert kda["q_conv"].shape == (1024, 4)
    assert kda["f_a_proj"].shape == (4096, 128)
    assert kda["f_b_proj"].shape == (128, 1024)
    assert kda["A_log"].shape == (8,) and kda["dt_bias"].shape == (1024,)
    gqa = shapes["layer_0"]["self_attn"]
    assert gqa["k_proj"].shape == (4096, 128)
    assert gqa["g_proj"].shape == (4096, 1024)
    assert shapes["layer_0"]["experts"]["gate_proj"].shape == (8, 4096, 1280)
    assert shapes["layer_0"]["shared"]["gate_proj"].shape == (4096, 1280)
    assert shapes["layer_0"]["router"].shape == (4096, 320)
    assert shapes["lm_head"].shape == (4096, 24576)
    assert cfg["published_parameter_count"] == 250_288_089_856
    batch = next(fam.batches(2 ** 31 + 5, 1))
    assert batch["tokens"].shape == (1, 8192)
    assert 0 <= batch["tokens"].min() and batch["tokens"].max() < 24576
    with pytest.raises(ValueError, match="exceeds"):
        family.build(cfg, dict(traffic, seq=2 ** 21))


NEW_CELL = "solar-open2-250b.lm8k"
NEW_READERS = ["kda.scan_ms", "kda.scan_roofline_pct", "model.kda_moe_mfu_pct"]
APPENDED = [
    "tokens_per_s", "loop.step_ms_p50", "loop.step_ms_p95", "step.device_ms",
    "step.compiles_in_window", "step.dispatch_ms_p50", "device.idle_pct",
    "device.peak_hbm_gb", "idle.trainer.data_ms", "idle.trainer.step_ms",
    "idle.trainer.loss_fetch_ms", "idle.ps.prepare_ms", "idle.ps.dispatch_ms",
    "idle.ps.wait_ms", "idle.ps.step_ms", "idle.in_program_ms",
    "idle.outside_ms", "moe.experts_ms", "moe.dispatch_ms",
    "moe.load_max_over_mean", "setup.import_s", "setup.state_s",
    "setup.step_trace_s", "setup.step_lower_s", "setup.step_compile_s",
    "setup.first_step_s", "setup.compile_s", "setup.programs", "cache.hits"]


def entry(doc, group, name):
    return next(m for m in doc[group] if m["name"] == name)


def test_the_appended_entries_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cell = entry(doc, "workloads", NEW_CELL)
    assert cell == dict(cell, config="solar-open2-250b", traffic="lm8k",
                        chips=1)
    assert len(cell["why"]) <= 200
    for word in ("delta-rule", "GQA", "1/40", "40 %"):
        assert word in cell["why"], word
    config = entry(doc, "configs", "solar-open2-250b")
    assert config["reduced"] == config_file()["reduced"]
    assert config["file"] == "chipbench/configs/solar-open2-250b.json"
    assert config["source"] == SOURCE_URL
    for name in NEW_READERS:
        m = entry(doc, "per_layer", name)
        assert NEW_CELL in m["workloads"] and m["moves"] == "tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["unit"] == ("%" if name.endswith("_pct") else "ms")
        assert m["source"] == "device_trace"
    for name in APPENDED:
        group = "end_to_end" if name == "tokens_per_s" else "per_layer"
        assert NEW_CELL in entry(doc, group, name)["workloads"], name
    # readers whose count is another family's do not list the cell
    for name in ("model.mla_moe_mfu_pct", "model.moe_mfu_pct", "model.mfu_pct",
                 "attn.mla_kernel_ms", "ssm.scan_ms"):
        assert NEW_CELL not in entry(doc, "per_layer", name)["workloads"]
    for m in doc["per_layer"]:
        if m["moves"] == "staleness_mean":
            assert NEW_CELL not in m["workloads"]
    assert "workloads" not in entry(doc, "per_layer", "cache.misses")
    # a layer's name is one spelling
    assert entry(doc, "per_layer", "model.kda_moe_mfu_pct")["layer"] == entry(
        doc, "per_layer", "model.mfu_pct")["layer"]
    assert entry(doc, "per_layer", "kda.scan_ms")["layer"] == entry(
        doc, "per_layer", "kda.scan_roofline_pct")["layer"]


def synthetic():
    """A reduced trace of 2 steps with the chunked scan's events (a loop and
    its body), the scope table that joins them, and the cell."""
    by_name = {
        "%fusion.31 = f32[8,128,64,64] fusion(%x), kind=kLoop": (12, 0.012),
        "%triangular-solve.2 = f32[8,128,64,256] triangular-solve(%a, %b)":
            (12, 0.018),
        "%while.7 = (s32[], f32[8,128,128]) while(%t)": (12, 0.400),
        "%fusion.32 = f32[8,64,128] fusion(%s), kind=kOutput": (1536, 0.030),
        "%fusion.40 = bf16[8192,1024] fusion(%x), kind=kOutput": (30, 0.050),
        "%ragged-dot.5 = bf16[65536,1280] ragged-dot(%a, %b, %g)": (72, 0.150),
    }
    trace = {"steps": 2, "step_device_s": 0.3, "window_s": 0.7,
             "busy_s": 0.6, "by_name": by_name}
    counters = {"chips": 1, "scopes": {
        "%fusion.31": "kda.scan", "%triangular-solve.2": "kda.scan",
        "%while.7": "kda.scan", "%fusion.32": "kda.scan",
        "%fusion.40": "kda.proj", "%ragged-dot.5": "moe.experts"}}
    cell = {"name": NEW_CELL, "config": config_file(),
            "shape": dict(CELL, dtype_bytes=2),
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    return trace, counters, cell


def test_the_readers_on_a_synthetic_trace(capfd):
    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    trace, counters, cell = synthetic()
    read = lambda name: man.reader(name)(trace, {}, counters, cell)
    # the loop's own event is left out beside its body's; no other scope
    scan_s = (0.012 + 0.018 + 0.030) / 2
    assert read("kda.scan_ms") == pytest.approx(1e3 * scan_s)
    least = flops_kda.kda_scan_cost(**CELL, dtype_bytes=2)["bytes"] / 819e9
    assert read("kda.scan_roofline_pct") == pytest.approx(100 * least / scan_s)
    assert read("model.kda_moe_mfu_pct") == pytest.approx(
        100 * flops_kda.train_flops(**CELL) / 197e12 / 0.3)
    rows = [json.loads(l) for l in capfd.readouterr().out.splitlines()]
    assert {r["check"]: r["bound"] for r in rows} == {
        "kda.scan_roofline_pct": "memory"}
    for name in NEW_READERS:      # a share stays a share
        assert 0 < read(name) <= 100 or name.endswith("_ms")
    # the grouped products' reader reads this cell through its shape
    assert man.reader("moe.rows_gmm_roofline_pct")(
        trace, {}, counters, cell) is not None


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
    # this family's cell on a program without the scopes (the whole step's
    # share needs none: it reads the step's time)
    trace, counters, mine = synthetic()
    if metric != "model.kda_moe_mfu_pct":
        assert read(full, {}, {"chips": 1}, mine) is None
        assert read(full, {}, {"chips": 1, "scopes": {
            "%fusion.1": "kda.proj"}}, mine) is None
    # another family's cells, scope table and all
    for shape in ({"seq": 4096, "rows": 2, "experts_held": 16},
                  {"seq": 8192, "rows": 1, "mamba_layers": 2},
                  {"seq": 8192, "rows": 2, "taps": 3, "conv_layers": 5}):
        other = dict(cell, peaks=peaks, shape=shape)
        assert read(trace, {}, counters, other) is None


def test_rehearsal_of_the_tiny_solar_cell(tmp_path, capfd):
    """``jobs/sync_train_streamed.py`` end to end on a tiny ``solar``
    configuration with both operator kinds: the family, the reference's
    ``terms`` and ``router_loads`` (the delta rule token by token) in the
    streamed comparison, the shared expert and the untied head through the
    reference's Adam, and the counters a CPU run may report."""
    manifest, doc = rehearsal_manifest(
        str(tmp_path),
        extra_cells={"tiny-solar.lm": ("tiny-solar", "tiny-lm-streamed", 1)})
    line, earlier = run_cell(capfd, manifest, "tiny-solar.lm", trace=1)
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    ref = {r["check"]: r for r in earlier if "check" in r}["reference"]
    assert ref["ok"] and ref["loss_rel"] < 1e-5
    assert ref["update_sign_share"] > 0.99 and ref["update_rel_l2"] < 1e-2
    assert ref["worst_expert_sign_share"] > 0.99
    # what the limits are set against: bf16 parameters lose the update
    assert ref["if_bf16_params"]["update_rel_l2"] > 0.3
    # three expert layers: the program's router is the reference's
    assert ref["router_tie_share"] == 0.0
    assert ref["router_loads_step1"] == ref["reference_router_loads_step1"]
    assert len(ref["router_loads_step1"]) == 3
    m = line["metrics"]
    assert m["step.compiles_in_window"]["value"] == 0
    assert m["moe.load_max_over_mean"]["value"] >= 1.0
    counts = {x["name"] for x in doc["per_layer"]
              if x["source"] == "program_counter"}
    assert set(m) <= counts
    # the program's set-up log holds the delta rule's plan, a row a trace
    # of a delta-rule layer
    from pytorch_ps_mpi_tpu import telemetry

    plans = [r["attrs"] for r in telemetry.setup_rows()
             if r["name"] == "kda.plan"]
    assert plans and all(p["chunk"] == 64 and p["dk"] == 8 and p["heads"] == 2
                         for p in plans)
    shutil.rmtree(os.path.join(ROOT, ".chipbench_run", "tiny-solar.lm"),
                  ignore_errors=True)
