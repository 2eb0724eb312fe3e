"""``flops_xing.py`` against numbers worked by hand, the configuration
``xing4-29b-a4b`` against the catalog's row, the mix ``lm4k``, the new
readers on a synthetic trace and where there is nothing to read, the
appended entries of ``BENCHMARK.json`` (looked up by NAME, never by
position), and a rehearsal of the streamed job on a tiny ``xing`` cell."""

import json
import os
import shutil

import pytest
from test_chipbench_rehearsal import (LINE_KEYS, ROOT, rehearsal_manifest,
                                      run_cell)

from chipbench import flops_xing
from chipbench.run import Manifest

# xing4-29b-a4b.lm4k: 1 row of 4,096 positions, hidden 3584, 32 heads, q
# rank 768, kv rank 512, keys 128 + 64 over a 128-wide value, dense SwiGLU
# 9216, experts of 1024, 64 routed (8 held, 4 a token), 1 shared, 4 streams,
# vocabulary 16,384; 1 dense + 4 expert layers, no prediction module.
# Forward, 2 operations a multiply-add, T = 4096, 5 attention layers:
#   q_proj    2 T (3584*768 + 768*32*192) * 5              =   306,016,419,840
#   kv_proj   2 T (3584*576 + 512*32*256) * 5              =   256,355,860,480
#   out_proj  2 T 32*128*3584 * 5                          =   601,295,421,440
#   pairs     32 * 4096 * 4097 / 2                         =       268,500,992
#   scores    2 * pairs * 192 * 5                          =   515,521,904,640
#   values    2 * pairs * 128 * 5                          =   343,681,269,760
#   hc weights 2 T 4*3584*24 * 2 * 5                       =    28,185,722,880
#   hc mixes  2 T 3584*24 * 2 * 5                          =     7,046,430,720
#   dense_ffn 2 T 3584*9216*3                              =   811,748,818,944
#   router    2 T 3584*64 * 4                              =     7,516,192,768
#   shared    2 T 3584*1024*3 * 4                          =   360,777,252,864
#   experts   2 * (T*4*8/64 = 2048) * 3*3584*1024 * 4      =   180,388,626,432
#   head      2 T 3584*16384                               =   481,036,337,152
#   sum 3,899,570,257,920; a training step is 3x          = 11,698,710,773,760
CELL = dict(rows=1, seq=4096, hidden=3584, heads=32, q_rank=768, kv_rank=512,
            nope_dim=128, rope_dim=64, v_dim=128, ffn=9216, expert_width=1024,
            experts=64, experts_held=8, top_k=4, shared_experts=1, streams=4,
            vocab=16384, dense_layers=1, expert_layers=4, mtp_modules=0)
# tiny, by hand: 1 row of 8 positions, hidden 4, 2 heads, ranks 3 and 2,
# keys 2 + 2 over a value of 3, dense 6, experts of 5, 4 routed (2 held, 1 a
# token), 2 shared, 2 streams, vocabulary 7; one layer of each kind and the
# prediction module: 3 attention layers, 2 expert layers; pairs 2 * 36
TINY = dict(rows=1, seq=8, hidden=4, heads=2, q_rank=3, kv_rank=2, nope_dim=2,
            rope_dim=2, v_dim=3, ffn=6, expert_width=5, experts=4,
            experts_held=2, top_k=1, shared_experts=2, streams=2, vocab=7,
            dense_layers=1, expert_layers=1, mtp_modules=1)


@pytest.mark.parametrize("shape, klass, want", [
    (CELL, "q_proj", 306_016_419_840),
    (CELL, "kv_proj", 256_355_860_480),
    (CELL, "out_proj", 601_295_421_440),
    (CELL, "attn_scores", 515_521_904_640),
    (CELL, "attn_values", 343_681_269_760),
    (CELL, "hc_weights", 28_185_722_880),
    (CELL, "hc_mixes", 7_046_430_720),
    (CELL, "dense_ffn", 811_748_818_944),
    (CELL, "router", 7_516_192_768),
    (CELL, "shared_experts", 360_777_252_864),
    (CELL, "experts", 180_388_626_432),
    (CELL, "mtp_join", 0),
    (CELL, "vocab_proj", 481_036_337_152),
    (TINY, "q_proj", 2 * 8 * (4 * 3 + 3 * 2 * 4) * 3),
    (TINY, "kv_proj", 2 * 8 * (4 * 4 + 2 * 2 * 5) * 3),
    (TINY, "out_proj", 2 * 8 * 2 * 3 * 4 * 3),
    (TINY, "attn_scores", 2 * 72 * 4 * 3),
    (TINY, "attn_values", 2 * 72 * 3 * 3),
    (TINY, "hc_weights", 2 * 8 * 2 * 4 * 8 * 2 * 3),
    (TINY, "hc_mixes", 2 * 8 * 4 * 8 * 2 * 3),
    (TINY, "dense_ffn", 2 * 8 * 4 * 6 * 3),
    (TINY, "router", 2 * 8 * 4 * 4 * 2),
    (TINY, "shared_experts", 2 * 8 * 4 * 5 * 2 * 3 * 2),
    (TINY, "experts", 2 * 4 * 3 * 4 * 5 * 2),
    (TINY, "mtp_join", 2 * 8 * 2 * 4 * 4),
    (TINY, "vocab_proj", 2 * 8 * 4 * 7 * 2),
])
def test_forward_classes(shape, klass, want):
    assert flops_xing.forward_flops(**shape)[klass] == want


def test_train_step_and_pairs():
    assert flops_xing.train_flops(**CELL) == 11_698_710_773_760
    assert flops_xing.allowed_pairs(**CELL) == 268_500_992
    assert flops_xing.pairs_held(**CELL) == 2048
    assert flops_xing.attention_layers(**CELL) == 5
    assert flops_xing.attention_layers(**TINY) == 3


def test_kernel_and_mix_costs():
    # four products at 192 (scores, their recomputation, dq, dk) and three
    # at 128 (values, dv, dp); six tensors of each width, bf16, five layers
    cost = flops_xing.mla_attention_kernel_cost(**CELL, dtype_bytes=2)
    assert cost["flops"] == 2 * 268_500_992 * (4 * 192 + 3 * 128) * 5
    assert cost["bytes"] == 6 * 4096 * 32 * (192 + 128) * 2 * 5
    # compute bounds the kernels by far
    assert cost["flops"] / 197e12 > 4 * cost["bytes"] / 819e9
    # ten hyper-connections: (3*4 + 2) + (5*4 + 3) = 37 values of hidden a
    # position in bf16
    mix = flops_xing.hc_mix_cost(**CELL, dtype_bytes=2)
    assert mix["bytes"] == 4096 * 3584 * 37 * 2 * 10
    assert mix["flops"] == 3 * 2 * 4096 * 3584 * (4 * 24 + 24) * 10
    # the memory bounds them by far
    assert mix["bytes"] / 819e9 > 20 * mix["flops"] / 197e12


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")


def config_file():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "xing4-29b-a4b.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_number():
    row, cfg = catalog_row(), config_file()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "num_nextn_predict_layers"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published_" + key] == value
            assert cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert row["source_url"] in cfg["source"] and len(cfg["source"]) <= 200


def test_the_cut_and_what_the_file_states():
    cfg = config_file()
    assert cfg["family"] == "xing"
    # ONE leading dense layer and four expert layers, at their published
    # indices; first_k_dense_replace stays what the source says
    assert cfg["num_hidden_layers"] == len(cfg["published_layer_index"]) == 5
    assert cfg["published_layer_index"] == [0, 2, 3, 4, 5]
    assert cfg["first_k_dense_replace"] == 2
    assert sum(i >= 2 for i in cfg["published_layer_index"]) >= 4   # the floor
    assert cfg["n_routed_experts"] == 8 and cfg["first_expert"] == 0
    assert cfg["vocab_size"] * 8 == cfg["published_vocab_size"]
    assert cfg["num_nextn_predict_layers"] == 0   # the retreat, as stated
    assert any("num_nextn_predict_layers 0" in a for a in cfg["assumed"])
    assert cfg["dtype"] == "bfloat16" and cfg["param_dtype"] == "float32"
    assert cfg["remat"] is True and cfg["optimizer"]["name"] == "adam"
    # 8 cannot overflow: 64 x min(4, 8) / (4 x 8)
    assert cfg["moe_capacity_factor"] == 64 * 4 / (4 * 8)
    for key in ("assumed", "deployment", "guarantees", "tolerances"):
        assert cfg[key], key
    assert len(cfg["assumed"]) >= 10 and cfg["tolerances"]["reason"]
    for limit in ("loss_rel", "update_sign_share", "update_rel_l2",
                  "worst_expert_sign_share", "worst_expert_rel_l2",
                  "router_tie_share"):
        assert 0 < cfg["tolerances"][limit] <= 1, limit


def test_the_family_builds_the_cut_and_counts_the_uncut_model():
    import jax

    from chipbench.families import xing as family
    from pytorch_ps_mpi_tpu.models import xing

    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cfg, traffic = man.config("xing4-29b-a4b"), man.traffic("lm4k")
    fam = family.build(cfg, traffic)
    assert xing.param_count(fam.cfg) == 759_346_446
    assert fam.shape == {k: v for k, v in CELL.items() if k != "rows"}
    assert (fam.unit, fam.units_per_row, fam.head_dim, fam.dtype_bytes) == (
        "tokens", 4096, 192, 2)
    assert fam.cfg.layers_dense == (True, False, False, False, False)
    assert fam.cfg.experts_held == (0, 8) and fam.cfg.n_routed_experts == 64
    shapes = jax.eval_shape(fam.init, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 759_346_446
    assert shapes["layer_0"]["mlp"]["gate_proj"].shape == (3584, 9216)
    assert shapes["layer_1"]["experts"]["gate_proj"].shape == (8, 3584, 1024)
    assert shapes["layer_1"]["router"].shape == (3584, 64)
    assert shapes["layer_1"]["self_attn"]["q_b_proj"].shape == (768, 32 * 192)
    assert shapes["layer_1"]["hc_mlp"]["w_res"].shape == (4, 3584, 16)
    assert "mtp" not in shapes
    # with the module the cut is the issue's 913.5 M; the uncut model with
    # it is the file's published_parameter_count, without it the "29B"
    with_module = family.build(dict(cfg, num_nextn_predict_layers=1), traffic)
    assert xing.param_count(with_module.cfg) == 913_473_668
    assert cfg["published_parameter_count"] == 30_276_195_174
    batch = next(fam.batches(2 ** 31 + 5, 1))
    assert batch["tokens"].shape == (1, 4096)
    assert 0 <= batch["tokens"].min() and batch["tokens"].max() < 16384
    with pytest.raises(ValueError, match="uncut sizes"):
        family.build(dict(cfg, published_parameter_count=29_500_000_000),
                     traffic)
    with pytest.raises(ValueError, match="exceeds"):
        family.build(cfg, dict(traffic, seq=2 ** 19))


def test_the_mix():
    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    assert man.traffic("lm4k") == {
        "job": "sync_train_streamed", "generator": "lm_zipf",
        "generator_params": {"exponent": 1.0}, "seq": 4096,
        "rows_per_chip": 1, "mode": "allgather", "codec": None,
        "bucket_mb": 0, "steps_per_fit": 3, "trace_fit_calls": 2}


NEW_CELL = "xing4-29b-a4b.lm4k"
NEW_READERS = ["model.mla_moe_mfu_pct", "attn.mla_kernel_ms",
               "attn.mla_roofline_pct", "hc.mix_ms", "hc.mix_roofline_pct",
               "moe.lm_gmm_roofline_pct"]
APPENDED = ["tokens_per_s", "loop.step_ms_p50", "loop.step_ms_p95",
            "step.device_ms", "step.compiles_in_window",
            "step.dispatch_ms_p50", "device.idle_pct", "device.peak_hbm_gb",
            "idle.trainer.data_ms", "idle.ps.prepare_ms",
            "idle.ps.dispatch_ms", "idle.ps.wait_ms",
            "idle.trainer.loss_fetch_ms", "idle.ps.step_ms",
            "idle.trainer.step_ms", "idle.in_program_ms", "idle.outside_ms",
            "moe.experts_ms", "moe.dispatch_ms", "moe.load_max_over_mean"]


def entry(doc, group, name):
    return next(m for m in doc[group] if m["name"] == name)


def test_the_appended_entries_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cell = entry(doc, "workloads", NEW_CELL)
    assert cell == dict(cell, config="xing4-29b-a4b", traffic="lm4k", chips=1)
    assert len(cell["why"]) <= 200
    chips4 = sum(c["chips"] == 4 for c in doc["workloads"])
    assert chips4 <= max(1, len(doc["workloads"]) // 4)
    config = entry(doc, "configs", "xing4-29b-a4b")
    assert config["reduced"] == config_file()["reduced"]
    assert config["file"] == "chipbench/configs/xing4-29b-a4b.json"
    assert config["source"] == catalog_row()["source_url"]
    for name in NEW_READERS:
        m = entry(doc, "per_layer", name)
        assert NEW_CELL in m["workloads"] and m["moves"] == "tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["unit"] == ("%" if name.endswith("_pct") else "ms")
    for name in APPENDED:
        group = "end_to_end" if name == "tokens_per_s" else "per_layer"
        assert NEW_CELL in entry(doc, group, name)["workloads"], name
    # readers whose count is another family's do not list the cell
    for name in ("model.moe_mfu_pct", "moe.gmm_roofline_pct", "model.mfu_pct",
                 "attn.bd_kernel_ms", "attn.diff_kernel_ms", "ssm.scan_ms"):
        assert NEW_CELL not in entry(doc, "per_layer", name)["workloads"]
    for m in doc["per_layer"]:
        if m["moves"] == "staleness_mean":
            assert NEW_CELL not in m["workloads"]
    # a layer's name is one spelling
    layers = {m["layer"] for m in doc["per_layer"]}
    assert entry(doc, "per_layer", "hc.mix_ms")["layer"] in layers
    assert entry(doc, "per_layer", "attn.mla_kernel_ms")[
        "layer"] == entry(doc, "per_layer", "attn.kernel_ms")["layer"]


def synthetic():
    """A reduced trace of 2 steps with the latent-attention kernels' and
    the hyper-connections' events, the scope table that joins them, and
    the cell."""
    call = ("(bf16[32,4096,128]) custom-call(%c, %q), custom_call_target="
            "\"tpu_custom_call\" [tpu_custom_call]")
    by_name = {
        f"%checkpoint_flash_wide_fwd_.1 = {call}": (20, 0.060),
        f"%transpose_jvp_flash_wide_dq__.1 = {call}": (10, 0.050),
        f"%transpose_jvp_flash_wide_dkv__.3 = {call}": (10, 0.070),
        f"%flash_bd_fwd.4 = {call}": (2, 0.5),           # another family's
        f"%jvp_flash_win_fwd_.9 = {call}": (2, 0.5),     # another family's
        "%fusion.11 = f32[16,1,4096] fusion(%s), kind=kLoop": (40, 0.020),
        "%fusion.13 = bf16[4,1,4096,3584] fusion(%x), kind=kLoop": (60, 0.100),
        "%fusion.15 = bf16[1,4096,3584] fusion(%x), kind=kLoop": (8, 0.030),
        "%while.3 = (s32[], f32[16,1,4096]) while(%t)": (2, 0.400),
        "%fusion.12 = bf16[4096,9216] fusion(%x), kind=kOutput": (4, 0.100),
        "%ragged-dot.5 = bf16[16384,1024] ragged-dot(%a, %b, %g)": (72, 0.040),
    }
    trace = {"steps": 2, "step_device_s": 0.4, "window_s": 1.0, "busy_s": 0.8,
             "by_name": by_name}
    counters = {"chips": 1, "moe_pairs_held_per_step": 9000.0, "scopes": {
        "%fusion.11": "hc.sinkhorn", "%fusion.13": "hc.mix",
        "%fusion.15": "mtp.hc.mix", "%while.3": "hc.sinkhorn",
        "%fusion.12": "mlp.swiglu", "%ragged-dot.5": "moe.experts"}}
    cell = {"name": NEW_CELL, "config": config_file(),
            "shape": dict(CELL, head_dim=192, dtype_bytes=2),
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    return trace, counters, cell


def test_the_readers_on_a_synthetic_trace(capfd):
    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    trace, counters, cell = synthetic()
    read = lambda name: man.reader(name)(trace, {}, counters, cell)
    assert read("attn.mla_kernel_ms") == pytest.approx(1e3 * 0.180 / 2)
    # the loop's own event is left out beside its body's
    assert read("hc.mix_ms") == pytest.approx(1e3 * 0.150 / 2)
    # 11.70 TFLOP over 0.4 s x 197 TFLOP/s
    assert read("model.mla_moe_mfu_pct") == pytest.approx(
        100 * 11_698_710_773_760 / 197e12 / 0.4)
    least = 2 * 268_500_992 * 1152 * 5 / 197e12       # compute-bound
    assert read("attn.mla_roofline_pct") == pytest.approx(100 * least / 0.090)
    assert read("hc.mix_roofline_pct") == pytest.approx(
        100 * (4096 * 3584 * 37 * 2 * 10 / 819e9) / 0.075)
    # 9 products a layer, four layers: each reads or writes its 2,048
    # expected rows on both sides and the eight held matrices once; at 256
    # rows an expert the matrices' bytes bound it, not the operations
    gmm = 9 * (2048 * (3584 + 1024) + 8 * 3584 * 1024) * 2 * 4 / 819e9
    assert gmm > 9 * 2 * 2048 * 3584 * 1024 * 4 / 197e12
    assert read("moe.lm_gmm_roofline_pct") == pytest.approx(
        100 * gmm / 0.020)
    rows = [json.loads(l) for l in capfd.readouterr().out.splitlines()]
    bounds = {r["check"]: r["bound"] for r in rows}
    assert bounds == {"attn.mla_roofline_pct": "compute",
                      "hc.mix_roofline_pct": "memory",
                      "moe.lm_gmm_roofline_pct": "memory"}
    for name in NEW_READERS:      # a share stays a share
        assert 0 < read(name) <= 100 or name.endswith("_ms")


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_reader_reads_nothing_where_nothing_is(metric):
    """On a program that lacks what this PR adds (no scope table, no such
    kernel, another family's shape) a reader returns None and does not
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
    if metric != "model.mla_moe_mfu_pct":
        assert read(full, {}, {"chips": 1}, mine) is None
    # another family's cells, scope table, wide kernels and all
    for shape in ({"seq": 4096, "rows": 2, "experts_held": 16},
                  {"seq": 8192, "rows": 1, "mamba_layers": 2}):
        other = dict(cell, peaks=peaks, shape=shape)
        assert read(trace, {}, counters, other) is None


def test_rehearsal_of_the_tiny_xing_cell(tmp_path, capfd):
    """``jobs/sync_train_streamed.py`` end to end on a tiny ``xing``
    configuration WITH its prediction module: the family, the reference's
    ``terms`` and ``router_loads`` in the streamed comparison, the frozen
    bias through the reference's Adam, and the counters a CPU run may
    report."""
    manifest, doc = rehearsal_manifest(
        str(tmp_path),
        extra_cells={"tiny-xing.lm": ("tiny-xing", "tiny-lm-streamed", 1)})
    line, earlier = run_cell(capfd, manifest, "tiny-xing.lm", trace=1)
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    ref = {r["check"]: r for r in earlier if "check" in r}["reference"]
    assert ref["ok"] and ref["loss_rel"] < 1e-5
    assert ref["update_sign_share"] > 0.95 and ref["update_rel_l2"] < 1e-2
    assert ref["worst_expert_sign_share"] > 0.99
    # what the limits are set against: bf16 parameters lose the update
    assert ref["if_bf16_params"]["update_rel_l2"] > 0.3
    # two expert layers and the module's: the program's router is the
    # reference's
    assert ref["router_tie_share"] == 0.0
    assert ref["router_loads_step1"] == ref["reference_router_loads_step1"]
    assert len(ref["router_loads_step1"]) == 3
    m = line["metrics"]
    assert m["step.compiles_in_window"]["value"] == 0
    assert m["moe.load_max_over_mean"]["value"] >= 1.0
    counts = {x["name"] for x in doc["per_layer"]
              if x["source"] == "program_counter"}
    assert set(m) <= counts
    shutil.rmtree(os.path.join(ROOT, ".chipbench_run", "tiny-xing.lm"),
                  ignore_errors=True)
