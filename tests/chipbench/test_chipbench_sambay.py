"""``flops_sambay.py`` against numbers worked by hand, the configuration
``phi4-mini-flash`` against the catalog's row, the two new mixes, the six
new readers on a synthetic trace and where there is nothing to read, the
appended entries of ``BENCHMARK.json`` (looked up by name), and a
rehearsal of the streamed job on a tiny ``sambay`` cell."""

import json
import os
import shutil

import pytest
from test_chipbench_rehearsal import (LINE_KEYS, ROOT, rehearsal_manifest,
                                      run_cell)

from chipbench import flops_sambay
from chipbench.run import Manifest

# phi4-mini-flash.lm8k: 1 row of 8,192 positions, hidden 2560, MLP 10240,
# 40 query heads over 20 key-value heads of 64, window 512, d_inner 5120,
# state 16, dt rank 160, conv 4, vocabulary 25,008; layers: 2 Mamba, 1 GMU,
# 1 window, 1 full, 1 cross. Forward, 2 operations a multiply-add:
#   mlp       2 * 8192 * 2560 * 10240 * 3 * 6                    = 7,730,941,132,800
#   ssm_proj  2 * 8192 * 2 * (2560*10240 + 5120*192 + 160*5120
#                             + 5120*2560 + 5120*4)             = 1,348,217,077,760
#   ssm_scan  6 * 8192 * 5120 * 16 * 2                           =     8,053,063,680
#   gmu       2 * 8192 * 2 * 2560 * 5120                         =   429,496,729,600
#   q_o_proj  2 * 8192 * 2 * 2560 * 2560 * 3                     =   644,245,094,400
#   kv_proj   2 * 8192 * 2560 * 2560 * 2                         =   214,748,364,800
#   pairs     causal 8192 * 8193 / 2 = 33,558,528; window 512 * 513 / 2
#             + 7680 * 512 = 4,063,488; 40 maps * (2 * 33,558,528 + 4,063,488)
#                                                                =     2,847,221,760
#   scores    2 * 2,847,221,760 * 64                             =   364,444,385,280
#   values    2 * 2,847,221,760 * 128                            =   728,888,770,560
#   head      2 * 8192 * 2560 * 25008                            = 1,048,911,544,320
#   sum 12,517,946,163,200; a training step is 3x               = 37,553,838,489,600
CELL = dict(rows=1, seq=8192, hidden=2560, ffn=10240, heads=40, kv_heads=20,
            head_dim=64, window=512, d_inner=5120, d_state=16, dt_rank=160,
            d_conv=4, vocab=25008, mamba_layers=2, gmu_layers=1,
            window_layers=1, full_layers=1, cross_layers=1)
# tiny, by hand: 1 row of 8 positions, hidden 4, MLP 6, 2 heads over 2 of
# dim 2, window 3, d_inner 8, state 2, dt rank 1, conv 4, vocabulary 5; one
# layer of each of mamba, gmu, window, cross (no full layer)
#   pairs: causal 36, window 3 + 6 + 5*3 = wait: 3*4/2 + 5*3 = 21; 2 maps
TINY = dict(rows=1, seq=8, hidden=4, ffn=6, heads=2, kv_heads=2, head_dim=2,
            window=3, d_inner=8, d_state=2, dt_rank=1, d_conv=4, vocab=5,
            mamba_layers=1, gmu_layers=1, window_layers=1, full_layers=0,
            cross_layers=1)


@pytest.mark.parametrize("shape, klass, want", [
    (CELL, "mlp", 7_730_941_132_800),
    (CELL, "ssm_proj", 1_348_217_077_760),
    (CELL, "ssm_scan", 8_053_063_680),
    (CELL, "gmu", 429_496_729_600),
    (CELL, "q_o_proj", 644_245_094_400),
    (CELL, "kv_proj", 214_748_364_800),
    (CELL, "attn_scores", 364_444_385_280),
    (CELL, "attn_values", 728_888_770_560),
    (CELL, "vocab_proj", 1_048_911_544_320),
    (TINY, "mlp", 2 * 8 * 4 * 6 * 3 * 4),
    (TINY, "ssm_proj", 2 * 8 * (4 * 16 + 8 * 5 + 8 + 8 * 4 + 8 * 4)),
    (TINY, "ssm_scan", 6 * 8 * 8 * 2),
    (TINY, "gmu", 2 * 8 * 2 * 4 * 8),
    (TINY, "q_o_proj", 2 * 8 * 2 * 4 * 4 * 2),
    (TINY, "kv_proj", 2 * 8 * 4 * 8),
    (TINY, "attn_scores", 2 * 2 * (36 + 21) * 2),
    (TINY, "attn_values", 2 * 2 * (36 + 21) * 4),
    (TINY, "vocab_proj", 2 * 8 * 4 * 5),
])
def test_forward_classes(shape, klass, want):
    assert flops_sambay.forward_flops(**shape)[klass] == want


def test_train_step_and_pairs():
    assert flops_sambay.train_flops(**CELL) == 37_553_838_489_600
    assert flops_sambay.attention_pairs(seq=8192) == 33_558_528
    assert flops_sambay.attention_pairs(seq=8192, window=512) == 4_063_488
    # a window as long as the sequence, or longer, is the causal mask
    assert flops_sambay.attention_pairs(seq=8, window=8) == 36
    assert flops_sambay.attention_pairs(seq=8, window=99) == 36
    assert flops_sambay.attention_pairs(seq=8, window=1) == 8


def test_kernel_costs():
    # 10 x 2 x 64 operations a pair (scores, their recomputation, dq, dk
    # at 64; values, dv, dp at 128); q, o, do, dq over 20 pairs and k, v,
    # dk, dv over 10, 128 wide, 8,192 positions, bf16, three layers
    cost = flops_sambay.diff_attention_kernel_cost(**CELL, dtype_bytes=2)
    assert cost["flops"] == 10 * 2 * 2_847_221_760 * 64
    assert cost["bytes"] == 6 * (20 + 10) * (8192 * 128 * 2 * 3)
    # a layer: forward xh (bf16), Dt, y (float32) = 10 bytes a channel and
    # step; backward xh, Dt, dy read, d xh, d Dt written = 18; B, C, A, D
    # beside them, once forward and twice backward
    scan = flops_sambay.scan_cost(**CELL, dtype_bytes=2)
    wide, narrow = 8192 * 5120, 2 * 8192 * 16 * 4 + (5120 * 16 + 5120) * 4
    assert scan["bytes"] == 2 * (wide * 28 + 3 * narrow)
    assert scan["flops"] == 3 * 8_053_063_680
    # the memory bounds it by far
    assert scan["bytes"] / 819e9 > 20 * scan["flops"] / 197e12


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Phi-4-mini-flash-reasoning")


def config_file():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "phi4-mini-flash.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_number():
    row, cfg = catalog_row(), config_file()
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published_" + key] == value
            assert cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert row["source_url"] in cfg["source"] and len(cfg["source"]) <= 200


def test_the_cut_and_what_the_file_states():
    cfg = config_file()
    assert cfg["family"] == "sambay"
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 6
    assert cfg["layer_types"] == [
        "mamba", "sliding_attention", "mamba_memory", "full_attention", "gmu",
        "cross_attention"]
    assert cfg["published_layer_index"] == [0, 1, 16, 17, 18, 19]
    assert cfg["vocab_size"] * 8 == cfg["published_vocab_size"] + 0
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            cfg["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert cfg["dtype"] == "bfloat16" and cfg["param_dtype"] == "float32"
    assert cfg["remat"] is True and cfg["optimizer"]["name"] == "adam"
    for key in ("assumed", "deployment", "guarantees", "tolerances"):
        assert cfg[key], key
    assert len(cfg["assumed"]) >= 8 and cfg["tolerances"]["reason"]
    for limit in ("loss_rel", "update_sign_share", "update_rel_l2"):
        assert 0 < cfg["tolerances"][limit] < 1


def test_the_family_builds_the_cut_and_counts_the_uncut_model():
    import jax

    from chipbench.families import sambay as family
    from pytorch_ps_mpi_tpu.models import sambay

    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cfg, traffic = man.config("phi4-mini-flash"), man.traffic("lm8k")
    fam = family.build(cfg, traffic)
    assert sambay.param_count(fam.cfg) == 697_094_272
    assert fam.shape == {k: v for k, v in CELL.items()
                         if k not in ("rows", "head_dim")}
    assert (fam.unit, fam.units_per_row, fam.head_dim, fam.dtype_bytes) == (
        "tokens", 8192, 64, 2)
    shapes = jax.eval_shape(fam.init, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 697_094_272
    assert "kv_proj" not in shapes["layer_5"]["mixer"]
    batch = next(fam.batches(2 ** 31 + 5, 1))
    assert batch["tokens"].shape == (1, 8192)
    assert 0 <= batch["tokens"].min() and batch["tokens"].max() < 25008
    with pytest.raises(ValueError, match="uncut sizes"):
        family.build(dict(cfg, published_parameter_count=3_800_000_000),
                     traffic)
    with pytest.raises(ValueError, match="exceeds"):
        family.build(cfg, dict(traffic, seq=2 ** 19))


def test_the_two_mixes():
    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    lm8k, mlm512 = man.traffic("lm8k"), man.traffic("mlm512")
    assert lm8k == {
        "job": "sync_train_streamed", "generator": "lm_zipf",
        "generator_params": {"exponent": 1.0}, "seq": 8192,
        "rows_per_chip": 1, "mode": "allgather", "codec": None,
        "bucket_mb": 0, "steps_per_fit": 3, "trace_fit_calls": 2}
    # chipbench/README.md's example, key for key
    assert mlm512 == {
        "job": "sync_train", "generator": "mlm_uniform",
        "generator_params": {"mask_rate": 0.15}, "seq": 512,
        "rows_per_chip": 16, "mode": "allgather", "codec": None,
        "bucket_mb": 0, "steps_per_fit": 10, "trace_fit_calls": 2}
    for mix in (lm8k, mlm512):   # 8,192 tokens a step, as every language cell
        assert mix["seq"] * mix["rows_per_chip"] == 8192


NEW_READERS = ["model.ssm_mfu_pct", "ssm.scan_ms", "ssm.scan_roofline_pct",
               "attn.diff_kernel_ms", "attn.diff_roofline_pct",
               "attn.window_dead_share"]
NEW_CELLS = ["phi4-mini-flash.lm8k", "bert-base.mlm512"]


def entry(doc, group, name):
    return next(m for m in doc[group] if m["name"] == name)


def test_the_appended_entries_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cells = {c["name"]: c for c in doc["workloads"]}
    assert len(cells) == 7
    assert sum(c["chips"] == 4 for c in cells.values()) == 1
    assert cells["phi4-mini-flash.lm8k"] == dict(
        cells["phi4-mini-flash.lm8k"], config="phi4-mini-flash",
        traffic="lm8k", chips=1)
    assert cells["bert-base.mlm512"] == dict(
        cells["bert-base.mlm512"], config="bert-base", traffic="mlm512",
        chips=1)
    config = entry(doc, "configs", "phi4-mini-flash")
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["file"] == "chipbench/configs/phi4-mini-flash.json"
    for name in NEW_READERS:
        m = entry(doc, "per_layer", name)
        assert m["workloads"] == ["phi4-mini-flash.lm8k"]
        assert m["moves"] == "tokens_per_s"
    assert entry(doc, "per_layer", "attn.window_dead_share")[
        "source"] == "program_counter"
    both = ["tokens_per_s", "loop.step_ms_p50", "loop.step_ms_p95",
            "step.device_ms", "step.compiles_in_window",
            "step.dispatch_ms_p50", "device.idle_pct", "device.peak_hbm_gb",
            "idle.in_program_ms", "idle.outside_ms", "idle.ps.step_ms"]
    for name in both:
        group = "end_to_end" if name == "tokens_per_s" else "per_layer"
        assert entry(doc, group, name)["workloads"][-2:] == NEW_CELLS, name
    for name in ("model.mfu_pct", "attn.kernel_ms", "attn.roofline_pct",
                 "flash_attention_roofline"):
        lists = entry(doc, "per_layer", name)["workloads"]
        assert "bert-base.mlm512" in lists
        assert "phi4-mini-flash.lm8k" not in lists
    for m in doc["per_layer"]:
        if m["moves"] == "staleness_mean":
            assert not set(NEW_CELLS) & set(m["workloads"])


def synthetic():
    """A reduced trace of 2 steps with the new kernels' and the scan's
    events, the scope table that joins them, and the cell."""
    call = ("(bf16[40,8192,128]) custom-call(%c, %q), custom_call_target="
            "\"tpu_custom_call\" [tpu_custom_call]")
    by_name = {
        f"%jvp_flash_win_fwd_.1 = {call}": (4, 0.004),
        f"%transpose_jvp_flash_win_dq__.1 = {call}": (2, 0.006),
        f"%transpose_jvp_flash_wide_dkv__.3 = {call}": (4, 0.030),
        f"%jvp_flash_wide_fwd_.2 = {call}": (8, 0.020),
        f"%flash_bd_fwd.4 = {call}": (2, 0.5),           # another family's
        f"%SelfAttention_0.9 = {call}": (2, 0.5),        # another family's
        # a loop's own events span its body's, which the trace holds too
        "%while.7 = (s32[], f32[1,16,5120], f32[128,64,1,5120]": (2, 0.200),
        "%while.9 = (s32[], f32[1,16,5120], f32[64,1,5120]": (256, 0.190),
        "%fusion.11 = f32[1,16,5120] fusion(%s), kind=kLoop": (16384, 0.180),
        "%fusion.13 = f32[64,1,16,5120] fusion(%x), kind=kLoop": (8, 0.060),
        "%fusion.12 = bf16[8192,10240] fusion(%x), kind=kOutput": (4, 0.100),
    }
    trace = {"steps": 2, "step_device_s": 0.5, "window_s": 1.2, "busy_s": 1.0,
             "by_name": by_name}
    counters = {"chips": 1, "scopes": {
        "%while.7": "ssm.scan", "%while.9": "ssm.scan",
        "%fusion.11": "ssm.scan", "%fusion.13": "ssm.scan",
        "%fusion.12": "mlp.swiglu"}}
    cell = {"name": "phi4-mini-flash.lm8k", "config": config_file(),
            "shape": dict(CELL, dtype_bytes=2),
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    return trace, counters, cell


def test_the_readers_on_a_synthetic_trace(capfd):
    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    trace, counters, cell = synthetic()
    read = lambda name: man.reader(name)(trace, {}, counters, cell)
    # the two operations of the loops' bodies, not the loops as well
    assert read("ssm.scan_ms") == pytest.approx(1e3 * 0.240 / 2)
    from chipbench import scope_time
    assert scope_time.seconds_per_step(trace, counters, r"^ssm\.scan$") == (
        pytest.approx((0.240 + 0.390) / 2))
    assert read("attn.diff_kernel_ms") == pytest.approx(1e3 * 0.060 / 2)
    # 37.55 TFLOP over 0.5 s x 197 TFLOP/s
    assert read("model.ssm_mfu_pct") == pytest.approx(
        100 * 37_553_838_489_600 / 197e12 / 0.5)
    least = 10 * 2 * 2_847_221_760 * 64 / 197e12      # compute-bound
    assert read("attn.diff_roofline_pct") == pytest.approx(
        100 * least / 0.030)
    scan_bytes = flops_sambay.scan_cost(**CELL, dtype_bytes=2)["bytes"]
    assert read("ssm.scan_roofline_pct") == pytest.approx(
        100 * scan_bytes / 819e9 / 0.120)
    assert read("attn.window_dead_share") == pytest.approx(100 * 225 / 256)
    rows = [json.loads(l) for l in capfd.readouterr().out.splitlines()]
    bounds = {r["check"]: r["bound"] for r in rows}
    assert bounds == {"attn.diff_roofline_pct": "compute",
                      "ssm.scan_roofline_pct": "memory"}
    for name in NEW_READERS:      # a share stays a share
        assert 0 <= read(name) <= 100 or name.endswith("_ms")


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_reader_reads_nothing_where_nothing_is(metric):
    """On a program that lacks what this PR adds (no scope table, no such
    kernel, no such plan, another family's shape) a reader returns None
    and does not raise."""
    read = Manifest(os.path.join(ROOT, "BENCHMARK.json")).reader(metric)
    cell = {"name": "no-such-run", "shape": {"seq": 8}, "peaks": None}
    assert read(None, {}, {}, cell) is None
    summary = {"steps": 3, "window_s": 1.0, "busy_s": 0.5}
    assert read(summary, {}, {}, cell) is None
    full = dict(summary, step_device_s=0.5, by_name={
        "%fusion.1 = f32[8] fusion(%x), kind=kLoop": (3, 0.3)})
    peaks = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}
    assert read(full, {}, {"chips": 1}, dict(cell, peaks=peaks)) is None
    # another family's cell, scope table and all
    other = dict(cell, peaks=peaks, config={"family": "sdar_moe"},
                 shape={"seq": 4096, "rows": 2, "experts_held": 16})
    assert read(full, {}, {"chips": 1, "scopes": {"%fusion.1": "moe.experts"}},
                other) is None


def test_rehearsal_of_the_tiny_sambay_cell(tmp_path, capfd):
    """``jobs/sync_train_streamed.py`` end to end on a tiny ``sambay``
    configuration: the family, the reference's ``terms`` in the streamed
    comparison, and the one new counter a CPU run may report."""
    manifest, doc = rehearsal_manifest(
        str(tmp_path),
        extra_cells={"tiny-sambay.lm": ("tiny-sambay", "tiny-lm-streamed", 1)})
    line, earlier = run_cell(capfd, manifest, "tiny-sambay.lm", trace=1)
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    ref = {r["check"]: r for r in earlier if "check" in r}["reference"]
    assert ref["ok"] and ref["loss_rel"] < 1e-5
    assert ref["update_sign_share"] > 0.999 and ref["update_rel_l2"] < 1e-2
    # what the limits are set against: bf16 parameters lose the update
    assert ref["if_bf16_params"]["update_rel_l2"] > 0.3
    assert "router_tie_share" not in ref
    m = line["metrics"]
    assert m["step.compiles_in_window"]["value"] == 0
    assert 0 <= m["attn.window_dead_share"]["value"] <= 100
    counts = {x["name"] for x in doc["per_layer"]
              if x["source"] == "program_counter"}
    assert set(m) <= counts
    shutil.rmtree(os.path.join(ROOT, ".chipbench_run", "tiny-sambay.lm"),
                  ignore_errors=True)
