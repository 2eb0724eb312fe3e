"""``flops_xing.forward_flops`` at ``streams`` 0 against this cut's count
written out by hand, the configuration ``joyai-llm-flash`` against the
catalog's row, the family's refusals, the two new readers on a synthetic
trace and where there is nothing to read, the appended entries of
``BENCHMARK.json`` (looked up by NAME, never by position), and a rehearsal
of the streamed job on a tiny ``joyai`` cell WITH its prediction module."""

import json
import os
import shutil

import pytest
from test_chipbench_rehearsal import (LINE_KEYS, ROOT, rehearsal_manifest,
                                      run_cell)

from chipbench import flops_xing
from chipbench.run import Manifest

CELL = "joyai-llm-flash.lm8k"
# joyai-llm-flash.lm8k: 1 row of 8,192 positions, hidden 2048, 32 heads, q
# rank 1536, kv rank 512, keys 128 + 64 over a 128-wide value, dense SwiGLU
# 7168, experts of 768, 256 routed (16 held, 8 a token), 1 shared, NO
# stream, vocabulary 16,160; 1 dense + 4 expert layers and the prediction
# module: 6 attention layers, 5 expert layers, the head twice.
# Forward, 2 operations a multiply-add, T = 8192:
#   q_proj    2 T (2048*1536 + 1536*32*192) * 6            = 1,236,950,581,248
#   kv_proj   2 T (2048*576 + 512*32*256) * 6              =   528,280,977,408
#   out_proj  2 T 32*128*2048 * 6                          =   824,633,720,832
#   pairs     32 * 8192 * 8193 / 2                         =     1,073,872,896
#   scores    2 * pairs * 192 * 6                          = 2,474,203,152,384
#   values    2 * pairs * 128 * 6                          = 1,649,468,768,256
#   hc weights, hc mixes (no stream)                       =                 0
#   dense_ffn 2 T 2048*7168*3                              =   721,554,505,728
#   router    2 T 2048*256 * 5                             =    42,949,672,960
#   shared    2 T 2048*768*3 * 5                           =   386,547,056,640
#   experts   2 * (T*8*16/256 = 4096) * 3*2048*768 * 5     =   193,273,528,320
#   mtp_join  2 T 2*2048*2048                              =   137,438,953,472
#   head      2 T 2048*16160 * 2                           = 1,084,479,242,240
#   sum 9,279,780,159,488; a training step is 3x           = 27,839,340,478,464
SHAPE = dict(seq=8192, hidden=2048, heads=32, q_rank=1536, kv_rank=512,
             nope_dim=128, rope_dim=64, v_dim=128, ffn=7168, expert_width=768,
             experts=256, experts_held=16, top_k=8, shared_experts=1,
             streams=0, vocab=16160, dense_layers=1, expert_layers=4,
             mtp_modules=1)
BY_HAND = {
    "q_proj": 1_236_950_581_248, "kv_proj": 528_280_977_408,
    "out_proj": 824_633_720_832, "attn_scores": 2_474_203_152_384,
    "attn_values": 1_649_468_768_256, "hc_weights": 0, "hc_mixes": 0,
    "dense_ffn": 721_554_505_728, "router": 42_949_672_960,
    "shared_experts": 386_547_056_640, "experts": 193_273_528_320,
    "mtp_join": 137_438_953_472, "vocab_proj": 1_084_479_242_240,
}


@pytest.mark.parametrize("klass", sorted(BY_HAND))
def test_forward_classes_without_a_stream(klass):
    assert flops_xing.forward_flops(rows=1, **SHAPE)[klass] == BY_HAND[klass]


def test_the_step_is_27_84_tflop_and_where_they_go():
    forward = flops_xing.forward_flops(rows=1, **SHAPE)
    assert set(forward) == set(BY_HAND)
    assert sum(BY_HAND.values()) == 9_279_780_159_488
    assert flops_xing.train_flops(rows=1, **SHAPE) == 27_839_340_478_464
    assert flops_xing.allowed_pairs(rows=1, **SHAPE) == 1_073_872_896
    assert flops_xing.pairs_held(rows=1, **SHAPE) == 4096
    assert flops_xing.attention_layers(**SHAPE) == 6
    share = lambda *names: 100 * sum(forward[n] for n in names) / sum(
        forward.values())
    assert share("attn_scores", "attn_values") == pytest.approx(44.4, abs=0.1)
    assert share("q_proj", "kv_proj", "out_proj") == pytest.approx(27.9,
                                                                   abs=0.1)
    assert share("vocab_proj") == pytest.approx(11.7, abs=0.1)
    assert share("dense_ffn") == pytest.approx(7.8, abs=0.1)
    # the prediction module with its head: one of six blocks' attention,
    # one of five expert layers, the joining product, one head of two
    module = (sum(forward[n] for n in ("q_proj", "kv_proj", "out_proj",
                                       "attn_scores", "attn_values")) / 6
              + sum(forward[n] for n in ("router", "shared_experts",
                                         "experts")) / 5
              + forward["mtp_join"] + forward["vocab_proj"] / 2)
    assert 100 * module / sum(forward.values()) == pytest.approx(20.7, abs=0.1)
    # the least the three kernels of a step do: 75.4 ms at 197 TFLOP/s
    cost = flops_xing.mla_attention_kernel_cost(rows=1, **SHAPE, dtype_bytes=2)
    assert cost["flops"] == 2 * 1_073_872_896 * (4 * 192 + 3 * 128) * 6
    assert 1e3 * cost["flops"] / 197e12 == pytest.approx(75.4, abs=0.05)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "JoyAI-LLM-Flash")


def config_file():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "joyai-llm-flash.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_number():
    row, cfg = catalog_row(), config_file()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published_" + key] == value
            assert cfg[key] < value
        else:
            assert cfg[key] == value, key
    # the module is held, as published, and is no cut
    assert cfg["num_nextn_predict_layers"] == 1
    assert "published_num_nextn_predict_layers" not in cfg
    assert row["source_url"] in cfg["source"] and len(cfg["source"]) <= 200


def test_the_cut_and_what_the_file_states():
    cfg = config_file()
    assert cfg["family"] == "joyai" and "hc_mult" not in cfg
    assert cfg["num_hidden_layers"] == len(cfg["published_layer_index"]) == 5
    assert cfg["published_layer_index"] == [0, 1, 2, 3, 4]
    assert cfg["first_k_dense_replace"] == 1
    assert sum(i >= 1 for i in cfg["published_layer_index"]) >= 4   # the floor
    assert cfg["n_routed_experts"] == 16 and cfg["first_expert"] == 0
    assert cfg["n_routed_experts"] >= 8                             # the floor
    assert cfg["vocab_size"] * 8 == cfg["published_vocab_size"]     # the floor
    assert cfg["mtp_loss_weight"] == 0.3
    assert cfg["rope_interleave"] is True and cfg["rope_scaling"] is None
    assert cfg["dtype"] == "bfloat16" and cfg["param_dtype"] == "float32"
    assert cfg["remat"] is True and cfg["optimizer"] == {"name": "adam",
                                                         "lr": 1e-06}
    # 16 cannot overflow: 256 x min(8, 16) / (8 x 16)
    assert cfg["moe_capacity_factor"] == 256 * 8 / (8 * 16)
    for key in ("assumed", "deployment", "guarantees", "tolerances"):
        assert cfg[key], key
    assert len(cfg["assumed"]) >= 10 and len(cfg["tolerances"]["reason"]) > 200
    assert "16 chips share each layer" in cfg["deployment"]
    for limit in ("loss_rel", "update_sign_share", "update_rel_l2",
                  "worst_expert_sign_share", "worst_expert_rel_l2",
                  "router_tie_share"):
        assert 0 < cfg["tolerances"][limit] <= 1, limit
        # each limit quotes the two chip readings it lies between
        assert limit in cfg["tolerances"]["reason"], limit


def test_the_family_builds_the_cut_and_counts_the_uncut_model():
    import jax

    from chipbench.families import joyai as family
    from pytorch_ps_mpi_tpu.models import xing

    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cfg, traffic = man.config("joyai-llm-flash"), man.traffic("lm8k")
    fam = family.build(cfg, traffic)
    assert xing.param_count(fam.cfg) == 680_441_088
    assert fam.shape == SHAPE
    assert (fam.unit, fam.units_per_row, fam.head_dim, fam.dtype_bytes) == (
        "tokens", 8192, 192, 2)
    assert fam.cfg.hc_mult == 0 and fam.cfg.rope_interleave
    assert fam.cfg.layers_dense == (True, False, False, False, False)
    assert fam.cfg.experts_held == (0, 16) and fam.cfg.n_routed_experts == 256
    assert fam.cfg.num_nextn_predict_layers == 1
    shapes = jax.eval_shape(fam.init, jax.random.key(0))
    size = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
    assert size(shapes) == 680_441_088
    assert size(shapes["layer_0"]["self_attn"]) == 26_347_520
    assert size(shapes["layer_0"]) == 70_391_808
    assert size(shapes["layer_1"]) == 107_092_224
    assert size(shapes["mtp"]) == 115_486_976
    assert size(shapes["embed_tokens"]) + size(shapes["lm_head"]) == 66_191_360
    assert shapes["layer_1"]["experts"]["gate_proj"].shape == (16, 2048, 768)
    assert shapes["layer_1"]["router"].shape == (2048, 256)
    assert shapes["layer_1"]["self_attn"]["q_b_proj"].shape == (1536, 32 * 192)
    assert not [k for k in shapes["layer_1"] if k.startswith("hc_")]
    assert shapes["mtp"]["eh_proj"].shape == (4096, 2048)
    # the "48B" is the model without its module; the file counts it with
    assert cfg["published_parameter_count"] == 50_190_491_648
    uncut = dict(cfg, num_hidden_layers=40, n_routed_experts=256,
                 vocab_size=129_280, num_nextn_predict_layers=0,
                 published_layer_index=list(range(40)))
    assert xing.param_count(
        xing.XingConfig.from_source(uncut)) == 48_942_542_592
    batch = next(fam.batches(2 ** 31 + 5, 1))
    assert batch["tokens"].shape == (1, 8192)
    assert 0 <= batch["tokens"].min() and batch["tokens"].max() < 16160


@pytest.mark.parametrize("change, error, match", [
    (dict(published_parameter_count=48_942_542_592), ValueError,
     "uncut sizes"),
    (dict(n_group=2), ValueError, "n_group"),
    (dict(hc_mult=4), ValueError, "hc_mult"),
    (dict(moe_intermediate_size=1024), ValueError, "uncut sizes"),
])
def test_what_the_family_refuses(change, error, match):
    from chipbench.families import joyai as family

    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cfg, traffic = man.config("joyai-llm-flash"), man.traffic("lm8k")
    with pytest.raises(error, match=match):
        family.build(dict(cfg, **change), traffic)


def test_a_row_longer_than_the_model_reads_is_refused():
    from chipbench.families import joyai as family

    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cfg, traffic = man.config("joyai-llm-flash"), man.traffic("lm8k")
    with pytest.raises(ValueError, match="exceeds"):
        family.build(cfg, dict(traffic, seq=2 ** 18))


def test_the_mix_is_the_accepted_one_as_it_is():
    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    assert man.traffic("lm8k") == {
        "job": "sync_train_streamed", "generator": "lm_zipf",
        "generator_params": {"exponent": 1.0}, "seq": 8192,
        "rows_per_chip": 1, "mode": "allgather", "codec": None,
        "bucket_mb": 0, "steps_per_fit": 3, "trace_fit_calls": 2}


NEW_READERS = {"mtp.block_ms": "prediction module",
               "attn.mla_proj_ms": "latent attention"}
SHAPE_READERS = ["model.mla_moe_mfu_pct", "attn.mla_kernel_ms",
                 "attn.mla_roofline_pct", "moe.lm_gmm_roofline_pct"]
APPENDED = ["tokens_per_s", "loop.step_ms_p50", "loop.step_ms_p95",
            "step.device_ms", "step.compiles_in_window",
            "step.dispatch_ms_p50", "device.idle_pct", "device.peak_hbm_gb",
            "idle.trainer.data_ms", "idle.ps.prepare_ms",
            "idle.ps.dispatch_ms", "idle.ps.wait_ms",
            "idle.trainer.loss_fetch_ms", "idle.ps.step_ms",
            "idle.trainer.step_ms", "idle.in_program_ms", "idle.outside_ms",
            "setup.import_s", "setup.state_s", "setup.step_trace_s",
            "setup.step_lower_s", "setup.step_compile_s",
            "setup.first_step_s", "setup.compile_s", "setup.programs",
            "cache.hits", "moe.experts_ms", "moe.dispatch_ms",
            "moe.load_max_over_mean"] + SHAPE_READERS


def entry(doc, group, name):
    return next(m for m in doc[group] if m["name"] == name)


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_and_the_configuration_by_name():
    doc = benchmark()
    cell = entry(doc, "workloads", CELL)
    assert cell == dict(cell, config="joyai-llm-flash", traffic="lm8k",
                        chips=1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200
    chips4 = sum(c["chips"] == 4 for c in doc["workloads"])
    assert chips4 == 1 and chips4 <= max(1, len(doc["workloads"]) // 4)
    config = entry(doc, "configs", "joyai-llm-flash")
    assert config["reduced"] == config_file()["reduced"]
    assert "num_nextn_predict_layers" not in config["reduced"]
    assert config["file"] == "chipbench/configs/joyai-llm-flash.json"
    assert config["source"] == catalog_row()["source_url"]
    assert len(config["why"]) <= 200 and len(config["source"]) <= 200
    assert len(json.dumps(doc, indent=1)) < 64 * 1024
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in doc[g]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("name", APPENDED)
def test_the_cell_is_appended_to(name):
    group = "end_to_end" if name == "tokens_per_s" else "per_layer"
    assert CELL in entry(benchmark(), group, name)["workloads"], name


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_the_new_entry(name):
    doc = benchmark()
    m = entry(doc, "per_layer", name)
    assert m == {"name": name, "unit": "ms", "better": "lower",
                 "source": "device_trace", "layer": NEW_READERS[name],
                 "moves": "tokens_per_s", "workloads": [CELL]}
    assert os.path.exists(os.path.join(ROOT, "chipbench", "layer_metrics",
                                       name + ".py"))


def test_no_other_familys_reader_lists_the_cell():
    doc = benchmark()
    for name in ("hc.mix_ms", "hc.mix_roofline_pct", "model.moe_mfu_pct",
                 "moe.gmm_roofline_pct", "moe.rows_gmm_roofline_pct",
                 "model.mfu_pct", "model.conv_moe_mfu_pct", "attn.kernel_ms",
                 "attn.bd_kernel_ms", "attn.diff_kernel_ms",
                 "attn.gqa_kernel_ms", "ssm.scan_ms", "conv.mix_ms"):
        assert CELL not in entry(doc, "per_layer", name)["workloads"], name
    for m in doc["per_layer"]:
        if m["moves"] == "staleness_mean":
            assert CELL not in m["workloads"]
        if m["moves"] == "tokens_per_s" and "workloads" in m:
            # every cell a tokens_per_s metric lists reports tokens_per_s
            assert set(m["workloads"]) <= set(entry(
                doc, "end_to_end", "tokens_per_s")["workloads"])


def synthetic():
    """A reduced trace of 2 steps with the latent-attention kernels' and
    the scoped fusions' events, the scope table that joins them, and the
    cell."""
    call = ("(bf16[32,8192,128]) custom-call(%c, %q), custom_call_target="
            "\"tpu_custom_call\" [tpu_custom_call]")
    by_name = {
        f"%checkpoint_flash_wide_fwd_.1 = {call}": (12, 0.120),
        f"%transpose_jvp_flash_wide_dq__.1 = {call}": (12, 0.100),
        f"%transpose_jvp_flash_wide_dkv__.3 = {call}": (12, 0.140),
        "%fusion.11 = bf16[8192,6144] fusion(%x), kind=kOutput": (24, 0.080),
        "%fusion.12 = bf16[8192,2048] fusion(%x), kind=kOutput": (24, 0.040),
        "%fusion.13 = bf16[8192,6144] fusion(%x), kind=kOutput": (4, 0.014),
        "%fusion.14 = bf16[8192,2048] fusion(%x), kind=kOutput": (6, 0.010),
        "%fusion.15 = f32[8192,16160] fusion(%x), kind=kOutput": (6, 0.030),
        "%fusion.16 = bf16[8192,768] fusion(%x), kind=kOutput": (6, 0.006),
        "%fusion.17 = f32[8192,16160] fusion(%x), kind=kOutput": (6, 0.032),
        "%fusion.18 = bf16[8192,7168] fusion(%x), kind=kOutput": (4, 0.100),
        "%ragged-dot.5 = bf16[65536,768] ragged-dot(%a, %b, %g)": (90, 0.050),
        "%sort.2 = (s32[65536], s32[65536]) sort(%k, %i)": (10, 0.004),
    }
    trace = {"steps": 2, "step_device_s": 0.5, "window_s": 1.2, "busy_s": 1.0,
             "by_name": by_name}
    counters = {"chips": 1, "moe_pairs_held_per_step": 19000.0, "scopes": {
        "%fusion.11": "attn.mla_proj", "%fusion.12": "attn.mla_proj",
        "%fusion.13": "mtp.attn.mla_proj", "%fusion.14": "mtp.block",
        "%fusion.15": "loss.mtp", "%fusion.16": "mtp.moe.shared",
        "%fusion.17": "loss.head", "%fusion.18": "mlp.swiglu",
        "%ragged-dot.5": "moe.experts", "%sort.2": "moe.dispatch",
        "%checkpoint_flash_wide_fwd_.1": "attn.mla"}}
    cell = {"name": CELL, "config": config_file(),
            "shape": dict(SHAPE, rows=1, head_dim=192, dtype_bytes=2),
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    return trace, counters, cell


def test_the_readers_on_a_synthetic_trace(capfd):
    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    trace, counters, cell = synthetic()
    read = lambda name: man.reader(name)(trace, {}, counters, cell)
    # the trunk's projections and the module's; not the module's joining
    # product, not the dense SwiGLU
    assert read("attn.mla_proj_ms") == pytest.approx(
        1e3 * (0.080 + 0.040 + 0.014) / 2)
    # everything behind "mtp." and loss.mtp; not loss.head, and not the
    # routed experts, which keep moe.* inside the module
    assert read("mtp.block_ms") == pytest.approx(
        1e3 * (0.014 + 0.010 + 0.030 + 0.006) / 2)
    # the four readers that take the cell through its shape
    assert read("attn.mla_kernel_ms") == pytest.approx(1e3 * 0.360 / 2)
    assert read("model.mla_moe_mfu_pct") == pytest.approx(
        100 * 27_839_340_478_464 / 197e12 / 0.5)
    least = 2 * 1_073_872_896 * 1152 * 6 / 197e12       # compute-bound
    assert read("attn.mla_roofline_pct") == pytest.approx(100 * least / 0.180)
    # 9 products a layer, FIVE layers (the module's among them): each reads
    # or writes its 4,096 expected rows on both sides and the 16 held
    # matrices once; at 256 rows an expert the matrices' bytes bound it
    gmm = 9 * (4096 * (2048 + 768) + 16 * 2048 * 768) * 2 * 5 / 819e9
    assert gmm > 9 * 2 * 4096 * 2048 * 768 * 5 / 197e12
    assert read("moe.lm_gmm_roofline_pct") == pytest.approx(100 * gmm / 0.025)
    assert man.reader("moe.experts_ms")(trace, {}, counters, cell) == \
        pytest.approx(25.0)
    assert man.reader("moe.dispatch_ms")(trace, {}, counters, cell) == \
        pytest.approx(2.0)
    rows = [json.loads(l) for l in capfd.readouterr().out.splitlines()]
    assert {r["check"]: r["bound"] for r in rows} == {
        "attn.mla_roofline_pct": "compute",
        "moe.lm_gmm_roofline_pct": "memory"}
    for name in SHAPE_READERS:          # a share stays a share
        assert 0 < read(name) <= 100 or name.endswith("_ms")
    # the hyper-connections' readers find nothing in a cell without streams
    assert man.reader("hc.mix_ms")(trace, {}, counters, cell) is None


@pytest.mark.parametrize("metric", sorted(NEW_READERS))
def test_a_new_reader_reads_nothing_where_nothing_is(metric):
    """On a program that lacks what this PR adds (no scope table, no such
    scope, another family's cell) a reader returns None and does not
    raise."""
    read = Manifest(os.path.join(ROOT, "BENCHMARK.json")).reader(metric)
    cell = {"name": "no-such-run", "shape": {"seq": 8}, "peaks": None}
    assert read(None, {}, {}, cell) is None
    summary = {"steps": 3, "window_s": 1.0, "busy_s": 0.5}
    assert read(summary, {}, {}, cell) is None
    full = dict(summary, step_device_s=0.5, by_name={
        "%fusion.1 = f32[8] fusion(%x), kind=kLoop": (3, 0.3)})
    assert read(full, {}, {"chips": 1}, cell) is None
    # a scope table with none of this reader's scopes (another family's)
    assert read(full, {}, {"chips": 1, "scopes": {
        "%fusion.1": "conv.proj"}}, cell) is None
    trace, counters, mine = synthetic()
    assert read(trace, {}, dict(counters, scopes={}), mine) is None
    assert read(trace, {}, {"chips": 1}, mine) is None


def test_rehearsal_of_the_tiny_joyai_cell(tmp_path, capfd):
    """``jobs/sync_train_streamed.py`` end to end on a tiny ``joyai``
    configuration with the module HELD: the family, the reference's
    ``terms`` (both losses) and ``router_loads`` in the streamed
    comparison, the frozen bias through the reference's Adam, the
    ``model.plan`` row, and the counters a CPU run may report."""
    from pytorch_ps_mpi_tpu import telemetry

    manifest, doc = rehearsal_manifest(
        str(tmp_path),
        extra_cells={"tiny-joyai.lm": ("tiny-joyai", "tiny-lm-streamed", 1)})
    line, earlier = run_cell(capfd, manifest, "tiny-joyai.lm", trace=1)
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    ref = {r["check"]: r for r in earlier if "check" in r}["reference"]
    assert ref["ok"] and ref["loss_rel"] < 1e-5
    # no hyper-connection weight, so no gradient that is zero by
    # construction: the signs agree where tiny-xing's 0.95 is wide
    assert ref["update_sign_share"] > 0.995 and ref["update_rel_l2"] < 1e-2
    assert ref["worst_expert_sign_share"] > 0.99
    # what the limits are set against: bf16 parameters lose the update
    assert ref["if_bf16_params"]["update_rel_l2"] > 0.3
    # two expert layers and the module's: the program's router is the
    # reference's, and no pair of the worst case is dropped
    assert ref["router_tie_share"] == 0.0
    assert ref["router_loads_step1"] == ref["reference_router_loads_step1"]
    assert len(ref["router_loads_step1"]) == 3
    assert all(len(layer) == 2 for layer in ref["router_loads_step1"])
    m = line["metrics"]
    assert m["step.compiles_in_window"]["value"] == 0
    assert m["moe.load_max_over_mean"]["value"] >= 1.0
    counts = {x["name"] for x in doc["per_layer"]
              if x["source"] == "program_counter"}
    assert set(m) <= counts
    plans = [r["attrs"] for r in telemetry.setup_rows()
             if r["name"] == "model.plan"]
    assert plans and all(
        (p["residual"], p["streams"], p["prediction_modules"],
         p["experts_held"], p["experts"], p["vocab_rows"],
         p["vocab_published"]) == ("plain", 0, 1, 2, 16, 96, 768)
        for p in plans)
    assert not [r for r in telemetry.setup_rows() if r["name"] == "hc.plan"]
    shutil.rmtree(os.path.join(ROOT, ".chipbench_run", "tiny-joyai.lm"),
                  ignore_errors=True)


def test_the_module_left_out_of_the_program_fails_the_loss(tmp_path, capfd,
                                                           monkeypatch):
    """The same rehearsal with ``mtp_loss_weight`` forced to 0 in the
    PROGRAM only: the reference still adds 0.3 x the module's loss, and
    ``loss_rel`` reads the module's share of the loss, a fifth or more,
    far over its limit: the comparison sees the module."""
    import dataclasses

    from pytorch_ps_mpi_tpu.models import xing

    whole = xing.causal_lm_loss
    monkeypatch.setattr(xing, "causal_lm_loss", lambda p, b, cfg: whole(
        p, b, dataclasses.replace(cfg, mtp_loss_weight=0.0)))
    manifest, _ = rehearsal_manifest(
        str(tmp_path),
        extra_cells={"tiny-joyai.lm": ("tiny-joyai", "tiny-lm-streamed", 1)})
    line, earlier = run_cell(capfd, manifest, "tiny-joyai.lm", trace=0)
    ref = {r["check"]: r for r in earlier if "check" in r}["reference"]
    assert line["correct"] is False and not ref["ok"]
    assert 0.18 < ref["loss_rel"] < 0.26
    shutil.rmtree(os.path.join(ROOT, ".chipbench_run", "tiny-joyai.lm"),
                  ignore_errors=True)
