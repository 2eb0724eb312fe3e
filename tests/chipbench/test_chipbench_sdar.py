"""``flops_sdar.py`` against numbers worked by hand, the block-diffusion
generator, the streamed comparison's leaf groups and scope table, the new
readers where there is nothing to read, and a rehearsal of the streamed
job on a tiny ``sdar_moe`` cell (with the rehearsal file's helpers)."""

import json
import os
import shutil

import numpy as np
import pytest
from test_chipbench_rehearsal import (LINE_KEYS, ROOT, rehearsal_manifest,
                                      run_cell)

from chipbench.run import Manifest

from chipbench import flops_sdar
from chipbench.gen.bd_zipf import batches
from chipbench.jobs.sync_train_streamed import leaf_groups

# sdar-30b-a3b.bd4k: 2 rows of 4,096 data tokens = 16,384 positions, 6 layers,
# hidden 2048, 32 query heads over 4 key-value heads of 128, 16 of 128 experts
# of width 768 at 8 a token, vocabulary 18,992. Forward, 2 operations a
# multiply-add:
#   qkv     2 * 16384 * 2048 * (32 + 2*4) * 128 * 6     = 2,061,584,302,080
#   out     2 * 16384 * 4096 * 2048 * 6                 = 1,649,267,441,664
#   router  2 * 16384 * 2048 * 128 * 6                  =    51,539,607,552
#   allowed pairs 2 * 32 * (4096^2 + 4096*4)            =     1,074,790,400
#   scores  2 * 1,074,790,400 * 128 * 6                 = 1,650,878,054,400 (= values)
#   pairs held 16384 * 8 * 16 / 128                     =            16,384
#   experts 2 * 16384 * 3 * 2048 * 768 * 6              =   927,712,935,936
#   head    2 * 2 * 4096 * 2048 * 18992                 =   637,265,772,544
#   sum 8,629,126,168,576; a training step is 3x       = 25,887,378,505,728
CELL = dict(rows=2, seq=4096, block=4, hidden=2048, heads=32, kv_heads=4,
            head_dim=128, expert_width=768, experts=128, experts_held=16,
            top_k=8, vocab=18992, layers=6)
# tiny, by hand: 1 row, seq 8, block 4, hidden 4, 2 heads over 1 of dim 2,
# 2 of 4 experts of width 3 at 2 a token, vocabulary 5, 1 layer
#   positions 16; allowed pairs 2 * (64 + 32) = 192; pairs held 16*2*2/4 = 16
TINY = dict(rows=1, seq=8, block=4, hidden=4, heads=2, kv_heads=1,
            head_dim=2, expert_width=3, experts=4, experts_held=2, top_k=2,
            vocab=5, layers=1)


@pytest.mark.parametrize("shape, klass, want", [
    (CELL, "qkv_proj", 2_061_584_302_080),
    (CELL, "out_proj", 1_649_267_441_664),
    (CELL, "router", 51_539_607_552),
    (CELL, "attn_scores", 1_650_878_054_400),
    (CELL, "attn_values", 1_650_878_054_400),
    (CELL, "experts", 927_712_935_936),
    (CELL, "vocab_proj", 637_265_772_544),
    (TINY, "qkv_proj", 2 * 16 * 4 * 4 * 2),
    (TINY, "out_proj", 2 * 16 * 4 * 4),
    (TINY, "router", 2 * 16 * 4 * 4),
    (TINY, "attn_scores", 2 * 192 * 2),
    (TINY, "experts", 2 * 16 * 3 * 4 * 3),
    (TINY, "vocab_proj", 2 * 8 * 4 * 5),
])
def test_forward_classes(shape, klass, want):
    assert flops_sdar.forward_flops(**shape)[klass] == want


def test_train_step_and_counts():
    assert flops_sdar.train_flops(**CELL) == 25_887_378_505_728
    assert flops_sdar.allowed_pairs(**CELL) == 1_074_790_400
    assert flops_sdar.pairs_held(**CELL) == 16_384
    # a quarter of the doubled score matrix, plus the diagonal blocks
    assert flops_sdar.allowed_pairs(**CELL) / (2 * 32 * 8192 ** 2) == \
        pytest.approx(0.25, rel=1e-3)


def test_kernel_costs():
    # 7 products over the allowed pairs; q, o, do, dq + k, v, dk, dv once
    # each way: 6 tensors of 32 heads and 6 of 4, 8,192 positions, bf16
    cost = flops_sdar.bd_attention_kernel_cost(**CELL, dtype_bytes=2)
    assert cost["flops"] == 7 * 1_650_878_054_400
    assert cost["bytes"] == 6 * (32 + 4) * (2 * 8192 * 128 * 2 * 6)
    # 9 products of 2 * 2048 * 768 a pair; each moves its rows on both
    # sides and the 16 matrices once
    gmm = flops_sdar.grouped_matmul_cost(**CELL, dtype_bytes=2)
    assert gmm["flops"] == 9 * 2 * 16384 * 2048 * 768 * 6 == 3 * 927_712_935_936
    assert gmm["bytes"] == 9 * (16384 * (2048 + 768) + 16 * 2048 * 768) * 2 * 6
    twice = flops_sdar.grouped_matmul_cost(**CELL, dtype_bytes=2, pairs=32768)
    assert twice["flops"] == 2 * gmm["flops"]


def test_bd_zipf_is_seeded_and_well_formed():
    a, b, other = (next(batches(s, 3, 32, 96)) for s in (5, 5, 6))
    for key in ("tokens", "noised", "replaced", "t"):
        assert np.array_equal(a[key], b[key])
    assert not np.array_equal(a["tokens"], other["tokens"])
    assert a["tokens"].shape == a["noised"].shape == a["replaced"].shape == (3, 32)
    assert a["t"].shape == (3, 8) and a["t"].dtype == np.float32
    assert a["tokens"].max() <= 94 and a["tokens"].min() >= 0   # data ids
    assert np.all(a["noised"][a["replaced"]] == 95)             # the mask id
    assert np.array_equal(a["noised"][~a["replaced"]],
                          a["tokens"][~a["replaced"]])
    assert np.all((a["t"] > 0) & (a["t"] <= 1))
    stream = batches(5, 3, 32, 96)
    first, second = next(stream), next(stream)
    assert not np.array_equal(first["t"], second["t"])
    # a large seed, as the driver's are
    assert next(batches(2 ** 31 + 12345, 1, 8, 16))["tokens"].shape == (1, 8)
    with pytest.raises(ValueError):
        next(batches(1, 1, 30, 96))


def test_masking_rate_follows_t():
    batch = next(batches(11, 64, 4096, 18992))
    rate = batch["replaced"].reshape(64, -1, 4).mean(-1)
    # over 65,536 blocks the replaced share tracks t: E[replaced | t] = t
    assert abs(float(rate.mean()) - float(batch["t"].mean())) < 5e-3
    assert abs(float(batch["t"].mean()) - 0.5005) < 5e-3
    hi = batch["t"] > 0.9
    assert float(rate[hi].mean()) > 0.9


def test_leaf_groups_pack_in_order():
    leaves = [np.zeros(n, np.float32) for n in (10, 10, 30, 5, 5, 50, 1)]
    # bytes: 40, 40, 120, 20, 20, 200, 4
    assert leaf_groups(leaves, limit=200) == [[0, 1, 2], [3, 4], [5], [6]]
    assert leaf_groups(leaves, limit=1e9) == [list(range(7))]


def test_rehearsal_of_the_streamed_job(tmp_path, capfd):
    """``jobs/sync_train_streamed.py`` end to end on a tiny ``sdar_moe``
    configuration: ``sync_train``'s window with the streamed comparison
    (leaf groups, host-parked Adam, router loads) and the counters it
    adds."""
    manifest, doc = rehearsal_manifest(
        str(tmp_path), extra_cells={"tiny-sdar.bd": ("tiny-sdar", "tiny-bd", 1)})
    line, earlier = run_cell(capfd, manifest, "tiny-sdar.bd", trace=1)
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    checks = {r["check"]: r for r in earlier if "check" in r}
    ref = checks["reference"]
    assert ref["ok"] and ref["loss_rel"] < 1e-5
    assert ref["update_sign_share"] > 0.999 and ref["update_rel_l2"] < 1e-2
    assert ref["worst_expert_sign_share"] > 0.99
    # float32 on both sides: the program's router and the reference's agree
    assert ref["router_tie_share"] == 0.0
    assert ref["router_loads_step1"] == ref["reference_router_loads_step1"]
    # what the limits are set against: bf16 parameters lose the update
    assert ref["if_bf16_params"]["update_rel_l2"] > 0.3
    m = line["metrics"]
    assert m["moe.load_max_over_mean"]["value"] >= 1.0
    assert m["step.compiles_in_window"]["value"] == 0
    counts = {x["name"] for x in doc["per_layer"]
              if x["source"] == "program_counter"}
    assert set(m) <= counts
    shutil.rmtree(os.path.join(ROOT, ".chipbench_run", "tiny-sdar.bd"),
                  ignore_errors=True)


def test_instruction_scopes():
    from chipbench.jobs.sync_train_streamed import instruction_scopes

    text = "\n".join([
        '  %fusion.7 = bf16[8,4]{1,0} fusion(%a), kind=kCustom, calls=%f, '
        'metadata={op_name="jit(step)/transpose(jvp(moe.combine))/jit(_take)/gather"}',
        '  %flash_bd_fwd.4 = (bf16[8]) custom-call(%q), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(step)/jvp(attn.bd)/flash_bd_fwd/pallas_call"}',
        '  %ragged-dot-none.3 = bf16[8,4] custom-call(%x), metadata={op_name="ragged-dot-none"}',
        '  ROOT %add.1 = f32[] add(%x, %y), metadata={op_name="jit(step)/jvp()/add"}',
        '  %copy.2 = f32[4] copy(%x)'])
    assert instruction_scopes(text, {"ragged-dot-none": "moe.experts"}) == {
        "%fusion.7": "moe.combine", "%flash_bd_fwd.4": "attn.bd",
        "%ragged-dot-none.3": "moe.experts"}
    assert instruction_scopes(None, {}) == {}


NEW_READERS = ["model.moe_mfu_pct", "attn.bd_kernel_ms", "attn.bd_roofline_pct",
               "moe.experts_ms", "moe.gmm_roofline_pct", "moe.dispatch_ms",
               "moe.load_max_over_mean"]


def test_the_cell_and_its_metrics_are_appended():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cell = next(c for c in doc["workloads"]
                if c["name"] == "sdar-30b-a3b.bd4k")
    assert cell == dict(cell, config="sdar-30b-a3b", traffic="bd4k", chips=1)
    config = next(c for c in doc["configs"] if c["name"] == "sdar-30b-a3b")
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config["file"] == "chipbench/configs/sdar-30b-a3b.json"
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name in NEW_READERS:
        m = by_name[name]
        assert "sdar-30b-a3b.bd4k" in m["workloads"]
        assert m["moves"] == "tokens_per_s"
        assert callable(Manifest(os.path.join(ROOT, "BENCHMARK.json"))
                        .reader(name))
    for name in ("model.mfu_pct", "attn.kernel_ms", "flash_attention_roofline",
                 "attn.roofline_pct"):
        m = next(m for m in doc["per_layer"] if m["name"] == name)
        assert "sdar-30b-a3b.bd4k" not in m["workloads"]


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_reader_reads_nothing_where_nothing_is(metric):
    """On a program that lacks what PR 27 adds (no scope table, no such
    kernel, no such counter) a reader returns None and does not raise."""
    read = Manifest(os.path.join(ROOT, "BENCHMARK.json")).reader(metric)
    cell = {"name": "no-such-run", "shape": {"seq": 8}, "peaks": None}
    assert read(None, {}, {}, cell) is None
    summary = {"steps": 3, "window_s": 1.0, "busy_s": 0.5}
    assert read(summary, {}, {}, cell) is None
    full = dict(summary, step_device_s=0.5, by_name={
        "%fusion.1 = f32[8] fusion(%x), kind=kLoop": (3, 0.3)})
    assert read(full, {}, {"chips": 1}, dict(cell, peaks={
        "flops_bf16": 1e12, "hbm_bytes_per_s": 1e11})) is None
