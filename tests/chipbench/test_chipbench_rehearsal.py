"""Every kind of cell end to end on the CPU from the tiny configurations
in ``data/``, through ``chipbench.run.main(argv, manifest=...)``; and a
fifth cell and a new per-layer metric added as files only."""

import json
import os
import shutil

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
CELLS = {"tiny-bert.mlm": ("tiny-bert", "tiny-mlm", 1),
         "tiny-bert.mlm.dp4": ("tiny-bert", "tiny-mlm", 4),
         "tiny-gpt.lm": ("tiny-gpt", "tiny-lm", 1),
         "tiny-resnet.async": ("tiny-resnet", "tiny-async", 1)}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}


def rehearsal_manifest(directory, extra_paths=(), extra_cells=None,
                       extra_layer_metrics=()):
    """BENCHMARK.json's metrics over the tiny cells, as a manifest in
    ``directory`` (never a cell of BENCHMARK.json itself)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cells = dict(CELLS, **(extra_cells or {}))
    doc["paths"] = list(extra_paths) + [DATA]
    doc["configs"] = [
        {"name": n, "source": "rehearsal", "reduced": [], "why": "rehearsal",
         "file": os.path.join(DATA, "configs", n + ".json")}
        for n in sorted({c for c, _, _ in cells.values()})]
    doc["workloads"] = [
        {"name": k, "config": c, "traffic": t, "chips": chips,
         "why": "rehearsal"} for k, (c, t, chips) in cells.items()]
    for group in ("end_to_end", "per_layer"):
        for m in doc[group]:
            m.pop("workloads", None)
    doc["per_layer"] += list(extra_layer_metrics)
    path = os.path.join(directory, "manifest.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    return path, doc


def run_cell(capfd, manifest, cell, trace):
    from chipbench.run import main

    rc = main(["--workload", cell, "--seed", "7", "--seconds", "1",
               "--trace", str(trace)], manifest=manifest)
    out = capfd.readouterr().out
    assert rc == 0
    rows = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    return rows[-1], rows[:-1]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rehearsal(cell, tmp_path, capfd):
    manifest, doc = rehearsal_manifest(str(tmp_path))
    line, earlier = run_cell(capfd, manifest, cell, trace=1)
    assert set(line) == LINE_KEYS  # no device trace off the chip: no breakdown
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # off a TPU: counts, and never a time, a rate or a share
    counts = {m["name"] for m in doc["per_layer"]
              if m["source"] == "program_counter"}
    assert set(line["metrics"]) <= counts
    assert line["metrics"]["step.compiles_in_window"]["value"] == 0
    assert {"value", "unit"} == set(line["metrics"]["cache.misses"])
    checks = {r["check"]: r for r in earlier if "check" in r}
    if cell.endswith("async"):
        g = checks["guarantees"]
        assert g["first_update"] and g["acknowledged_accounted"]
        assert g["received"] == g["applied"] + g["stale_drops"]
        assert line["metrics"]["wire.mb_per_update"]["value"] == pytest.approx(
            11.17421, rel=1e-3)  # 11,173,962 int8 codes + frame headers
    else:
        assert checks["reference"]["ok"] and checks["window"]["copies_equal"]
        assert checks["reference"]["loss_rel"] < 1e-5
        # every fit call of the window is timed: the rate is taken over
        # their median
        w = checks["window"]
        assert line["attempted"] % len(w["calls_ms"]) == 0
        assert (w["call_s"]["p0"] <= w["call_s"]["p50"] <= w["call_s"]["p100"]
                <= w["elapsed_s"])
    if cell.endswith("dp4"):
        assert line["metrics"]["wire.mb_per_update"]["value"] > 0


@pytest.mark.parametrize("cell, names", [
    ("tiny-gpt.lm", {"loss_rel", "update_sign_share", "update_rel_l2",
                     "compiles_in_window", "failed"}),
    ("tiny-resnet.async", {"first_update_rel_l2",
                           "first_update_worst_leaf_rel_l2",
                           "compiles_in_window", "failed"})])
def test_the_numbers_compared_close_both_streams(cell, names, tmp_path,
                                                 capfd):
    """Each number a run compared, with its limit: the result's last key,
    and the last lines of standard error."""
    from chipbench.run import main

    manifest, _ = rehearsal_manifest(str(tmp_path))
    assert main(["--workload", cell, "--seed", "7", "--seconds", "1",
                 "--trace", "0"], manifest=manifest) == 0
    out, err = capfd.readouterr()
    line = json.loads(out.splitlines()[-1])
    assert list(line)[-1] == "compared" and set(line["compared"]) == names
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}
    tail = err.splitlines()[-len(names):]
    assert [l.split()[1] for l in tail] == list(line["compared"])
    assert all(l.startswith("compared ") and " limit " in l for l in tail)


def test_untraced_line_off_the_chip_has_no_rate(tmp_path, capfd):
    manifest, _ = rehearsal_manifest(str(tmp_path))
    line, _ = run_cell(capfd, manifest, "tiny-gpt.lm", trace=0)
    assert set(line) == LINE_KEYS and line["metrics"] == {}


def test_a_cell_of_the_benchmark_needs_a_tpu():
    from chipbench.run import main

    with pytest.raises(SystemExit) as e:
        main(["--workload", "bert-base.mlm128", "--seed", "1",
              "--seconds", "1", "--trace", "0"])
    assert "needs 'tpu'" in str(e.value)


def test_a_new_cell_and_metric_are_files_only(tmp_path, capfd):
    """A fifth cell from an existing configuration and a new traffic
    file, and a new per-layer metric from a new reader file: nothing
    under chipbench/ is edited."""
    before = {p: os.path.getmtime(os.path.join(d, p))
              for d, _, files in os.walk(os.path.join(ROOT, "chipbench"))
              if "__pycache__" not in d and ".chipbench" not in d
              for p in files}
    os.makedirs(tmp_path / "traffic")
    os.makedirs(tmp_path / "layer_metrics")
    with open(os.path.join(DATA, "traffic", "tiny-mlm.json")) as f:
        mix = dict(json.load(f), seq=16, rows_per_chip=2)
    with open(tmp_path / "traffic" / "tiny-mlm16.json", "w") as f:
        json.dump(mix, f)
    with open(tmp_path / "layer_metrics" / "loop.steps.py", "w") as f:
        f.write("def read(trace, spans, counters, cell):\n"
                "    return counters['steps']\n")
    manifest, _ = rehearsal_manifest(
        str(tmp_path), extra_paths=[str(tmp_path)],
        extra_cells={"tiny-bert.mlm16": ("tiny-bert", "tiny-mlm16", 1)},
        extra_layer_metrics=[{"name": "loop.steps", "unit": "count",
                              "better": "higher", "source": "program_counter",
                              "layer": "CLI / loop", "moves": "tokens_per_s"}])
    line, _ = run_cell(capfd, manifest, "tiny-bert.mlm16", trace=1)
    assert line["correct"] is True
    assert line["metrics"]["loop.steps"]["value"] == line["attempted"]
    after = {p: os.path.getmtime(os.path.join(d, p))
             for d, _, files in os.walk(os.path.join(ROOT, "chipbench"))
             if "__pycache__" not in d and ".chipbench" not in d
             for p in files}
    assert after == before
    shutil.rmtree(os.path.join(ROOT, ".chipbench_run", "tiny-bert.mlm16"),
                  ignore_errors=True)
