"""The order of a synchronous run: the comparison with the reference
comes after the window, on a device the system has left; and the
streamed comparison on such a device: one leaf group or many give the
same readings, and the host copies of the parameters it takes from the
caller are dropped as they are read."""

import contextlib
import io
import json
import os
import shutil
import weakref

import jax
import numpy as np
import pytest
from test_chipbench_rehearsal import ROOT, rehearsal_manifest, run_cell

from chipbench.jobs import sync_train, sync_train_streamed

SDAR = {"tiny-sdar.bd": ("tiny-sdar", "tiny-bd", 1)}


def test_the_comparison_starts_after_the_window_with_the_system_gone(
        tmp_path, capfd, monkeypatch):
    rows, seen = [], {}
    say = sync_train.say

    def said(**row):
        rows.append(row.get("check"))
        say(**row)

    def stub(fam, config, lr, devices, kept, first, losses, budget=None):
        seen["rows"] = list(rows)
        seen["new_arrays"] = [a for a in jax.live_arrays()
                              if id(a) not in before]
        seen["kept"] = sorted(kept)
        seen["budget"] = budget
        return {"ok": True}

    monkeypatch.setattr(sync_train, "say", said)
    monkeypatch.setattr(sync_train, "against_reference", stub)
    manifest, _ = rehearsal_manifest(str(tmp_path))
    alive = jax.live_arrays()  # held, so that no id is used again
    before = {id(a) for a in alive}
    line, earlier = run_cell(capfd, manifest, "tiny-bert.mlm", trace=0)
    assert line["correct"] is True
    # the window's row and the memory's are out before the comparison
    assert seen["rows"][-2:] == ["memory", "window"]
    assert "reference" not in seen["rows"]
    # nothing the run put on the device is left: not the optimizer's
    # state, not a batch of the pipeline
    assert seen["new_arrays"] == []
    assert seen["kept"] == ["p0", "p1"] and seen["budget"] > 0
    (ref,) = [r for r in earlier if r.get("check") == "reference"]
    # what is left is named (a test process may hold other tests' arrays)
    assert set(ref["system_left_on_device"]) == {"arrays", "count", "bytes"}
    assert [r["check"] for r in earlier if "check" in r][-2:] == [
        "window", "reference"]


def reference_row(directory, monkeypatch, setting):
    """The streamed comparison's row on the tiny ``sdar_moe`` cell, and
    the group limits it planned with: ``one`` group with no budget (the
    CPU keeps no count of its bytes), ``roomy`` under a budget that holds
    one group, or ``squeezed`` under a budget of one byte, so that every
    branch for a chip too small for one group runs: several groups and
    their gradients on the host. The latter two on a device that says it
    holds nothing."""
    from chipbench.run import main

    limits, pack = [], sync_train_streamed.leaf_groups
    monkeypatch.setattr(sync_train_streamed, "leaf_groups",
                        lambda leaves, limit: limits.append(limit)
                        or pack(leaves, limit))
    if setting != "one":
        compare = sync_train_streamed.against_reference
        monkeypatch.setattr(sync_train_streamed, "bytes_in_use",
                            lambda device: 0)
        monkeypatch.setattr(
            sync_train_streamed, "against_reference",
            lambda *args: compare(
                *args[:7], budget=1 if setting == "squeezed" else 1e15))
    manifest, _ = rehearsal_manifest(directory, extra_cells=SDAR)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert main(["--workload", "tiny-sdar.bd", "--seed", "7",
                     "--seconds", "1", "--trace", "0"],
                    manifest=manifest) == 0
    shutil.rmtree(os.path.join(ROOT, ".chipbench_run", "tiny-sdar.bd"),
                  ignore_errors=True)
    rows = [json.loads(l) for l in out.getvalue().splitlines()
            if l.startswith("{")]
    return next(r for r in rows if r.get("check") == "reference"), limits


READINGS = ("loss_rel", "update_sign_share", "update_rel_l2",
            "worst_expert_sign_share", "worst_expert_rel_l2",
            "router_tie_share")


@pytest.fixture(scope="module")
def one_group(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        return reference_row(str(tmp_path_factory.mktemp("one")), mp, "one")


@pytest.mark.parametrize("setting", ["one", "roomy", "squeezed"])
def test_one_group_or_many_read_alike(setting, one_group, tmp_path,
                                      monkeypatch):
    ref, limits = reference_row(str(tmp_path), monkeypatch, setting)
    one, _ = one_group
    assert ref["ok"] is one["ok"] is True
    assert one["leaf_groups"] == 1 and one["budget_bytes"] is None
    # one plan: the loss's own temporaries told beforehand whether one
    # group fits, so no group is compiled and thrown away
    assert len(limits) == 1
    if setting != "squeezed":  # the same programs: the same numbers
        assert ref["leaf_groups"] == 1
        assert {k: ref[k] for k in READINGS} == {k: one[k] for k in READINGS}
    else:
        # other programs take each group's gradient: the CPU's sums come
        # out in another order, so the last digits move. About eight
        # groups at most
        assert 1 < ref["leaf_groups"] <= 12 and ref["budget_bytes"] == 1
        for k in READINGS:
            assert ref[k] == pytest.approx(one[k], rel=1e-3, abs=1e-9)
    assert ref["losses"] == one["losses"]


def test_the_host_copies_are_dropped_as_they_are_read(tmp_path, capfd,
                                                      monkeypatch):
    """Weak references to every host leaf of ``p0`` and ``p1``, counted
    at each point where the comparison reads its memory: none is left
    once step 1's statistics are read, long before the comparison ends,
    and the caller keeps neither. (On the CPU a device array may hold the
    host leaf it was put from: ``p0`` lives on as the reference's
    parameters until step 1's Adam rewrites them.)"""
    compare, refs, counts = sync_train_streamed.against_reference, {}, []
    rss = sync_train_streamed.host_rss

    def spied(fam, config, lr, devices, kept, *args):
        for k in ("p0", "p1"):
            # leaves that own their memory: on the CPU a fetched array
            # is a view that the backend's own buffer keeps alive
            kept[k] = jax.tree.map(np.array, kept[k])
            refs[k] = [weakref.ref(a) for a in jax.tree.leaves(kept[k])]
        return compare(fam, config, lr, devices, kept, *args)

    def counted():
        counts.append({k: sum(r() is not None for r in v)
                       for k, v in refs.items()})
        return rss()

    monkeypatch.setattr(sync_train_streamed, "against_reference", spied)
    monkeypatch.setattr(sync_train_streamed, "host_rss", counted)
    manifest, _ = rehearsal_manifest(str(tmp_path), extra_cells=SDAR)
    line, earlier = run_cell(capfd, manifest, "tiny-sdar.bd", trace=0)
    assert line["correct"] is True
    (ref,) = [r for r in earlier if r.get("check") == "reference"]
    leaves = len(refs["p1"])
    assert counts[0] == {"p0": leaves, "p1": leaves}  # on entry
    for k in ("p0", "p1"):
        alive = [c[k] for c in counts]
        assert alive == sorted(alive, reverse=True), k
    # step 1's points: the reference's parameters, two routers, a
    # group's gradient for each of the batch's two rows, and after Adam
    gone = next(i for i, c in enumerate(counts) if c == {"p0": 0, "p1": 0})
    assert gone == 7 and len(counts) > gone + 1
    assert len(ref["device_bytes_in_use"]) == 6
    shutil.rmtree(os.path.join(ROOT, ".chipbench_run", "tiny-sdar.bd"),
                  ignore_errors=True)
