"""libtpu's asynchronous all-reduce in a trace (PR 28): on hand-made
events whose answers are known, on two runs of the four-chip step program
recorded on the chip (``data/bert-base.mlm128.dp4.async.trace.json.gz``:
its start / done fusions, its one synchronous all-reduce and its module
events, nothing else), and on the trace of the program before PR 28."""

import json
import os

import pytest

from chipbench import async_collectives as ac
from chipbench import trace_reduce as tr
from chipbench.run import ROOT, Manifest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1_000  # nanoseconds
NEW = ("coll.async_time_ms", "coll.async_wait_ms")


def start(n, at, dur=3 * US):
    dot = f".{n}" if n else ""
    return (f"%async-collective-start{dot} = (f32[8], f32[8], s32[2]) "
            f"fusion(f32[8] %g.{n})", at, dur)


def done(n, at, dur):
    dot = f".{n}" if n else ""
    return (f"%async-collective-done{dot} = f32[8] fusion(f32[8] %gte.{n})",
            at, dur)


def test_an_exchange_is_paired_by_its_number_and_waits_in_its_own_events():
    events = [
        start(0, 100 * US), ("%fusion.7 = f32[8] fusion(%a)", 103 * US, 90 * US),
        start(1, 193 * US), ("%fusion.8 = f32[8] fusion(%b)", 196 * US, 50 * US),
        done(0, 246 * US, 10 * US),     # 0: 100-256; 1 overlaps it
        done(1, 256 * US, 44 * US),     # 1: 193-300
        start(2, 400 * US), done(2, 403 * US, 97 * US),  # 2: 400-500, alone
        done(3, 600 * US, 5 * US),      # its start lies before the trace
        start(0, 1000 * US),            # the next run of the program
    ]
    intervals, wait = ac.pairs(events, 0, 1000 * US)
    assert intervals == [(100 * US, 256 * US), (193 * US, 300 * US),
                         (400 * US, 500 * US), (600 * US, 605 * US)]
    assert tr.length(tr.union(intervals)) == (200 + 100 + 5) * US
    assert wait == (3 * 3 + 10 + 44 + 97 + 5) * US


def test_the_accepted_readers_do_not_see_these_events():
    for event in (start(4, 0), done(4, 10, 5)):
        name = tr.short(event[0])
        assert ac.ASYNC_EVENT.match(name) and not tr.is_collective(name)


def test_a_step_is_a_whole_run_of_the_program_on_every_device():
    """Two devices, two runs of 1 ms inside the window and one that the
    window cuts: the mean is over the whole runs, then the devices."""
    def run(t, wait):
        return [start(0, t + 100 * US), done(0, t + 500 * US, wait)]
    ms = 1000 * US
    ops = {0: run(0, 100 * US) + run(ms, 200 * US) + run(2 * ms, 400 * US),
           1: run(0, 200 * US) + run(ms, 300 * US) + run(2 * ms, 400 * US)}
    modules = {d: [("jit_spmd", t, ms - 1) for t in (0, ms, 2 * ms)]
               for d in ops}
    trace = tr.Trace(ops, modules, [("fit.call", 0, 2 * ms + 500 * US)])
    found = ac.per_step(trace)
    assert found["pairs"] == 1
    assert found["wait_ms"] == pytest.approx((0.153 + 0.253) / 2)
    assert found["under_way_ms"] == pytest.approx((0.55 + 0.65) / 2)


def test_the_recorded_four_chip_step_holds_51_exchanges():
    trace = tr.load(os.path.join(
        DATA, "bert-base.mlm128.dp4.async.trace.json.gz"))
    found = ac.per_step(trace)
    assert found["pairs"] == 51
    assert 3.9 < found["wait_ms"] < 4.0        # the done events wait 3.85
    assert 17.0 < found["under_way_ms"] < 17.6
    summary = tr.summarize(trace)
    assert summary["steps"] == 2
    # what coll.time_ms reads of the same runs: the one synchronous tuple
    assert 1e3 * summary["collective_s"] / summary["steps"] < 0.05


def test_a_program_without_them_gives_nothing():
    before = tr.load(os.path.join(DATA, "bert-base.mlm128.dp4.trace.json.gz"))
    assert ac.per_step(before) is None
    assert ac.per_step(tr.Trace({}, {}, [])) is None
    cell = {"name": "no-such-cell"}
    assert ac.read(None, cell, "wait_ms") is None
    assert ac.read({"steps": 2}, cell, "wait_ms") is None  # no trace file


@pytest.mark.parametrize("name", NEW)
def test_the_manifest_names_the_reader_for_the_four_chip_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    assert entry["workloads"] == ["bert-base.mlm128.dp4"]
    assert entry["layer"] == "collectives" and entry["moves"] == "tokens_per_s"
    read = Manifest(os.path.join(ROOT, "BENCHMARK.json")).reader(name)
    assert read(None, [], {"chips": 4}, {"name": "no-such-cell"}) is None
