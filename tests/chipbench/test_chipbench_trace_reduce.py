"""The reduction from a trace to numbers: on hand-made traces whose
answers are known, and on the small trace recorded on the chip that
``data/`` keeps."""

import os

import pytest

from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000  # nanoseconds


def test_interval_arithmetic():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert tr.length(u) == 6
    assert tr.intersection_length(u, [(2, 6), (7, 20)]) == 1 + 1 + 1
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


@pytest.mark.parametrize("text, tag", [
    ("%all-reduce.36 = (f32[768]{0}, f32[3072]{0}) all-reduce(f32[768]{0} %a, "
     "f32[3072]{0} %b), channel_id=2", " [all-reduce]"),
    ("%psum.1204 = f32[768,30522]{1,0:T(8,128)} all-reduce(f32[768,30522]{1,0} "
     "%bitcast_convert_fusion), channel_id=1", " [all-reduce]"),
    ("%ag = f32[4,8]{1,0} all-gather-start(f32[1,8]{1,0} %x)",
     " [all-gather-start]"),
    ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %all-reduce.36), kind=kLoop", ""),
    ("%SelfAttention_0.5 = bf16[96,1024,64]{2,1,0} custom-call(bf16[96,1024,64]"
     '{2,1,0} %q), custom_call_target="tpu_custom_call"', tr.PALLAS_TAG)])
def test_what_an_operation_is_comes_from_its_whole_text(text, tag):
    name = tr.short(text * 3)  # the cut must not lose the opcode
    assert "{" not in name and len(name) <= 120 + len(tag)
    assert name.endswith(tag) if tag else not name.endswith("]")
    assert tr.is_collective(name) is (tag.startswith(" [a"))


def hand_made():
    """Two steps of 10 ms on two devices. In each step: compute 0-4,
    an asynchronous all-reduce started at 4 (0.1 ms) and finished at
    7-7.5 with compute 4.1-6 under it, a synchronous all-gather 7.5-8,
    compute 8-9, idle 9-10. The host was in data.next over the idle
    millisecond of step 1 and in nothing else but fit.call."""
    def step(t):
        return [("fusion.1", t + 0, 4 * MS),
                ("%ar = f32[8] all-reduce-start(f32[8] %g) [all-reduce-start]",
                 t + 4 * MS, MS // 10),
                ("fusion.2", t + 4 * MS + MS // 10, 19 * MS // 10),
                ("%ard = f32[8] all-reduce-done(%ar) [all-reduce-done]",
                 t + 7 * MS, MS // 2),
                ("%psum.1 = f32[8] all-gather(f32[2] %p) [all-gather]",
                 t + 7 * MS + MS // 2, MS // 2),
                ("fusion.3", t + 8 * MS, MS)]
    ops = step(0) + step(10 * MS)
    modules = [("jit_step", 0, 9 * MS), ("jit_step", 10 * MS, 9 * MS),
               ("jit_small", 9 * MS + MS // 2, MS // 100)]
    host = [("fit.call", 0, 20 * MS), ("data.next", 9 * MS, MS)]
    return tr.Trace({0: ops, 1: list(ops)}, {0: modules, 1: list(modules)},
                    host)


def test_summary_of_a_hand_made_trace():
    s = tr.summarize(hand_made())
    assert s["devices"] == 2 and s["steps"] == 2
    assert s["window_s"] == pytest.approx(0.020)
    # busy per step: 0-6, 7-9 = 8 ms
    assert s["busy_s"] == pytest.approx(0.016)
    # collectives under way per step: 4-7.5 and 7.5-8 = 4 ms
    assert s["collective_s"] == pytest.approx(0.008)
    # of which other operations cover 4.1-6: exposed 4 - 1.9 = 2.1 ms
    assert s["collective_exposed_s"] == pytest.approx(0.0042)
    assert s["step_device_s"] == pytest.approx(0.009)
    assert s["by_name"]["fusion.1"] == (2, pytest.approx(0.008))
    assert tr.top_ops(s, keep=1) == [["fusion.1", pytest.approx(0.008)]]
    assert tr.seconds_matching(s, r" \[all-") == (6, pytest.approx(0.0022))
    # gaps: 6-7 and 9-10 in each step; the first 9-10 is under data.next
    assert sorted(s["gaps"]) == sorted(
        [["fit.call", pytest.approx(0.001)]] * 3
        + [["data.next", pytest.approx(0.001)]])


def test_no_device_operations_is_no_summary():
    assert tr.summarize(tr.Trace({}, {}, [("fit.call", 0, MS)])) is None


def test_dump_and_load_round_trip(tmp_path):
    path = str(tmp_path / "t.json.gz")
    tr.dump(hand_made(), path)
    assert tr.summarize(tr.load(path)) == tr.summarize(hand_made())
    assert tr.load(str(tmp_path)) is None  # a directory with no trace


# -- the recorded trace -------------------------------------------------------
# Three steps of bert-base.mlm128.dp4 on two of its four chips, cut from the
# trace of a run on the v5e host (PR 22) with every event as the profiler
# wrote it.
RECORDED = os.path.join(DATA, "bert-base.mlm128.dp4.trace.json.gz")


def sweep(events, lo, hi, keep):
    """Nanoseconds in [lo, hi) during which at least one event of
    ``keep`` runs and (second number) none of the others does — by
    counting at every boundary, independently of the interval code."""
    marks = []
    for name, start, dur in events:
        kind = 0 if keep(name) else 1
        marks += [(max(start, lo), 1, kind), (min(start + dur, hi), -1, kind)]
    marks.sort()
    depth, covered, alone, last = [0, 0], 0, 0, lo
    for t, step, kind in marks:
        if t > last:
            covered += (t - last) * (depth[0] > 0)
            alone += (t - last) * (depth[0] > 0 and depth[1] == 0)
            last = t
        depth[kind] += step
    return covered, alone


@pytest.fixture(scope="module")
def recorded():
    return tr.load(RECORDED)


def test_recorded_trace_busy_and_idle(recorded):
    s = tr.summarize(recorded)
    lo, hi = tr.window_of(recorded)
    assert s["devices"] == 2 and s["steps"] == 3
    busy = [sweep(recorded.ops[d], lo, hi, lambda n: True)[0]
            for d in sorted(recorded.ops)]
    assert s["busy_s"] == pytest.approx(sum(busy) / 2 / 1e9, rel=1e-9)
    assert 0.5 < s["busy_s"] / s["window_s"] < 1.0
    # the step program's median duration lies inside the window per step
    assert s["step_device_s"] * 3 < s["window_s"] < s["step_device_s"] * 4


def test_recorded_trace_collectives(recorded):
    s = tr.summarize(recorded)
    names = {n for n, _, _ in recorded.ops[0] if tr.is_collective(n)}
    # XLA's combined all-reduces and jax.lax.psum's own, told by opcode
    assert any(n.startswith("%all-reduce.") for n in names)
    assert any(n.startswith("%psum.") for n in names)
    # every collective on the XLA Ops line is synchronous here, so the
    # boundary count over the raw events must agree with the intervals
    assert all(n.endswith(" [all-reduce]") for n in names)
    lo, hi = tr.window_of(recorded)
    both = [sweep(recorded.ops[d], lo, hi, tr.is_collective)
            for d in sorted(recorded.ops)]
    assert s["collective_s"] == pytest.approx(
        sum(c for c, _ in both) / 2 / 1e9, rel=1e-9)
    assert s["collective_exposed_s"] == pytest.approx(
        sum(a for _, a in both) / 2 / 1e9, rel=1e-9)
    assert 0 < s["collective_exposed_s"] <= s["collective_s"]


def test_recorded_trace_names_and_gaps(recorded):
    s = tr.summarize(recorded)
    events, seconds = tr.seconds_matching(s, r" \[all-reduce\]$")
    assert events > 0 and seconds > 0
    assert tr.seconds_per_step(s, tr.ATTENTION_KERNEL) is None  # einsum path
    top = tr.top_ops(s)
    assert len(top) == 8 and top[0][1] >= top[-1][1] > 0
    assert all(len(name) <= 120 + len(tr.PALLAS_TAG) for name, _ in top)
    assert s["gaps"] and all(label in ("fit.call", "data.next", "none")
                             for label, _ in s["gaps"])
    assert s["gaps"][0][1] == max(sec for _, sec in s["gaps"])
