"""Timeline-level comm/compute overlap measurement.

Pure interval math is tested exactly; the trace-driven path is tested on
the 8-device CPU mesh with a real psum program, asserting the
accounting invariants a correct sweep must satisfy (the CPU scheduler's
actual overlap amount is a measurement, not a spec, so only invariants
are asserted — the committed overlap artifact carries the numbers).
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pytorch_ps_mpi_tpu.utils.tracing import (
    _interval_intersection_len,
    _interval_union,
    profiled_overlap,
)


def test_interval_union_merges_and_sorts():
    assert _interval_union([]) == []
    assert _interval_union([(3, 5), (0, 2)]) == [(0, 2), (3, 5)]
    # overlapping + touching + contained
    assert _interval_union([(0, 2), (1, 4), (4, 6), (5, 5.5), (10, 11)]) == [
        (0, 6), (10, 11)
    ]


def test_interval_intersection_len():
    a = _interval_union([(0, 10)])
    b = _interval_union([(2, 3), (5, 7), (9, 12)])
    assert _interval_intersection_len(a, b) == (1 + 2 + 1)
    assert _interval_intersection_len(a, []) == 0
    # disjoint
    assert _interval_intersection_len(
        _interval_union([(0, 1)]), _interval_union([(2, 3)])
    ) == 0
    # identical
    assert _interval_intersection_len(a, a) == 10


def test_profiled_overlap_invariants_on_real_psum_program(mesh8):
    def spmd(x, w):
        y = jnp.tanh(x @ w)
        g = jax.lax.psum(y @ w.T, "data")
        return g.sum()

    f = jax.jit(
        jax.shard_map(
            spmd, mesh=mesh8, in_specs=(P("data"), P()), out_specs=P(),
            check_vma=False,
        )
    )
    x = jax.random.normal(jax.random.key(0), (256, 128))
    w = jax.random.normal(jax.random.key(1), (128, 128))
    jax.block_until_ready(f(x, w))  # warm so the trace sees execution only

    out, d = profiled_overlap(lambda: jax.block_until_ready(f(x, w)))
    assert d["devices"] == 8
    assert d["comm_s"] > 0, "the psum must appear as comm"
    assert d["compute_s"] > 0
    # sweep-line invariants
    assert 0.0 <= d["overlap_s"] <= min(d["comm_s"], d["compute_s"]) + 1e-12
    assert 0.0 <= d["overlap_frac"] <= 1.0
    assert d["serial_equiv_s"] == d["comm_s"] + d["compute_s"]
    # union ≤ sum, and union ≥ max of the parts
    assert d["busy_union_s"] <= d["serial_equiv_s"] + 1e-12
    assert d["busy_union_s"] >= max(d["comm_s"], d["compute_s"]) - 1e-12
    # conservation: union + overlap == comm + compute (exact by sweep)
    assert abs(
        (d["busy_union_s"] + d["overlap_s"]) - d["serial_equiv_s"]
    ) < 1e-9
