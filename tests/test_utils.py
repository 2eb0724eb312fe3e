"""Utils: wire format round-trips (replacing the reference's broken
``serialization.py`` experiment, SURVEY §2.3 — ours actually works and is
tested), checkpoint/resume (absent in the reference, SURVEY §5.4), and
metrics helpers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.utils import (
    MetricsAccumulator,
    StepTimer,
    load_pytree,
    pack_pytree,
    save_pytree,
    unpack_pytree,
)
from pytorch_ps_mpi_tpu.utils.checkpoint import CheckpointManager


def tree():
    return {
        "w": jnp.arange(12.0).reshape(3, 4),
        "nested": {"b": jnp.ones((5,), jnp.int32), "s": jnp.float32(2.5)},
    }


def assert_tree_equal(a, b):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)), a, b
    )


def test_pack_unpack_roundtrip():
    t = tree()
    buf, spec = pack_pytree(t)
    out = unpack_pytree(buf, spec, template=t)
    assert_tree_equal(t, out)
    # immutable-bytes input (the old return type) still unpacks
    assert_tree_equal(t, unpack_pytree(bytes(buf), spec, template=t))


def test_unpack_truncated_buffer_raises_clearly():
    # a short buffer must fail with a ValueError naming both sizes, not
    # an opaque downstream reshape error
    t = tree()
    buf, spec = pack_pytree(t)
    with pytest.raises(ValueError, match="truncated buffer"):
        unpack_pytree(buf[: len(buf) - 8], spec, template=t)
    with pytest.raises(ValueError, match="truncated buffer"):
        unpack_pytree(b"", spec, template=t)


def test_unpack_copy_modes():
    t = tree()
    buf, spec = pack_pytree(t)
    # default: independent writable copies
    out = unpack_pytree(buf, spec, template=t)
    out["w"][0, 0] = 99.0
    assert np.asarray(unpack_pytree(buf, spec, template=t)["w"])[0, 0] == 0.0
    # copy=False: zero-copy views into the buffer (checkpoint-load fast
    # path) — mutating the buffer is visible through the view
    views = unpack_pytree(buf, spec, template=t, copy=False)
    assert views["w"].base is not None
    assert views["w"][1, 1] == 5.0
    buf[:] = bytes(len(buf))  # zero the backing buffer
    assert views["w"][1, 1] == 0.0


def test_save_load_roundtrip(tmp_path):
    t = tree()
    path = str(tmp_path / "state.npz")
    save_pytree(path, t)
    out = load_pytree(path, t)
    assert_tree_equal(t, out)


def test_load_wrong_template_raises(tmp_path):
    t = tree()
    path = str(tmp_path / "state.npz")
    save_pytree(path, t)
    with pytest.raises(ValueError):
        load_pytree(path, {"only_one": jnp.zeros(1)})


def test_checkpoint_manager_numpy_fallback(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ckpt"), use_orbax=False, max_to_keep=2)
    t = tree()
    for step in [1, 2, 3]:
        mgr.save(step, jax.tree.map(lambda x: x * step, t))
    assert mgr.latest_step() == 3
    out = mgr.restore(t)
    assert_tree_equal(out, jax.tree.map(lambda x: x * 3, t))
    # gc kept only the last 2
    assert mgr._numpy_steps() == [2, 3]


def test_checkpoint_manager_orbax(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    t = {"w": jnp.arange(6.0).reshape(2, 3)}
    mgr.save(0, t)
    out = mgr.restore(t)
    assert_tree_equal(out, t)


def test_step_timer_and_accumulator():
    timer = StepTimer()
    with timer("comm_wait"):
        pass
    assert "comm_wait" in timer.data and timer.data["comm_wait"] >= 0

    acc = MetricsAccumulator()
    acc.add({"a": 1.0, "b": 2.0})
    acc.add({"a": 3.0})
    m = acc.mean()
    assert m["a"] == 2.0 and m["b"] == 2.0 and len(acc) == 2


def test_save_load_compressed_roundtrip(tmp_path):
    t = tree()
    path = str(tmp_path / "state_c.npz")
    save_pytree(path, t, compress=True)
    out = load_pytree(path, t)
    assert_tree_equal(t, out)


def test_compressed_checkpoint_smaller_for_sparse(tmp_path):
    sparse = {"w": jnp.zeros((64, 64)).at[0, 0].set(1.0)}
    p1 = str(tmp_path / "raw.npz")
    p2 = str(tmp_path / "comp.npz")
    save_pytree(p1, sparse, compress=False)
    save_pytree(p2, sparse, compress=True)
    import os
    assert os.path.getsize(p2) < os.path.getsize(p1) / 4
    assert_tree_equal(load_pytree(p2, sparse), sparse)


def test_print_summary(capsys):
    from pytorch_ps_mpi_tpu.utils.metrics import print_summary

    print_summary({"a": jnp.zeros((3, 4)), "b": [1, jnp.ones(2)], "c": "x"})
    out = capsys.readouterr().out
    assert "array(3, 4)" in out and "'x'" in out


def test_devtime_helpers():
    """timed awaits the result and returns a positive wall; safe_ratio
    never raises on a zero denominator; an unknown device_kind is an
    error, not a 0.0 peak."""
    import jax.numpy as jnp
    import pytest

    from pytorch_ps_mpi_tpu.utils.devtime import (
        peak_flops_for,
        safe_ratio,
        timed,
    )

    assert safe_ratio(1.0, 0.0) == 0.0
    assert safe_ratio(6.0, 3.0) == 2.0
    assert timed(lambda: jnp.ones((8, 8)) * 2.0, reps=2) > 0.0
    assert peak_flops_for("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no published peak"):
        peak_flops_for("cpu")


def test_data_prefetch():
    """prefetch(): order-preserving, bounded, propagates source errors."""
    from pytorch_ps_mpi_tpu.data import prefetch

    assert list(prefetch(iter(range(10)), depth=3)) == list(range(10))

    def boom():
        yield 1
        raise RuntimeError("source failed")

    it = prefetch(boom(), depth=2)
    assert next(it) == 1
    import pytest as _pytest

    with _pytest.raises(RuntimeError, match="source failed"):
        next(it)

    # overlaps: consuming 3 of an endless stream returns promptly
    import itertools
    vals = list(itertools.islice(prefetch(iter(int, 1), depth=2), 3))
    assert vals == [0, 0, 0]


def test_codec_timing_encode_phase_is_partial_cost():
    """phase='encode' times the encode half alone: positive, and not
    more than the full roundtrip by more than measurement noise (CPU
    backend: both are exact single-call walls)."""
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu.codecs import get_codec
    from pytorch_ps_mpi_tpu.utils.devtime import codec_roundtrip_seconds

    code = get_codec("blocktopk", fraction=0.05)
    shape = (256, 1024)
    enc = codec_roundtrip_seconds(code, shape, jnp.float32, k=8,
                                  phase="encode")
    both = codec_roundtrip_seconds(code, shape, jnp.float32, k=8)
    assert enc > 0.0
    assert enc < both * 2.0  # same order; roundtrip adds decode on top

    import pytest

    with pytest.raises(ValueError):
        codec_roundtrip_seconds(code, shape, jnp.float32, k=8, phase="dec")


def test_save_load_pytree_python_scalar_leaves(tmp_path):
    """Regression: load_pytree's compressed path crashed on template
    leaves that are plain python scalars (an optimizer state_dict
    carries step_count as an int) — np.asarray-coerced dtype/shape must
    be used, not array-only attributes."""
    import numpy as np

    from pytorch_ps_mpi_tpu.utils.serialization import (
        load_pytree,
        save_pytree,
    )

    tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "step_count": 7}
    p = str(tmp_path / "t.npz")
    save_pytree(p, tree, compress=True)
    out = load_pytree(p, {"w": np.zeros((3, 4), np.float32),
                          "step_count": 0})
    np.testing.assert_array_equal(out["w"], tree["w"])
    assert int(out["step_count"]) == 7


def test_the_package_imports_nothing_above_it():
    """The arrow points one way: ``benchmarks``, ``tools``, ``chipbench``,
    ``examples`` and the tests use the package, and no module of the
    package imports one of them (or a ``bench`` beside it), at module
    level or inside a function."""
    import ast
    import glob
    import os

    import pytorch_ps_mpi_tpu

    above = {"benchmarks", "tools", "chipbench", "examples", "bench", "tests"}
    root = os.path.dirname(pytorch_ps_mpi_tpu.__file__)
    found = []
    for path in sorted(glob.glob(os.path.join(root, "**", "*.py"),
                                 recursive=True)):
        with open(path, encoding="utf-8") as f:
            tree_ = ast.parse(f.read())
        for node in ast.walk(tree_):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module or ""]
            found += [(os.path.relpath(path, root), node.lineno, n)
                      for n in names if n.split(".")[0] in above]
    assert not found, found
