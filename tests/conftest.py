"""Test bootstrap: force an 8-device virtual CPU mesh.

The TPU analog of the reference's ``mpirun -n 2 py.test`` harness
(``Makefile:2-3``): multi-chip is simulated by multi-device single-process
via ``--xla_force_host_platform_device_count`` — the SURVEY §4 test
strategy. Must run before JAX initializes its backends, hence env setup at
conftest import time (``JAX_PLATFORMS`` is honoured, and spawned fleet
processes inherit it).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from the tier-1 `-m 'not slow'` "
        "gate (e.g. the double-run chaos determinism check; its fast "
        "single-run form stays in the default path)",
    )


# ---------------------------------------------------------------------------
# Tier-1 hygiene: the tier-1 gate runs `-m 'not slow'` under a hard
# 870 s timeout. TIER1_SLOW moves the heaviest passing tests (each
# 15–45 s; the 3×120 s compressed-mailbox convergence timeouts) out of
# the tier-1 selection — they all still run in `make test`.
# ---------------------------------------------------------------------------

# nodeid prefixes (params stripped) — heaviest tests by --durations on
# this 2-core CI box; sum removed ≈ 800 s, bringing tier-1 to ~700 s.
TIER1_SLOW = (
    "tests/test_dcn.py::test_codec_compressed_mailbox_trains",
    "tests/test_sharded.py::test_sharded_checkpoint_resume_continues_independently",
    "tests/test_sharded.py::test_sharded_ps_converges_with_per_shard_versions",
    "tests/test_async_train.py::test_sync_barrier_collapses_to_straggler_async_does_not",
    "tests/test_async_train.py::test_worker_crash_and_elastic_replacement",
    "tests/test_async_train.py::test_gpt_causal_lm_over_async_wire",
    "tests/test_async_train.py::test_async_jitted_workers_converge_with_staleness_and_drops",
    "tests/test_async_train.py::test_inxla_sampled_staleness_matches_shm_arrival_histogram",
    "tests/test_agg.py::test_serve_loop_one_decode_per_publish",
    "tests/test_agg.py::test_serve_loop_screens_nonfinite_payload",
    "tests/test_models.py::test_scan_layers_matches_loop_layout",
    "tests/test_models.py::test_bf16_logits_loss_matches_f32",
    "tests/test_models.py::test_resnet_batchnorm_aux_state_distributed",
    "tests/test_models.py::test_resnet18_forward_and_grad",
    "tests/test_models.py::test_resnet50_forward",
    "tests/test_models.py::test_resnet18_distributed_step",
    "tests/test_attention_pallas.py::test_ring_flash_gradients_flow",
    "tests/test_trainer.py::test_torch_interop_roundtrip",
    "tests/test_tcp.py::test_server_checkpoint_resume_continues_training",
    "tests/test_tcp.py::test_async_jitted_workers_converge_over_tcp",
    "tests/test_ep.py::test_moe_top2_matches_dense_oracle",
    "tests/test_ring.py::test_ring_grads_flow",
    "tests/test_numerics.py::test_serve_quarantines_nan_worker_policy_skip",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        base_id = item.nodeid.split("[", 1)[0]
        if base_id.startswith(TIER1_SLOW):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def fresh_setup_log():
    """The set-up log is the process's, and a recorder starts with its
    rows: a test begins with none of those that earlier tests left."""
    from pytorch_ps_mpi_tpu.telemetry import recorder

    recorder._setup_log.clear()


@pytest.fixture(scope="session")
def mesh8():
    from pytorch_ps_mpi_tpu.mesh import make_mesh

    assert len(jax.devices()) == 8, jax.devices()
    return make_mesh()


@pytest.fixture(scope="session")
def mesh4x2():
    from pytorch_ps_mpi_tpu.mesh import make_mesh

    return make_mesh(shape=(4, 2), axis_names=("data", "seq"))


@pytest.fixture
def annotations_made(monkeypatch):
    """``jax.profiler``'s two annotation classes, patched to count: the
    list of ``(class, name, kwargs)`` of every one constructed."""
    made = []
    for cls in ("TraceAnnotation", "StepTraceAnnotation"):
        real = getattr(jax.profiler, cls)

        def counted(name, *a, _real=real, _cls=cls, **kw):
            made.append((_cls, name, kw))
            return _real(name, *a, **kw)

        monkeypatch.setattr(jax.profiler, cls, counted)
    return made
