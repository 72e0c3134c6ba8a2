"""Test configuration: run everything on a virtual 8-device CPU mesh.

JAX_PLATFORMS=cpu is required in this container: jax otherwise finds the
libtpu wheel and waits for a device that is not there.  The tier-1 command
sets it; the config update below covers a bare ``pytest``.  jax leaves backend
initialization lazy, so setting the platform and the host device count here,
before any test touches a device, gives the 8-way CPU mesh the sharding tests
need (xla_force_host_platform_device_count, SURVEY §4.4).
"""
import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# The package's compile-cache rule (mxnet_tpu/__init__.py): a set
# JAX_COMPILATION_CACHE_DIR is left alone, by this file too.  Unset, the
# tests name their own fixed directory through the same variable, before jax
# is imported, so the package sets nothing and the subprocess children (dist
# workers, examples, launcher tests: most of the suite's wall time) inherit
# it.  It stays where earlier runs on this box left it warm; the suite is
# compile-heavy and tier-1 is cut by a timeout.  (Not the directory the
# suite used up to PR 25, /tmp/mxnet_tpu_test_jax_cache: runs on this box
# left tiny programs there while a test held jax's thresholds at 0, see
# _cache_thresholds below.)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      "/tmp/mxnet_tpu_tests_jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest


@pytest.fixture(autouse=True)
def _seed():
    import mxnet_tpu as mx

    mx.random.seed(42)
    yield


_CACHE_THRESHOLDS = ("jax_persistent_cache_min_compile_time_secs",
                     "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def _cache_thresholds():
    """What a test changes of jax's persistent-cache thresholds ends with
    the test.  ``benchmark/run.py``'s ``configure_jax()`` stores EVERY
    program, and tests/benchmark call its ``main()`` in this process:
    without the restore, every later test of that worker would write its
    tiny programs into the shared cache directory."""
    before = [getattr(jax.config, k) for k in _CACHE_THRESHOLDS]
    yield
    for key, value in zip(_CACHE_THRESHOLDS, before):
        if getattr(jax.config, key) != value:
            jax.config.update(key, value)


# ---------------------------------------------------------------------------
# smoke tier (r3 verdict #7): `pytest -m smoke` gives <2 min signal across
# every subsystem; the full ~750-test suite stays the default.  The tier
# list is central here so it's one place to curate.
# ---------------------------------------------------------------------------
_SMOKE = {
    "test_ndarray.py::test_arithmetic",
    "test_autograd.py::test_chain_rule",
    "test_gluon.py::test_sequential_forward",
    "test_symbol.py::test_infer_shape_conv_batchnorm",
    "test_module.py::test_module_fit_converges",
    "test_op_tail.py::test_batch_take",
    "test_pallas.py::test_flash_attention_forward",
    "test_amp.py::test_amp_bf16_workflow_trains",
    "test_checkpoint_viz.py::test_async_checkpoint_write_rotate",
    "test_io_image.py::test_recordio_roundtrip",
    "test_native_io.py::test_native_iter_shapes_and_labels",
    "test_control_flow.py::test_foreach_cumsum",
    "test_quantization_subgraph.py::test_quantized_fc_matches_f32",
    "test_sparse_namespace.py::test_sparse_dot_csr",
    "test_model_zoo.py::test_model_forward",
    "test_profiler.py::test_dumps_ranks_ops_for_model_step",
    "test_rnn_legacy.py::test_lstm_gru_cell_unroll",
    "test_cv_ops.py::test_box_nms_suppresses_overlaps",
    "test_compat_tail.py::test_legacy_save_load_roundtrip",
    "test_parallel.py::test_make_mesh_axes",
    "test_parallel.py::test_kvstore_semantics",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        # nodeid like "tests/test_x.py::test_y[param]" -> "test_x.py::test_y"
        base = item.nodeid.split("/")[-1].split("[")[0]
        if base in _SMOKE:
            item.add_marker(pytest.mark.smoke)
        name = item.nodeid.split("/")[-1]
        if name.startswith("test_dist_launch.py::"):
            item.add_marker(pytest.mark.dist)
        # slow-tier by rationale: the n=3 dist variants re-cover the n=2
        # path with non-power-of-two ranks (redundant for the default
        # tier, r4 verdict #9); the 3D bert example is a ~1 min
        # subprocess whose parity is already covered by
        # test_bert_pp.py::test_pp_tp_dp_3d_parity in the default tier
        if base in ("test_dist_launch.py::test_dist_sync_kvstore_three_workers",
                    "test_dist_launch.py::test_dist_sync_training_three_workers",
                    "test_examples_e2e.py::test_bert_pretrain_3d_e2e"):
            item.add_marker(pytest.mark.slow)
        # compile-heavy composition tests whose constituent paths keep
        # default-tier coverage (the tier-1 wall-clock budget is tight on
        # this box — cold XLA:CPU compiles run ~20s each): ring-parity
        # re-covers the ring kernel units + sp sharding tests; the
        # telemetry gang e2e re-covers the telemetry units + the no-jax
        # supervisor tests; the 2D pp parity is subsumed by
        # test_pp_tp_dp_3d_parity, which deliberately STAYS default-tier —
        # it is the 3D coverage the e2e exclusion above leans on and it
        # exercises the same GPipe schedule plus tp.
        if base in ("test_parallel.py::test_ring_attention_training_step_parity",
                    "test_bert_pp.py::test_pp_bert_matches_dp_only",
                    "test_telemetry.py::"
                    "test_two_rank_gang_emits_jsonl_and_advancing_heartbeats"):
            item.add_marker(pytest.mark.slow)
        if (name.startswith("test_op_sweep.py::test_gradient")
                or name.startswith("test_op_sweep.py::test_bf16_backward")):
            item.add_marker(pytest.mark.slow)
