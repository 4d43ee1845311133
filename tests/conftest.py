"""Test harness config.

Mirrors the reference's test strategy (SURVEY.md §4): CPU-hosted, with a
virtual 8-device mesh for distributed tests
(xla_force_host_platform_device_count — the TPU-world analog of the
reference's single-node multi-process CUDA_VISIBLE_DEVICES splitting).
"""
import numpy as np
import pytest
import jax

# tests run on the CPU, on eight virtual devices: the shared pin_cpu helper
# applies the env + config-API pin before any backend initializes
# (importing paddle_tpu is backend-free by design)
from paddle_tpu.device import pin_cpu

if not pin_cpu(8):
    raise RuntimeError("could not pin the 8-device virtual CPU platform")

# numeric-parity tests compare against float64-ish numpy; XLA's default
# matmul precision is bf16-based (the TPU/TF32 tradeoff the reference also
# makes on CUDA) — pin to highest for the test suite.
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu as paddle
    paddle.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture(autouse=True)
def _reset_monitor_registry():
    """Cross-test isolation for the PROCESS-GLOBAL monitor registry —
    the same fix PR 7 applied to the flight-recorder ring, hoisted to
    conftest: counters/gauges/histograms accumulate across tests, so a
    counter-delta assert could pass or fail depending on which files
    ran before it (file-ordering poisoning). Zeroing every stat at
    test START keeps cached handles valid (call sites hold Stat
    objects, reset() only zeroes values) and leaves post-test state
    inspectable on failure."""
    import sys
    mod = sys.modules.get("paddle_tpu.profiler.monitor")
    if mod is not None:
        mod.registry().reset()
    # the RecordEvent span ring is process-global too: a traced test's
    # in_trace spans must not be read by the next test's readers
    prof = sys.modules.get("paddle_tpu.profiler")
    if prof is not None:
        prof.clear_profiler_spans()
    yield


@pytest.fixture(autouse=True)
def _checkpoint_write_audit():
    """Integrity guard: every checkpoint save_sharded committed during a
    test must pass manifest checksum verification at teardown — an
    unchecksummed or torn write path cannot land silently. Tests that
    corrupt checkpoints ON PURPOSE go through paddle_tpu.testing.faults
    (whose corruptors call checkpoint.audit_forget)."""
    import sys
    mod = sys.modules.get("paddle_tpu.parallel.checkpoint")
    if mod is not None:
        mod._AUDIT.clear()
    yield
    mod = sys.modules.get("paddle_tpu.parallel.checkpoint")
    if mod is None:
        return
    paths, mod._AUDIT[:] = list(mod._AUDIT), []
    import os
    for p in paths:
        if os.path.isdir(p):
            mod.verify_checkpoint(p)   # raises CheckpointCorruptError


# ---------------------------------------------------------------- smoke tier
# `pytest -m smoke` — a <5-minute slice covering every subsystem (the full
# suite measures ~27 min on the 1-core build host). File-level membership:
# one fast representative per subsystem; the heavy compile farms
# (test_vision's model zoo, test_examples, the pipeline/CP/MoE mesh suites,
# launch's subprocess rendezvous) stay full-suite-only.
SMOKE_FILES = {
    # framework core + ops
    "test_core_coverage.py", "test_optable.py", "test_ops_math.py",
    "test_ops_manipulation.py", "test_double_grad.py",
    # static graph + IR + control flow + dy2static
    "test_static_program.py", "test_control_flow.py", "test_pir_passes.py",
    "test_dy2static.py",
    # models + kernels (smallest end-to-end slices)
    "test_e2e_mnist.py", "test_kernels.py", "test_kernel_primitives.py",
    "test_llama.py",
    # distributed (mesh-light representatives)
    "test_collective.py", "test_sharding_stages.py", "test_auto_parallel.py",
    "test_fleet_e2e.py", "test_distributed_tail.py", "test_67b_lowering.py",
    "test_compression.py", "test_ps_embedding.py",
    "test_kernel_selection.py", "test_plan3d.py", "test_plan4d.py",
    # io / inference / serving
    "test_multiprocess_loader.py", "test_inference.py", "test_int8.py",
    "test_serving.py", "test_serving_robustness.py", "test_paged_kv.py",
    "test_spec_decode.py", "test_tp_serving.py", "test_quant_serving.py",
    "test_serving_observability.py", "test_autoscale.py",
    "test_multi_tick.py", "test_admission.py",
    # high-level API + aux subsystems
    "test_hapi.py", "test_profiler.py", "test_checkpoint.py",
    "test_tokenizer.py", "test_misc_modules.py", "test_telemetry.py",
    "test_train_observability.py", "test_mem_observability.py",
    # fault-tolerance runtime (in-process; the chaos drills in
    # test_chaos_drill.py / test_chaos_serving.py stay full-suite-only)
    "test_fault_tolerance.py", "test_checkpoint_edges.py",
    "test_checkpoint_async.py", "test_elastic.py",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "smoke: fast cross-subsystem slice (<5 min; see conftest)")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 gate (`-m 'not "
        "slow'` — the ROADMAP verify command); full-suite-only. For "
        "redundant bench-style re-measurements on this noisy host, "
        "not for unique coverage")


def pytest_collection_modifyitems(config, items):
    import os
    for item in items:
        if os.path.basename(str(item.fspath)) in SMOKE_FILES:
            item.add_marker(pytest.mark.smoke)
