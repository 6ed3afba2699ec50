"""Test fixture: run the whole suite on a virtual 8-device CPU mesh.

This is the TPU build's analog of the reference's import-under-new-context
trick (tests/python/gpu/test_operator_gpu.py:? imports the unittest modules
with ctx=gpu): XLA's CPU backend is the "fake device" the reference never
had, and --xla_force_host_platform_device_count=8 gives every test a
multi-device mesh without hardware.  Must run before jax initialises a
backend.  The lane runs with ``JAX_PLATFORMS=cpu`` in the environment (the
tier-1 command in ROADMAP.md); the ``jax_platforms`` pin below makes a bare
``pytest tests/`` do the same on a machine that has a chip.

The persistent compile cache is switched off for this process and, through
the environment, for every child a test starts: ``import mxnet_tpu`` points
it at the in-tree ``.jax_cache/`` (``mxnet_tpu.base``), which belongs to
chip runs and must not fill up with CPU executables.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") +
    " --xla_force_host_platform_device_count=8")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# float64 needed by finite-difference gradient checks (CPU-only; the TPU
# bench path stays in x32/bf16)
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

# Fast CI lane: the heavyweight model/system suites carry the 'slow'
# marker so `pytest -m "not slow"` is a <5-min core lane (ops, autograd,
# gluon fundamentals, data plane, serialization, kvstore), while the
# default full run keeps everything.  Module-level marking keeps the
# split in one place.
_SLOW_MODULES = {
    "test_llama", "test_model_zoo", "test_nlp_models",
    "test_detection_models", "test_operator_sweep", "test_quantization",
    "test_module", "test_moe", "test_ring", "test_parallel",
    "test_onnx", "test_dist_loopback", "test_nightly_large",
    "test_model", "test_rnn", "test_contrib_gluon", "test_fm",
    "test_contrib", "test_fault_injection",
}


# Heaviest tier: the model-family suites (big configs, many compiles).
# `pytest -m "not heavy"` is the mid lane — core + distributed-system
# suites in a ~15-min window — while cheap per-family smokes live in the
# fast lane (tests/test_model_smoke.py).
_HEAVY_MODULES = {
    "test_llama", "test_model_zoo", "test_nlp_models",
    "test_detection_models", "test_moe", "test_onnx", "test_model",
    "test_rnn", "test_quantization",
}


def pytest_collection_modifyitems(items):
    for item in items:
        if item.module.__name__ in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
        if item.module.__name__ in _HEAVY_MODULES:
            item.add_marker(pytest.mark.heavy)


@pytest.fixture(autouse=True)
def _seed():
    import mxnet_tpu as mx

    mx.random.seed(42)
    yield


@pytest.fixture(autouse=True, scope="module")
def _own_lane_log():
    """The lane log is one ring a process and outlives every server, and the
    benchmark's readers take it from its start (``token_gaps`` gives up at a
    speculative tick).  Every test module starts with an empty one, so what
    a file reads does not depend on which files ran before it on its worker."""
    from mxnet_tpu.telemetry import tracing

    tracing._lane_log.clear()
    yield
