"""Test env: force an 8-device virtual CPU mesh BEFORE jax initializes.

Mirrors the reference's trick of testing multi-device paths with multiple CPU
contexts (SURVEY.md §4, tests/python/unittest/test_model_parallel.py).

TPU tier: ``MXTPU_TEST_TPU=1 pytest -m tpu`` keeps the accelerator backend
available (CPU stays reachable via jax.devices('cpu')) and runs the
cross-device consistency tests — the analogue of the reference's GPU tier
(tests/python/gpu/test_operator_gpu.py check_consistency).
"""
import os

import pytest

_TPU_TIER = os.environ.get("MXTPU_TEST_TPU") == "1"

if not _TPU_TIER:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: cross-device consistency tests that need a real "
        "accelerator (run with MXTPU_TEST_TPU=1 pytest -m tpu)")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` gate "
        "(long convergence runs and known-flaky-threshold gates)")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if "tpu" in item.keywords and not _TPU_TIER:
            item.add_marker(pytest.mark.skip(
                reason="TPU tier disabled (set MXTPU_TEST_TPU=1)"))
        elif "tpu" not in item.keywords and _TPU_TIER and \
                config.getoption("-m") == "tpu":
            pass  # -m tpu already deselects these
