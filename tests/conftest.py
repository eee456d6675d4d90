"""Test harness config: force an 8-device virtual CPU mesh BEFORE jax
import so multi-device/GSPMD tests run without TPU hardware (SURVEY.md §4:
dist-parity tests via multi-device CPU XLA)."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import pytest


def pytest_configure(config):
    # "slow": excluded from the time-budgeted tier-1 run (-m 'not slow');
    # still executed by tools/run_ci.sh's python stage, which runs the
    # whole suite unfiltered
    config.addinivalue_line(
        "markers", "slow: long-running (subprocess-spawning) tests "
        "excluded from the tier-1 budget")


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Give every test fresh default programs, scope and name counters."""
    import paddle_tpu as fluid
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core.scope import Scope
    import paddle_tpu.executor as executor_mod

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch({})
    executor_mod._global_scope = Scope()
    executor_mod._scope_stack = [executor_mod._global_scope]
    np.random.seed(42)
    yield


@pytest.fixture(scope="module", autouse=True)
def _fresh_executables():
    """A test module starts with none of another module's executables: the
    process-wide registry is content-addressed, so a program that an
    earlier file of this worker compiled would otherwise be served from
    it, traced by nobody, and a module that counts what ITS run traced
    (the set-up ledger's rehearsal) would pass or fail by the order the
    files came in."""
    import paddle_tpu.executor as executor_mod
    from paddle_tpu.core import exec_cache
    from paddle_tpu.observability import explain

    executor_mod._shared_executables.clear()
    exec_cache.reset_stats()
    explain.reset()


_NATIVE_BUILD_RESULT = {}


def build_native_binary(name):
    """Locate a native/build binary, running the cmake build AT MOST once
    per session and only when first asked (never at collection time).
    Returns the path or None when the toolchain is unavailable. Shared by
    every test that drives a native executable."""
    import subprocess

    if name in _NATIVE_BUILD_RESULT:
        return _NATIVE_BUILD_RESULT[name]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "native", "build", name)
    if not os.path.exists(path):
        try:
            subprocess.run(
                ["cmake", "-S", os.path.join(root, "native"), "-B",
                 os.path.join(root, "native", "build"), "-G", "Ninja"],
                check=True, capture_output=True)
            subprocess.run(
                ["cmake", "--build", os.path.join(root, "native", "build")],
                check=True, capture_output=True)
        except (OSError, subprocess.CalledProcessError):
            pass
    _NATIVE_BUILD_RESULT[name] = path if os.path.exists(path) else None
    return _NATIVE_BUILD_RESULT[name]
