"""Test harness config: force the CPU backend with 8 virtual devices.

Mirrors the reference's test strategy (SURVEY.md §4): unit tests run on a
host backend with numpy as oracle; multi-device behaviour is simulated via
XLA's virtual host devices; cpu↔tpu consistency has its own opt-in marker.

The suite pins the CPU platform itself (jax.config.update below), so it
runs the same with or without JAX_PLATFORMS=cpu in the environment and
never takes the chip on a TPU host; MXNET_TEST_PLATFORM=tpu lifts the pin
for the on-device lane.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("MXNET_TEST_SEED", "0")

import jax

# MXNET_TEST_PLATFORM=tpu keeps the real accelerator visible for the
# opt-in on-device suite (tests/test_tpu_device.py, run via
# tools/run_tpu_tests.py); default pins the virtual-8-device CPU backend.
_ON_DEVICE = os.environ.get("MXNET_TEST_PLATFORM") == "tpu"
if _ON_DEVICE:
    from mxnet_tpu.compile_cache import jax_cache

    jax_cache.configure()
else:
    jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_report_header(config):
    """The on-device lane names its device in the session header, where
    tools/run_tpu_tests.py reads it (one process per chip: a second
    process could not ask)."""
    if _ON_DEVICE:
        devs = jax.devices()
        return (f"mxnet_tpu device: platform={devs[0].platform} "
                f"kind={devs[0].device_kind!r} count={len(devs)}")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from tier-1 (-m 'not slow'); the "
        "nightly lanes run these")
    # MXNET_SAN=1: importing mxnet_tpu (in every test) arms the
    # sanitizer; this plugin turns any violation into a failure of the
    # test it happened under and writes MXSAN.json at session end
    # (tools/run_nightly.py archives it).  Truthiness mirrors
    # base.get_env's bool parse WITHOUT importing the framework here
    # (that must stay lazy for sessions that don't use the sanitizer).
    _raw = os.environ.get("MXNET_SAN", "").strip().lower()
    if _raw not in ("", "0", "false", "no", "off"):
        import sys as _sys

        _tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        if _tools not in _sys.path:
            _sys.path.insert(0, _tools)
        import mxsan_pytest

        config.pluginmanager.register(mxsan_pytest.MxsanPlugin(),
                                      "mxsan")


@pytest.fixture(autouse=True)
def _seed():
    """with_seed-style reproducibility (ref: tests/python/unittest/common.py)."""
    seed = int(os.environ["MXNET_TEST_SEED"])
    np.random.seed(seed)
    import mxnet_tpu as mx

    mx.random.seed(seed)
    yield
