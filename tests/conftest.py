"""Test config: run everything on an 8-device virtual CPU mesh.

Mirrors the reference's test leverage (SURVEY.md section 4): one suite,
re-runnable across contexts; distributed behavior tested in-process — here by
asking XLA for 8 virtual CPU devices so every sharding/collective path
compiles and executes without TPU hardware (the driver separately dry-runs
the multi-chip path).
"""

import os

# must be set before jax import; FORCE cpu whatever the session environment
# names — the suite needs the 8-device virtual CPU mesh (set
# MXTPU_TEST_PLATFORM to override, e.g. for a TPU run)
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
_platform = os.environ.get("MXTPU_TEST_PLATFORM", "cpu")
if _platform != "cpu":
    # keep the host backend registered alongside the accelerator so
    # ctx=mx.cpu() placement (reference semantics) stays real on TPU runs
    _platform = f"{_platform},cpu"
os.environ["JAX_PLATFORMS"] = _platform

import jax

# a pytest plugin may import jax before this conftest runs, freezing the
# env-derived platform config — override through the config API as well
jax.config.update("jax_platforms", _platform)
import numpy as np
import pytest

# numeric parity checks assume true f32 matmuls (TPU perf path uses bf16 via
# AMP explicitly; the default low-precision dot would fail fp32 tolerance)
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from the tier-1 run "
        "(-m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: fault-injected failure-path scenario "
        "(serving resilience; runs in tier-1)")


@pytest.fixture(autouse=True)
def _seed_rng():
    import mxnet_tpu as mx

    mx.random.seed(42)
    np.random.seed(42)
    yield


@pytest.fixture
def paged_kernels(monkeypatch):
    """``paged_kernels(on)`` sets what ``ops.paged.kernels_on()`` answers
    for the rest of the test: True runs the paged attention kernels where
    the CPU would take the ``jax.numpy`` forms (interpreted here:
    ``MXTPU_FLASH_INTERPRET`` is every kernel's and stays the
    environment's), False keeps the ``jax.numpy`` forms where a TPU would
    take the kernels. Every asker reaches the function as
    ``paged.kernels_on()`` while it is traced, so the answer holds for
    programs traced after the call; a trace is cached by the callable, so
    jit another one after changing it."""
    from mxnet_tpu.ops import paged

    def force(on):
        monkeypatch.setattr(paged, "kernels_on", lambda: bool(on))

    return force
