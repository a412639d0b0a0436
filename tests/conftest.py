"""Test harness configuration.

The reference spawns real multi-GPU processes per distributed test
(tests/unit/common.py:68 DistributedTest). The TPU-native equivalent is a
CPU-simulated multi-device mesh: 8 virtual XLA devices in ONE process, which
exercises the same SPMD programs (collectives included) deterministically.
These env vars must be set before the first ``import jax`` anywhere.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Keep XLA's C++ WARNING stream on: tests assert on compile-time diagnostics
# (e.g. the GSPMD involuntary-full-rematerialization warning in test_zero.py)
# which a TF_CPP_MIN_LOG_LEVEL >= 2 inherited from the caller would suppress.
# A deliberately lower (more verbose) inherited level is left alone.
try:
    if int(os.environ.get("TF_CPP_MIN_LOG_LEVEL", "1")) > 1:
        os.environ["TF_CPP_MIN_LOG_LEVEL"] = "1"
except ValueError:
    os.environ["TF_CPP_MIN_LOG_LEVEL"] = "1"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_topology():
    """Each test gets a fresh default mesh topology."""
    yield
    from deepspeed_tpu.parallel import mesh

    mesh.reset_default_topology()


@pytest.fixture
def eight_devices():
    import jax

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs
