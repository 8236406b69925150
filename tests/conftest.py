"""Test config: run everything on a virtual 8-device CPU mesh.

The suite runs on the CPU whether or not the machine has a chip: the XLA
host device count is set to 8 and the CPU platform is forced before the
first backend client is created (SURVEY §4: XLA's CPU backend is the
"fake TPU" for sharding tests; __graft_entry__.dryrun_multichip drives the
multi-chip path the same way).  A chip belongs to one process at a time,
so only the explicit on-chip tier below ever touches it.
"""
import os

# TPUMX_TEST_TPU=1 skips the CPU pin so the on-chip tier can actually run:
#   TPUMX_TEST_TPU=1 python -m pytest tests/test_tpu_chip.py -m tpu
# (one process only — see docstring above)
_TPU_TIER = os.environ.get("TPUMX_TEST_TPU") == "1"

if not _TPU_TIER:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=8").strip()

import jax

if not _TPU_TIER:
    jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    """Fixed seeds per test — the reference's @with_seed decorator pattern."""
    np.random.seed(0)
    import tpu_mx as mx
    mx.random.seed(0)
    yield
