"""On-chip test tier (`pytest -m tpu`): the kernel-tail checks that CPU
interpret mode cannot prove (real Mosaic enforces constraints the
interpreter does not, and the MXU's default precision is not the CPU's).

These wrap tools/tpu_validate.py's check functions as pytest nodes.  The
default conftest pins tests to the CPU, so run the tier on the chip, in
one process, as:

    TPUMX_TEST_TPU=1 python -m pytest tests/test_tpu_chip.py -m tpu

which skips the CPU pin; without the env var (or off-chip) every check
skips rather than green-washing.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

pytestmark = pytest.mark.tpu


def _on_tpu():
    import jax
    return jax.devices()[0].platform == "tpu"


@pytest.fixture(scope="module")
def tpu():
    if not _on_tpu():
        pytest.skip("no TPU backend in this process")


import tpu_validate as tv  # noqa: E402


@pytest.mark.parametrize("name,fn", tv.CHECKS,
                         ids=[n for n, _ in tv.CHECKS])
def test_chip_check(tpu, name, fn):
    fn()
