"""Chip-vs-CPU per-op parity (SURVEY §4's acceptance mechanism).

Runs tools/parity_sweep.py's battery through check_consistency when a
non-CPU platform is available. The default CI environment pins
JAX_PLATFORMS=cpu (conftest), so this file is skipped there; on a
TPU-equipped host run it with:

    MXNET_TPU_TEST_PLATFORM=tpu,cpu python -m pytest tests/test_tpu_parity.py

The standalone sweep (tools/parity_sweep.py) writes the committed
PARITY_TPU.json evidence file.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))


def _tpu_available():
    import jax

    try:
        return any(d.platform != "cpu" for d in jax.devices())
    except Exception:
        return False


pytestmark = pytest.mark.skipif(
    not _tpu_available(),
    reason="needs a TPU (run with MXNET_TPU_TEST_PLATFORM=<tpu platform>,cpu)")


def _battery():
    from parity_sweep import battery

    return battery()


@pytest.mark.parametrize("case", _battery() if _tpu_available() else [],
                         ids=lambda c: c[0])
def test_strict_fp32_parity(case):
    """fp32 must match CPU exactly (1e-3) when the MXU keeps fp32."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.test_utils import check_consistency

    name, build, shapes = case
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        np.random.seed(7)
        ctx_list = [
            {"ctx": mx.cpu(), "type_dict":
             {k: np.float32 for k in shapes}, **shapes},
            {"ctx": mx.tpu(), "type_dict":
             {k: np.float32 for k in shapes}, **shapes},
        ]
        check_consistency(build(), ctx_list, rtol=1e-3, atol=5e-4)
    finally:
        jax.config.update("jax_default_matmul_precision", None)
