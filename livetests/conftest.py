"""Live-TPU test lane: real-Mosaic execution of the pallas kernels.

The main suite (`tests/`) forces an 8-device virtual CPU mesh and runs the
pallas kernels in interpret mode — it validates semantics, not lowering.
This lane is the opposite: it requires a REAL accelerator and executes the
kernels through the actual Mosaic compiler, closing the "interpret-mode-only
in CI" gap (SURVEY.md §4 test strategy; the reference has no analog because
its CUDA tests always ran on hardware).

``pytest livetests/`` means "on the chip": without one every test FAILS —
a lane that skips when the device is missing reports green for a kernel
nobody compiled.  One process holds the chip, so run the lane on its own.

Run:  python -m pytest livetests/ -q
"""

import pytest


@pytest.fixture(scope="session")
def jx():
    import jax

    from dmlc_core_tpu.device import init_device

    info = init_device()
    assert info.platform == "tpu", (
        f"livetests/ runs on the chip; JAX reports platform="
        f"{info.platform!r} ({info.device_kind}, {info.count} device(s))")
    return jax
