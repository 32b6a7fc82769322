"""TPU smoke-tier harness.

Unlike tests/conftest.py this does NOT pin jax_platforms=cpu: these tests
run on the attached TPU, in the one process that holds it (no xdist
workers, no subprocesses that touch JAX). Run them on the chip machine:

    python -m pytest tests_tpu -q

A machine with no chip FAILS this tier — it is the tier's whole subject.
Keep it under 5 minutes: one train step per model family, one scorer
call, one HBM device_put, each kernel once — enough that chip-only
breakage (Mosaic refusals, dtype/layout surprises) surfaces outside the
bench. ``chip_smoke.py`` at the repo root is the full-size counterpart.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_collection_modifyitems(config, items):
    # Everything in this directory is implicitly tpu-marked.
    for item in items:
        item.add_marker(pytest.mark.tpu)


@pytest.fixture(scope="session")
def tpu_device():
    import jax

    from dragonfly2_tpu.utils.compilecache import enable_compilation_cache

    enable_compilation_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        pytest.fail("the TPU tier needs the chip; JAX found "
                    f"{sorted({d.platform for d in devices})}")
    return devices[0]
