"""Hardware-gated tests: run ONLY on a real TPU. The main suite under
tests/ forces an 8-device CPU mesh; this directory is the on-chip
complement — Pallas kernels compiled by Mosaic, calibration
microbenchmarks, sim-vs-real validation (reference analog: the CI legs
that needed real GPUs, .circleci/config.yml / tests/multi_gpu_tests.sh).

Run: `python -m pytest tests_tpu/ -q` from the repo root on a machine
with a chip (one process per chip; `python chip_smoke.py` first).
Everything skips off-TPU.
"""

import pytest


import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def pytest_collection_modifyitems(config, items):
    # session-scoped hook: only gate items that live in THIS directory
    # (a mixed `pytest tests/ tests_tpu/` run must not skip tests/).
    # fspath exists across pytest versions; the trailing separator stops
    # a sibling tests_tpu_* dir from matching.
    prefix = _HERE + os.path.sep
    ours = [it for it in items if str(it.fspath).startswith(prefix)]
    if not ours:
        return
    import jax
    try:
        on_tpu = jax.default_backend() == "tpu"
    except Exception:
        on_tpu = False
    if not on_tpu:
        skip = pytest.mark.skip(reason="requires a real TPU backend")
        for item in ours:
            item.add_marker(skip)
