"""Shared helpers of the benchmark's CPU tests: the repo root on the
path (the ``bench`` package lives there), and any cell of
``BENCHMARK.json`` cut to a size a test run holds."""
import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def shrink(cell):
    """The cell at n = 512, tile 128, on the jax backend (the Pallas
    kernel only interprets on a CPU); mixes, limits and metrics as
    committed."""
    cfg = dict(cell.config, n=512, tile=128,
               runtime=dict(cell.config["runtime"], backend="jax"))
    return dataclasses.replace(cell, config=cfg)


@pytest.fixture
def small_cell():
    from bench.cell import find_cell

    return lambda name: shrink(find_cell(name))
