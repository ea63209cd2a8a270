"""Fixtures of the benchmark's CPU tests (helpers in smoke_cells.py)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def smoke_cell():
    from bench.registry import load_cell
    from smoke_cells import shrink

    return lambda name: shrink(load_cell(name))
