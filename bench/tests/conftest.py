"""Harness tests, on the CPU at small sizes: ``python -m pytest bench/tests``."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT, Path(__file__).resolve().parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchutil import make_tiny_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
