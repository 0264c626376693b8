"""Tests of the chip benchmark's own code, on the CPU.

Run by path (the repository's ``testpaths`` is ``tests/``):

  JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
for sub in ("", "metrics", "traffic", "tests"):
    sys.path.insert(0, str(CHIP / sub))


@pytest.fixture(autouse=True)
def _private_compile_cache(tmp_path, monkeypatch):
    """Keep CPU programs out of the checkout's compile cache."""
    import harness
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "jax_cache")
