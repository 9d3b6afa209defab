"""CPU tests of the benchmark: JAX held to the CPU, the benchmark's own
directory and the repo root importable, the persistent compile cache off
(its CPU entries would only warn)."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent))

import pytest  # noqa: E402


@pytest.fixture
def no_compile_cache(monkeypatch):
    from lib import harness

    monkeypatch.setattr(harness, "arm_compile_cache", lambda: "off")
