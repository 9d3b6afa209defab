"""The comparison fails when it should: the control (the reference with a
non-MDS layout in the codec's place) and every fault a cell can have,
planted under a tiny CPU run, each make ``correct`` false."""

import time

import pytest

import plants
from lib import harness, spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
CASES = [(name, fault) for name in CELLS
         for fault in ["control"] + plants.faults_for(
             spec.load_cell(name).traffic)]


@pytest.mark.parametrize("name,fault", CASES)
def test_planted_fault_is_not_correct(name, fault, monkeypatch,
                                      no_compile_cache):
    plant = getattr(plants, fault)
    result = harness.run_cell(
        name, 11, 0.5, False, t_process=time.perf_counter(),
        require_tpu=False, overrides=plants.tiny(spec.load_cell(name)),
        plant=lambda cluster: plant(cluster, monkeypatch.setattr))
    assert result["correct"] is False
    broken = [k for k, v in result["checks"].items()
              if v["value"] > v["limit"]]
    assert broken
