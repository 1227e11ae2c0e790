"""A run whose timed path is broken underneath comes out not correct:
each fault is planted in the engine of a tiny mamba2_130m.chat run on the
CPU, and the rest of the run is the harness's own."""
import pytest

from bench.tests.tiny import FAULTS, run_tiny


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(fault):
    res = run_tiny("mamba2_130m.chat", patch=FAULTS[fault])
    assert res["correct"] is False
    assert list(res)[-1] == "checks"
