"""The control: the plain reference at fp8 matmul precision put in the
program's place reads far above the program itself (at tiny size on
the CPU; the chip readings that set the limits are in PERF.md)."""
import pytest

from bench import calibrate
from bench.tests.tiny import tiny_spec


@pytest.mark.parametrize("workload,name", [
    ("dit_xl2.stagger", "latent_rel_l2"),
    ("mamba2_130m.chat", "token_gap"),
])
def test_control_reads_far_above_the_program(workload, name):
    spec = tiny_spec()
    row, = calibrate.main(["--workload", workload, "--seeds", "7",
                           "--control-seeds", "7", "--seconds", "1"],
                          spec=spec, require=False)
    program, control = row["program"][name], row["control"][name]
    assert control >= 3 * program, (program, control)
    assert row["program"]["counter_mismatches"] == 0
