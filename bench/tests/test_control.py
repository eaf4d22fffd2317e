"""The limits of `correct` separate the program from its control: the
plain reference computed in bfloat16, put in the program's place, fails
at least one number of each cell, and the program passes them all, at
the cell's own size over a short window."""
import pytest

from bench import control, run
from bench.tests.test_cells import CELLS


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_and_program_passes(workload):
    parts = run.prepare(workload)
    limits = parts["limits"]
    lines = list(control.readings(parts, [1, 2**31 + 3], [1, 2**31 + 3],
                                  seconds=0.5, answers=200))
    for line in lines[:-1]:
        side = {k: line[k] for k in limits}
        if line["side"] == "program":
            assert all(side[k] <= limits[k] for k in limits), line
        else:
            assert any(side[k] > limits[k] for k in limits), line
