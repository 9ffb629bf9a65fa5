"""The control — the plain reference in the program's place, its matrix
operands in the precision just below the configuration's — must come out
not correct: at least one of the cell's numbers over its limit.  Here at a
size a CPU test run can hold; the readings at the cells' own sizes on the
chip are in PERF.md."""
import pytest

import small


@pytest.mark.parametrize("workload", ["toy-n4-k2"])
def test_control_fails_a_limit(workload):
    s = small.spec(workload)
    limits = s.cell["limits"]
    assert limits, f"{workload} holds no limit"
    for seed in (5, 6):
        numbers = small.entry(s).control_readings(s, seed)
        over = [k for k, v in numbers.items()
                if k in limits and v > limits[k]]
        assert over, f"seed {seed}: the control passes every limit: {numbers}"
