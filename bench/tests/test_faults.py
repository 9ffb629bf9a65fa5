"""A run with the timed path broken underneath must report
``correct: false``, and for the fault's own sake: some number that a sound
run at the same size keeps under its limit goes over it.  Each test skips
the harness's look for a chip and drives the rest of a run on the CPU
(``run.execute``) at a size a test can hold (``small.py``), with one
planted fault (``faults.py``)."""
import functools

import jax
import pytest

import faults
import run as bench_run
import small

CASES = [
    ("toy-n4-k2", "frozen_state"),
    ("toy-n4-k2", "half_batch"),
    ("toy-n4-k2", "no_exchange"),
]


@functools.lru_cache(maxsize=None)
def sound(workload):
    line, numbers = bench_run.execute(small.spec(workload), 7, 1.0, False,
                                      jax)
    return line, numbers


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_makes_the_run_incorrect(workload, fault):
    s = small.spec(workload)
    limits = s.cell["limits"]
    _, ok = sound(workload)
    with faults.FAULTS[fault]():
        line, numbers = bench_run.execute(s, 7, 1.0, False, jax)
    assert not line["correct"], numbers
    caught = [k for k in limits
              if numbers[k] > limits[k] and ok[k] <= limits[k]]
    assert caught or line["failed"], (numbers, ok)
