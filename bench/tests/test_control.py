"""The control of `correct`: the reference computed one precision step
below the program's ring (16-bit fixed point) and put in the program's
place must fail a limit the program meets, at a size a test run holds
(bench/rehearsal.json). The chip readings at the cells' own sizes come
from bench/readings.py."""
import pytest

from bench import harness, readings


@pytest.mark.parametrize("workload", ["bertmini2pc.probe"])
def test_control_fails_where_the_program_passes(workload):
    limits = harness.load_cell(workload, rehearse=True).limits
    rows = readings.collect(workload, [5, 6], rehearse=True)
    for row in rows:
        r = row["readings"]
        for name, limit in limits.items():
            stat = name[len("gap."):]
            assert r["program"][stat] <= limit, (name, r)
            assert r["fx16"][stat] > limit, (name, r)
