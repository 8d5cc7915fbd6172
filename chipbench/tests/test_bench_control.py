"""The control -- the plain reference computed in bfloat16, the precision
below the float32 the configurations state, put in the scheduler's place
-- reads not correct, while the scheduler's own answers read correct."""
import pytest

import control
from benchcase import small_cell


@pytest.mark.parametrize("name", ["stream.poisson_0.9", "bound.paper_s1"])
def test_control_fails_a_number(name):
    cell = small_cell(name)
    r = control.readings(cell, 5, 0.01)
    limits = cell.config["limits"]
    assert all(v <= limits[k] for k, v in r["sound"].items()), r
    assert any(v > limits[k] for k, v in r["control"].items()), r
