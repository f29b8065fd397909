"""The control of each kind of cell comes out not correct: the plain
reference put in the program's place, one precision step below the
configuration's (float8 e4m3 matrix-product operands and cache, e5m2
gradients in training), judged by the harness's own comparison
(`run_cell(..., control=True)`, as `bench/run.py --control 1`).  Test
cells at toy widths on the CPU; bench/control.py reads the same on the
chip at each cell's own size."""

import pytest

from bench.tests import _tiny


@pytest.mark.parametrize("cell,seed", [("tiny.chat", 21), ("tiny.train", 22)])
def test_control_is_not_correct(cell, seed):
    sound = _tiny.run_cell(cell, seed)
    control = _tiny.run_cell(cell, seed, control=True)
    assert sound["correct"], sound["checked"]
    assert not control["correct"], control["checked"]
