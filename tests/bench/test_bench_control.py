"""The control comes out not correct: each cell's plain reference computed
with its matrix products' operands rounded to float8, put in the
program's place and compared with the float32 reference under the cell's
own limits, at a tiny size on the CPU."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from bench.lib import harness, reference  # noqa: E402


@pytest.mark.parametrize("cell", sorted(bench_tiny.CELLS))
def test_float8_control_is_not_correct(cell):
    c = harness.Cell(cell, overrides=bench_tiny.CELLS[cell])
    seed = 2 ** 31 + 11
    batches = reference.batches_np(harness.corpus_for(c, seed))
    ref = reference.train(c.ref, c.c, c.w["opt"], seed, batches)
    control = reference.train(c.ref, c.c, c.w["opt"], seed, batches,
                              prec="fp8")
    numbers = reference.compare(control, ref)
    assert not reference.verdict(numbers, c.w["limits"]), numbers
    # the same reference against itself is correct
    assert reference.verdict(reference.compare(ref, ref), c.w["limits"])
