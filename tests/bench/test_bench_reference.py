"""Each configuration's plain reference computes what the program
computes: with the weights and activations held in float32 on both sides,
the program's first three training steps (loss, first gradient, change
of the weights) agree with the reference's to float32 rounding, at a tiny
size on the CPU.  In the configured bfloat16 they differ by rounding."""

import copy
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from bench.lib import harness, reference  # noqa: E402

harness.use_program(harness.spec.ROOT)

CASES = bench_tiny.REFERENCE


def _numbers(config, dtype, monkeypatch):
    cell_name, overrides = copy.deepcopy(CASES[config])
    overrides["c"]["program"]["cut"]["dtype"] = dtype
    cell = harness.Cell(cell_name, overrides=overrides)
    cell.w["opt"] = dict(cell.w["opt"], state_bits=32)
    if dtype == "float32":
        layout = cell.ref.layout
        monkeypatch.setattr(cell.ref, "layout", lambda cfg: [
            (p, s, "float32", i, sc) for p, s, _, i, sc in layout(cfg)])
    prog = harness.Program(cell)
    seed = 2 ** 32 + 7
    corpus = harness.corpus_for(cell, seed)
    state = prog.init_state(seed)
    step, _, _ = prog.compile(state, prog.feed(corpus, 0))
    _, readings = harness.first_steps(prog, step, state, corpus, seed)
    ref = reference.train(cell.ref, cell.c, cell.w["opt"], seed,
                          reference.batches_np(corpus))
    return reference.compare(readings, ref)


@pytest.mark.parametrize("config", sorted(CASES))
def test_float32_program_matches_the_reference(config, monkeypatch):
    n = _numbers(config, "float32", monkeypatch)
    assert max(n["loss_gaps"]) < 1e-6, n
    assert n["grad_err"] < 1e-4 and n["delta_gap"] < 1e-4, n
