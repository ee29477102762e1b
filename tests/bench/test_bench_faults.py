"""A run whose timed path is broken underneath comes out not correct.

Each test skips only the harness's look for a chip and drives the rest of
a run of a one-chip cell, at a tiny size on the CPU, with one fault
planted in the program: a step that returns its state unchanged, half of
the batch left out with the mean taken over the rest, and the step's loss
altered where it is produced.  (The one-chip cells have no exchange
between chips to leave out.)  A sound run of the same cell is correct."""

import pathlib
import sys
import time

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from bench.lib import harness  # noqa: E402

harness.use_program(harness.spec.ROOT)


def _run(cell):
    return harness.run(cell, 3 ** 25, 0.3, False, time.perf_counter(),
                       require_tpu=False, overrides=bench_tiny.CELLS[cell],
                       log=lambda s: None)


def _frozen(monkeypatch):
    from repro.train import optimizer

    def apply(self, params, grads, state):
        import jax.numpy as jnp
        return params, state, {"grad_norm": jnp.float32(0.0),
                               "lr": jnp.float32(0.0)}
    monkeypatch.setattr(optimizer.Adam, "apply", apply)


def _half_batch(monkeypatch):
    from repro.models import model
    orig = model.Model.loss_fn

    def loss_fn(self, params, batch):
        half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return orig(self, params, half)
    monkeypatch.setattr(model.Model, "loss_fn", loss_fn)


def _loss_altered(monkeypatch):
    from repro.models import model
    orig = model.Model.loss_fn

    def loss_fn(self, params, batch):
        loss, metrics = orig(self, params, batch)
        return loss * 1.01, metrics
    monkeypatch.setattr(model.Model, "loss_fn", loss_fn)


FAULTS = {"frozen": _frozen, "half_batch": _half_batch,
          "loss_altered": _loss_altered}


@pytest.mark.parametrize("cell", sorted(bench_tiny.CELLS))
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", sorted(bench_tiny.CELLS))
def test_planted_fault_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = _run(cell)
    assert res["correct"] is False, res["checks"]
