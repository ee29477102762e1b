"""Substrate units: data pipeline determinism, checkpoint atomicity/restore,
fault monitoring — the pieces the fault-tolerance story depends on."""

import json
import pathlib
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import obs
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.models.params import Pv
from repro.train import checkpoint, fault


def test_data_determinism_and_resume():
    d1 = SyntheticCorpus(DataConfig(vocab_size=97, seq_len=16, global_batch=4))
    d2 = SyntheticCorpus(DataConfig(vocab_size=97, seq_len=16, global_batch=4))
    for step in (0, 7, 12345):
        b1, b2 = d1.batch(step), d2.batch(step)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        np.testing.assert_array_equal(b1["labels"], b2["labels"])
    # labels are next-token shifted inputs
    b = d1.batch(3)
    # teacher structure: most next tokens follow the affine map
    nxt = (d1.a * b["tokens"].astype(np.int64) + d1.b) % 97
    frac = (b["labels"] == nxt).mean()
    assert frac > 0.8, frac


def test_data_host_slicing():
    d = SyntheticCorpus(DataConfig(vocab_size=97, seq_len=8, global_batch=8))
    full = d.batch(5)
    half = d.batch(5, host_slice=slice(4, 8))
    np.testing.assert_array_equal(full["tokens"][4:8], half["tokens"])


def test_optimal_xent_bounds():
    d = SyntheticCorpus(DataConfig(vocab_size=128, seq_len=8, global_batch=2,
                                   noise=0.1))
    floor = d.optimal_xent()
    assert 0.0 < floor < np.log(128)


def test_checkpoint_roundtrip_and_latest(tmp_path):
    tree = {"a": Pv(jnp.arange(6.0).reshape(2, 3), (None, "model")),
            "b": jnp.ones((4,), jnp.int32)}
    checkpoint.save(tmp_path, 3, tree, extra={"note": "x"})
    checkpoint.save(tmp_path, 7, tree)
    assert checkpoint.latest_step(tmp_path) == 7
    like = {"a": Pv(jax.ShapeDtypeStruct((2, 3), jnp.float32),
                    (None, "model")),
            "b": jax.ShapeDtypeStruct((4,), jnp.int32)}
    restored, man = checkpoint.restore(tmp_path, like, step=3)
    assert man["extra"]["note"] == "x"
    np.testing.assert_array_equal(np.asarray(restored["a"].v),
                                  np.arange(6.0).reshape(2, 3))
    assert restored["a"].spec == (None, "model")


def test_checkpoint_async_and_atomic(tmp_path):
    tree = {"w": Pv(jnp.zeros((8,)), (None,))}
    t = checkpoint.save(tmp_path, 1, tree, blocking=False)
    t.join(timeout=30)
    assert checkpoint.latest_step(tmp_path) == 1
    # no stray tmp dirs after completion (atomic rename)
    assert not list(pathlib.Path(tmp_path).glob("*.tmp"))


def test_checkpoint_leaf_count_mismatch(tmp_path):
    tree = {"w": Pv(jnp.zeros((8,)), (None,))}
    checkpoint.save(tmp_path, 1, tree)
    bad = {"w": Pv(jax.ShapeDtypeStruct((8,), jnp.float32), (None,)),
           "extra": jax.ShapeDtypeStruct((2,), jnp.float32)}
    with pytest.raises(AssertionError):
        checkpoint.restore(tmp_path, bad)


def test_step_monitor_straggler_and_heartbeat(tmp_path):
    hb = tmp_path / "hb.json"
    mon = fault.StepMonitor(heartbeat_path=str(hb), straggler_factor=2.0,
                            ema_decay=0.0)
    mon.begin()
    time.sleep(0.01)
    info = mon.end(0)
    assert not info["straggler"]
    compiles = json.loads(hb.read_text())["compiles"]
    mon.begin()
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(3)).block_until_ready()
    time.sleep(0.06)  # > 2x the 10ms EMA
    info = mon.end(1)
    assert info["straggler"]
    assert mon.stragglers == 1
    data = json.loads(hb.read_text())
    assert data["step"] == 1
    # the step that compiled shows in the heartbeat
    assert compiles < data["compiles"] == obs.compile_count()
    assert not fault.heartbeat_stale(hb, timeout_s=60)
    assert fault.heartbeat_stale(tmp_path / "missing.json", 1)


def test_restart_policy(tmp_path):
    pol = fault.RestartPolicy(str(tmp_path), max_restarts=2)
    assert pol.should_restart()
    assert pol.on_failure() is None          # no checkpoint yet
    tree = {"w": Pv(jnp.zeros((4,)), (None,))}
    checkpoint.save(tmp_path, 9, tree)
    assert pol.on_failure() == 9
    assert not pol.should_restart()          # budget exhausted
