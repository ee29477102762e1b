"""The training launcher as a library: ``run(args, cfg)`` in-process on one
CPU device, its step timer, the 8-bit optimizer state, and the process
set-up in ``repro.launch.runtime`` (host devices, compile cache)."""

import json
import math
import os
import pathlib

import pytest

import jax

from repro.launch import runtime, train


def _args(*extra):
    return train.parse_args(["--arch", "minitron-4b", "--reduced",
                             "--seq", "32", "--global-batch", "2",
                             *extra])


def test_run_returns_history_timed_at_the_device(monkeypatch):
    from repro.train import fault
    order = []
    real_block, real_end = jax.block_until_ready, fault.StepMonitor.end

    def block(x):
        order.append("block")
        return real_block(x)

    def end(self, step):
        order.append("end")
        return real_end(self, step)

    monkeypatch.setattr(jax, "block_until_ready", block)
    monkeypatch.setattr(fault.StepMonitor, "end", end)
    hist = train.run(_args("--steps", "3"))
    # every step's timer closes only after its outputs are ready
    assert order == ["block", "end"] * 3
    assert len(hist["loss"]) == len(hist["grad_norm"]) == 3
    assert len(hist["step_time"]) == 3
    assert all(t > 0 for t in hist["step_time"]) and hist["compile_time"] > 0
    assert all(math.isfinite(v) for v in hist["loss"] + hist["grad_norm"])
    assert set(hist["wire_bytes"]) <= {"dp", "tp", "zero"}
    assert jax.tree.leaves(hist["params"]) and hist["opt_state"]


def test_opt_state_at_8_bits_tracks_32_bits(tmp_path):
    """bq8 state keeps m and sqrt(v); storing v itself rounded its small
    entries to 0 and the loss blew up (6.24 -> 115 in ten steps).  The
    optimizer checkpoint says which of the two its v holds."""
    full = train.run(_args("--steps", "20"))["loss"]
    low = train.run(_args("--steps", "20", "--opt-state-bits", "8",
                          "--ckpt-dir", str(tmp_path)))["loss"]
    assert low[-1] < low[0]
    assert max(abs(a - b) / b for a, b in zip(low, full)) < 0.01
    man = json.loads((tmp_path / "opt" / "latest" / "manifest.json")
                     .read_text())
    assert man["extra"] == {"v_layout": "sqrt_v"}


def test_malformed_codec_rule_is_a_usage_error():
    with pytest.raises(SystemExit) as e:
        train.main(["--arch", "minitron-4b", "--reduced",
                    "--codec-for", "no-codec-here"])
    assert e.value.code == 2


@pytest.mark.parametrize("platforms,asked,devices", [
    (None, False, None),      # an accelerator run keeps its own devices
    ("tpu", False, None),
    ("cpu", False, 4),
    (None, True, 4)])         # --host-devices pins the CPU
def test_host_devices_only_on_the_cpu(monkeypatch, platforms, asked,
                                      devices):
    # setenv first, so that teardown restores what the code under test
    # writes into os.environ
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setenv("JAX_PLATFORMS", platforms or "")
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS")
    runtime.force_cpu_devices(4, asked=asked)
    flags = os.environ.get("XLA_FLAGS", "")
    if devices is None:
        assert "device_count" not in flags
    else:
        assert f"--xla_force_host_platform_device_count={devices}" in flags
        assert os.environ["JAX_PLATFORMS"] == "cpu"


def test_host_devices_refused_for_an_accelerator(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(ValueError):
        runtime.force_cpu_devices(4, asked=True)


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                               str(tmp_path / env_dir))
        runtime.use_compile_cache()
        got = jax.config.jax_compilation_cache_dir
        # with the variable set, JAX reads it and the code sets nothing
        assert got == (str(runtime.CACHE_DIR) if env_dir is None else None)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    # one fixed directory, at the root of the checkout
    assert runtime.CACHE_DIR == \
        pathlib.Path(__file__).resolve().parent.parent / ".jax_cache"
