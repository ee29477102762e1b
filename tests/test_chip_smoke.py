"""chip_smoke.py on the CPU.

Without a TPU the script must fail before any phase and print no result.
Its phases themselves are rehearsed here at a reduced size on CPU devices,
with the Pallas kernels in interpret mode: one device for the one-chip
phase, four host devices for the dp=2 x tp=2 phase.  Each run is a
subprocess of its own, because the host device count is fixed when JAX
starts.
"""

import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

_REHEARSAL = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
import chip_smoke
from repro import configs
from repro.kernels import ops
ops.set_default_backend("pallas_interpret")
cfg = configs.get(chip_smoke.ARCH).reduced()
chip_smoke.{call}
print("REHEARSAL PASSED")
"""


def _run(argv, cwd, devices=1):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable] + argv, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("alone", [False, True])
@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_refuses_to_run_without_a_tpu(tmp_path, argv, alone):
    script = ROOT / "chip_smoke.py"
    if alone:            # a directory with the script and nothing else
        script = pathlib.Path(shutil.copy(script, tmp_path))
    proc = _run([str(script)] + argv, cwd=tmp_path)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("devices,call", [
    (1, "one_chip(cfg, batch=4, seq=64, kernel_rows=256, "
        "kernel_backend='pallas_interpret')"),
    (4, "four_chips(cfg, batch=8, seq=64, steps=16)")])
def test_phases_pass_at_reduced_size(tmp_path, devices, call):
    code = _REHEARSAL.format(root=str(ROOT), src=str(ROOT / "src"),
                             call=call)
    proc = _run(["-c", code], cwd=tmp_path, devices=devices)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "REHEARSAL PASSED" in proc.stdout
    assert "values differ" not in proc.stdout


@pytest.fixture
def smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(str(ROOT))


# losses of the untrained model on each batch: the batches differ by far
# more than training moves the loss
CONTROL = [11.0, 10.9, 11.2, 10.8, 11.1, 10.7, 11.3, 10.9, 11.0, 11.2,
           10.8, 11.1, 10.9, 11.3, 10.7, 11.0, 11.2, 10.8, 11.1, 10.9]


def _trained(gains):
    return [c - g for c, g in zip(CONTROL, gains)]


@pytest.mark.parametrize("gains,learned", [
    ([0.0] * 20, False),                    # no update: the control itself
    ([0.01 * i for i in range(20)], True),
    ([0.2, -0.2] * 10, False),              # noise around no gain
    ([0.0] * 10 + [0.15, 0.1, 0.2, 0.18, 0.12] * 2, True)])
def test_training_is_judged_against_the_lr_0_control(smoke, gains, learned):
    if learned:
        assert smoke.check_learned("run", _trained(gains), CONTROL) > 0
    else:
        with pytest.raises(smoke.SmokeError):
            smoke.check_learned("run", _trained(gains), CONTROL)


@pytest.mark.parametrize("share,kept", [(1.3, True), (0.5, True),
                                        (0.45, False), (0.0, False)])
def test_compressed_run_keeps_half_the_baseline_gain(smoke, share, kept):
    gains = [0.0] * 10 + [0.15, 0.1, 0.2, 0.18, 0.12] * 2
    base = smoke.check_learned("baseline", _trained(gains), CONTROL)
    run = _trained([share * g for g in gains])
    if kept:
        smoke.check_kept("run", run, CONTROL, base)
    else:
        with pytest.raises(smoke.SmokeError):
            smoke.check_kept("run", run, CONTROL, base)
