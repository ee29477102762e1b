"""Without a TPU the benchmark exits non-zero and prints no result; so it
does in a checkout that holds only the benchmark's own files."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "minitron4b.1chip.opt8", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _result_lines(out: str) -> list:
    lines = []
    for line in out.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metrics" in obj:
            lines.append(obj)
    return lines


def _run(cwd: pathlib.Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert _result_lines(p.stdout) == []


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    bench = ROOT / "BENCHMARK.json"
    for d in json.loads(bench.read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d)
    shutil.copy(bench, tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _result_lines(p.stdout) == []
