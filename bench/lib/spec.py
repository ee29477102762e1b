"""Finds the benchmark's data files by name.

Every configuration, traffic mix, cell and per-layer metric is a file of
its own, found from the name that ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     sizes as run, source, cut, assumptions
    bench/configs/<config>.py       the configuration's plain reference
    bench/traffic/<traffic>.json    batch, sequence and corpus parameters
    bench/workloads/<cell>.json     mesh, policy, optimizer, limits
    bench/metrics/<metric>.py       ``read(facts) -> float | None``

No list of them is written in code: a later cell or metric is added by
adding its files and its entry in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell_entry(bench: dict, cell: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == cell:
            return w
    raise KeyError(f"BENCHMARK.json has no cell {cell!r}")


def workload(cell: str, bench_dir: pathlib.Path = BENCH) -> dict:
    return load_json(bench_dir / "workloads" / f"{_checked(cell)}.json")


def traffic(name: str, bench_dir: pathlib.Path = BENCH) -> dict:
    return load_json(bench_dir / "traffic" / f"{_checked(name)}.json")


def config(name: str, bench_dir: pathlib.Path = BENCH) -> dict:
    return load_json(bench_dir / "configs" / f"{_checked(name)}.json")


def _module(path: pathlib.Path, label: str):
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config_name: str, bench_dir: pathlib.Path = BENCH):
    """The configuration's plain reference module (``layout``, ``row_loss``)."""
    path = bench_dir / "configs" / f"{_checked(config_name)}.py"
    return _module(path, "bench_ref_" + re.sub(r"\W", "_", config_name))


def metric(name: str, bench_dir: pathlib.Path = BENCH):
    """The per-layer metric's reader module (``read(facts)``)."""
    path = bench_dir / "metrics" / f"{_checked(name)}.py"
    return _module(path, "bench_metric_" + re.sub(r"\W", "_", name))


def metrics_of(bench: dict, kind: str, cell: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports:
    those that list it under ``workloads``, or list no cells at all."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]
