"""Tiny versions of the benchmark's configurations, for CPU tests: the
same program paths and references at widths a test run can hold.

Each is a file ``tests/bench/tiny/<name>.json``:

    cell        the cell file (``bench/workloads``) that runs it
    w, c        overrides of that cell file and of its configuration
    traffic     overrides of its traffic, for the cell's own tests
    reference   the traffic of the float32 reference test

A file named after a cell of ``BENCHMARK.json`` is that cell's tiny
version: the control, fault and scope tests run it (``CELLS``).  Any
other file is named after the configuration it shrinks.  Each
configuration's first file gives the reference test its case
(``REFERENCE``).  A later cell gets these tests by adding its file.
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib import spec  # noqa: E402

TINY = pathlib.Path(__file__).resolve().parent / "tiny"
_OVERRIDES = ("w", "c", "traffic")


def load(tiny=TINY, root=ROOT):
    """(``CELLS``, ``REFERENCE``) from the files in ``tiny``, for the
    benchmark at ``root``."""
    cells = {w["name"] for w in spec.benchmark(root)["workloads"]}
    by_cell, by_config = {}, {}
    for path in sorted(pathlib.Path(tiny).glob("*.json")):
        t = spec.load_json(path)
        config = t.get("w", {}).get("config") \
            or spec.workload(t["cell"], root / "bench")["config"]
        if path.stem in cells:
            if t["cell"] != path.stem:
                raise ValueError(f"{path} shrinks cell {path.stem!r} but "
                                 f"names {t['cell']!r}")
            by_cell[path.stem] = {k: t[k] for k in _OVERRIDES if k in t}
        elif path.stem != config:
            raise ValueError(f"{path} is named after no cell of "
                             f"BENCHMARK.json, nor after its configuration "
                             f"{config!r}")
        if config not in by_config:
            by_config[config] = (t["cell"], {
                **{k: t[k] for k in ("w", "c") if k in t},
                "traffic": t["reference"]})
    return by_cell, by_config


# cell of BENCHMARK.json -> overrides of its files;
# configuration -> (cell file that runs it, overrides at the reference size)
CELLS, REFERENCE = load()
