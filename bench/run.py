#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the program's training step for the cell (weights from the
seed, made on the device), compiles it, and drives it through its first
three steps; then steps run back to back for ``--seconds``.  With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` (a run of its own, the profiler on over a few steps) its
per-layer metrics.  Every run checks the first three steps against the
plain float32 reference and prints each number compared beside its
limit, as the last lines of standard error and under ``checks`` in the
result, which is the last line of standard output.

Without a TPU, or with fewer chips than the cell needs, it exits non-zero
and prints no result.
"""

from __future__ import annotations

import os
import time


def _process_age() -> float:
    """Seconds since this process started (Linux), so that set-up counts
    the interpreter's start and the imports too."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_START = time.perf_counter() - _process_age()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default="",
                    help="with --trace 1: also write the reduced trace "
                         "(gzipped JSON) here")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    from bench.lib import harness
    try:
        res = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START, root=ROOT, log=log,
                          save_trace=args.save_trace or None)
    except harness.NoChip as e:
        log(f"error: {e}")
        return 3
    for k, v in res["checks"].items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
