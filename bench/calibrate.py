#!/usr/bin/env python3
"""Readings that set a cell's limits: the program against the reference on
many seeds, and the control and planted faults against the reference.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--out readings.json]

One process, on the chip: the cell's step is built and compiled once, and
for each seed the set-up of a run is repeated (weights from the seed,
the first three steps through the step's own call and feed), the
program's state freed, and the plain reference run over the same
batches.  For each control seed the reference is run twice more and each
is compared with the float32 reference as the program would be:

    control      the reference computed in float8 (e4m3), the precision
                 below the configuration's bfloat16 (``bench/lib/nn.py``)
    half_batch   half of the batch left out, the mean over the rest

A step that returns its state unchanged reads 1 on ``grad_gap``,
``grad_err`` and ``delta_gap`` by construction and needs no run.
Nothing here is run by ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def _ints(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from bench.lib import harness, reference
    harness.use_program(ROOT)
    cell = harness.Cell(args.workload)
    harness.require_chip(cell.chips)
    harness.use_compile_cache()
    out = {"workload": args.workload, "device": harness.device_info(),
           "program": {}, "control": {}}
    t0 = time.perf_counter()
    prog = harness.Program(cell)
    compiled = None
    for seed in args.seeds:
        corpus = harness.corpus_for(cell, seed)
        state = prog.init_state(seed)
        if compiled is None:
            compiled, _, _ = prog.compile(state, prog.feed(corpus, 0))
        state, readings = harness.first_steps(prog, compiled, state, corpus,
                                              seed)
        del state
        ref = reference.train(cell.ref, cell.c, cell.w["opt"], seed,
                              reference.batches_np(corpus))
        nums = reference.compare(readings, ref)
        out["program"][seed] = nums
        print(f"program seed {seed}: " + _fmt(nums), flush=True)
    del compiled, prog
    rows = cell.traffic["global_batch"]
    for seed in args.control_seeds:
        corpus = harness.corpus_for(cell, seed)
        b = reference.batches_np(corpus)
        ref = reference.train(cell.ref, cell.c, cell.w["opt"], seed, b)
        runs = {"control": dict(prec="fp8"),
                "half_batch": dict(grad_rows=range(rows // 2),
                                   loss_rows=range(rows // 2))}
        res = {}
        for name, kw in runs.items():
            other = reference.train(cell.ref, cell.c, cell.w["opt"], seed,
                                    b, **kw)
            res[name] = reference.compare(other, ref)
            print(f"{name} seed {seed}: " + _fmt(res[name]), flush=True)
        out["control"][seed] = res
    out["seconds"] = time.perf_counter() - t0
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


def _fmt(nums: dict) -> str:
    return ", ".join(f"{k} {nums[k]!r}" for k in
                     ("loss_gap", "grad_gap", "delta_gap", "grad_err")) \
        + f" (loss per step {nums['loss_gaps']}; worst leaves " \
        f"{nums['grad_leaf']}, {nums['delta_leaf']}, {nums['err_leaf']})"


if __name__ == "__main__":
    sys.exit(main())
