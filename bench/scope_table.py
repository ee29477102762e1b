#!/usr/bin/env python3
"""Where one cell's step goes, scope by scope, and what tracing costs.

    python3 bench/scope_table.py --workload <cell> --seed <n> --seconds <s> \
        [--save <file.json.gz>]

One process, on the chip, built and driven as ``bench/run.py`` builds and
drives a run (no reference is run, so nothing is checked): set-up through
the first three steps, with the program's compile clock
(``repro.obs.snapshot``) read where ``setup_s`` is taken; an untraced
window of ``--seconds``; then, the profiler on, the cell's ``trace_steps``
steps.  The trace is reduced as a traced run of ``bench/run.py`` reduces
it (``harness.reduce_trace``: ``bench/lib/trace.py`` and, joined to the
compiled step's text, ``bench/lib/scopes.py``).  It prints one JSON
line: set-up and its compile split, the host's seconds a step over the
window and over as many steps as are traced with the profiler off and
then on, and the device time a step of each scope, in total
and forward, backward and recomputed, with the busy time no scope holds.
``--save`` writes the reduced trace with the scope path of each operation
in it (``scope_map``), as ``bench/testdata`` holds it.

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

from bench.run import T_START  # noqa: E402  (the process's start)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", default="")
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    from bench.lib import harness
    harness.use_program(ROOT)
    cell = harness.Cell(args.workload, ROOT / "bench")
    import jax
    harness.require_chip(cell.chips)
    harness.use_compile_cache()
    try:
        from repro import obs
    except ImportError:                       # a program without the clock
        obs = None
    counter = harness.CompileCounter()
    prog = harness.Program(cell)
    corpus = harness.corpus_for(cell, args.seed)
    state = prog.init_state(args.seed)
    compiled, _, _ = prog.compile(state, prog.feed(corpus, 0))
    state, _ = harness.first_steps(prog, compiled, state, corpus, args.seed)
    jax.block_until_ready(state)
    out = {"setup_s": time.perf_counter() - T_START,
           "compile_clock": obs.snapshot() if obs else None}

    state, _, n, win_s, _ = harness.window(prog, compiled, state, corpus,
                                           args.seconds, counter)
    out["step_s"] = win_s / n
    out["tokens_per_s"] = n * cell.tokens_per_step / win_s
    # as many steps as the traced window, from a drained device, untraced
    k, first = cell.w["trace_steps"], 3 + n
    state, _, _, win_k, _ = harness.window(prog, compiled, state, corpus, 0,
                                           counter, first_step=first,
                                           max_steps=k)
    out["untraced_step_s"] = win_k / k
    out.update(_traced(prog, compiled, state, corpus, counter, cell,
                       first + k, args.save, log))
    out["traced_step_overhead"] = \
        out["traced_step_s"] / out["untraced_step_s"] - 1
    out["compilations_in_windows"] = counter.n
    print(json.dumps(out), flush=True)
    return 0


def _traced(prog, compiled, state, corpus, counter, cell, first_step, save,
            log) -> dict:
    import shutil
    import tempfile

    import jax
    from bench.lib import harness, scopes, trace
    k = cell.w["trace_steps"]
    out_dir = tempfile.mkdtemp(prefix="bench_scope_trace_")
    try:
        jax.profiler.start_trace(out_dir)
        try:
            _, _, n, win_s, _ = harness.window(
                prog, compiled, state, corpus, 0, counter,
                first_step=first_step, max_steps=k)
        finally:
            jax.profiler.stop_trace()
        compact = trace.load(out_dir)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    smap = scopes.scope_map(compiled.as_text())
    facts = harness.reduce_trace(compact, smap)
    red, sc = facts["trace"], facts["scopes"]
    names = {trace.op_name(t) for ops in compact["devices"]
             for t, _, _ in ops}
    codec = sorted({scopes.scope_of(smap[x])[0] if x in smap else None
                    for x in names if trace.CODEC.match(x)}, key=str)
    if save:
        trace.save({**compact, "scope_map": {x: smap[x] for x in sorted(names)
                                             if x in smap}}, save)
        log(f"saved the trace and its scope map to {save}")
    per = 1e3 / n
    return {
        "traced_step_s": win_s / n, "steps": n,
        "busy_ms": red["busy_s"] * per, "window_ms": red["window_s"] * per,
        "codec_ms": red["codec_s"] * per,
        "scopes_ms": {s: {p: v * per for p, v in row.items()}
                      for s, row in sc["scopes"].items()},
        "unscoped_ms": sc["unscoped_s"] * per,
        "unscoped_share_of_busy": sc["unscoped_s"] / red["busy_s"],
        "codec_kernel_scopes": codec,
    }


if __name__ == "__main__":
    sys.exit(main())
