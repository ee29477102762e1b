"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
only what the reduction needs: per device the operations on its "XLA Ops"
line (name, start, end in ns), and the benchmark's own host spans
(``bench.input``, ``bench.dispatch``, ``bench.wait``).  ``reduce`` works
on that compact form, which is also what ``bench/testdata`` holds.

The traced window runs from the start of the first ``bench.input`` span to
the end of the last ``bench.wait`` span.  Device time is clipped to it.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
# by the operation's opcode or name: all-reduce, all-gather,
# reduce-scatter, collective-permute, all-to-all, with their -start /
# -done halves (an async pair can carry the collective in its name alone)
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter"
                        r"|collective-permute|all-to-all)([-.]|$)")
# by the operation's name: on the chip each bq Pallas kernel is a
# tpu_custom_call named after its wrapper (bq_encode_pallas,
# bq_decode_pallas, bq_decode_add_encode_pallas, bq_decode_add_pallas,
# bq_gather_decode_pallas), the kernel bodies (_encode_kernel, ...) inside
CODEC = re.compile(r"^bq_\w*pallas(\.\d+)?$")
NAME_CHARS = 200        # of an operation's HLO text, in the breakdown


def op_name(text: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def opcode(text: str) -> str:
    """``%x = (f32[2], s8[2]) all-reduce-start(...)`` -> ``all-reduce-start``
    (the operation itself, not the names of its operands)."""
    if " = " not in text:
        return text
    rest = text.split(" = ", 1)[1]
    if rest.startswith("("):                     # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return rest.strip().split("(", 1)[0]


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, host, lines = {}, [], {}
    for plane in pd.planes:
        lines[plane.name] = [ln.name for ln in plane.lines]
        m = _DEVICE.match(plane.name)
        if m:
            ops = []
            for ln in plane.lines:
                if ln.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.end_ns)
                            for e in ln.events]
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in ln.events
                         if e.name.startswith("bench.")]
    if not devices:
        raise ValueError(f"the trace holds no TPU device plane: {lines}")
    return {"devices": [devices[k] for k in sorted(devices)],
            "host": sorted(host, key=lambda e: e[1]), "lines": lines}


def save(compact: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(compact, f)


def read_saved(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def union(intervals) -> list:
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of union ``a`` not covered by union ``b`` (both merged)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi) -> list:
    """Idle intervals of the window [lo, hi] around merged ``busy``."""
    return subtract([(lo, hi)], busy)


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------

def window(compact: dict) -> tuple:
    host = compact["host"]
    starts = [s for n, s, _ in host if n == "bench.input"]
    ends = [e for n, _, e in host if n == "bench.wait"]
    if not starts or not ends:
        raise ValueError("the trace holds no bench.input / bench.wait spans")
    return min(starts), max(ends)


def _label(lo, hi, host) -> str:
    best, name = 0.0, "none"
    for n, s, e in host:
        ov = min(e, hi) - max(s, lo)
        if ov > best:
            best, name = ov, n
    return name


def reduce(compact: dict, top: int = 10) -> dict:
    lo, hi = window(compact)
    win = (hi - lo) * 1e-9
    n_dev = len(compact["devices"])
    busy, exposed, codec, per_op = [], [], [], {}
    all_gaps = []
    for d, ops in enumerate(compact["devices"]):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
               if e > lo and s < hi]
        b = union((s, e) for _, s, e in ops)
        busy.append(length(b) * 1e-9)
        is_coll = [bool(COLLECTIVE.match(opcode(n))
                        or COLLECTIVE.match(op_name(n))) for n, _, _ in ops]
        coll = union((s, e) for (_, s, e), c in zip(ops, is_coll) if c)
        other = union((s, e) for (_, s, e), c in zip(ops, is_coll)
                      if not c)
        exposed.append(length(subtract(coll, other)) * 1e-9)
        codec.append(sum(e - s for n, s, e in ops
                         if CODEC.match(op_name(n))) * 1e-9)
        for n, s, e in ops:
            n = n[:NAME_CHARS]
            per_op[n] = per_op.get(n, 0.0) + (e - s) * 1e-9 / n_dev
        if d == 0:
            all_gaps = [(_label(s, e, compact["host"]), (e - s) * 1e-9)
                        for s, e in gaps(b, lo, hi)]
    host_in = sum(e - s for n, s, e in compact["host"]
                  if n == "bench.input" and s >= lo and e <= hi) * 1e-9
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(all_gaps, key=lambda kv: -kv[1])[:top]
    mean = lambda xs: sum(xs) / len(xs)                      # noqa: E731
    return {"window_s": win, "busy_s": mean(busy), "busy_per_device": busy,
            "collective_exposed_s": mean(exposed), "codec_s": mean(codec),
            "input_s": host_in,
            "breakdown": {"device_ops": [[n, v] for n, v in ops_top],
                          "idle_gaps": [[n, v] for n, v in gaps_top]}}
