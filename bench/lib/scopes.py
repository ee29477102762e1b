"""From the compiled step and a profiler trace to the device time of each
named scope of the program.

The program enters a named scope in each of its layers (``repro/obs.py``:
``attention``, ``mlp``, ``norm``, ``moe``, ``ssm``, ``xlstm``, ``embed``,
``head``, ``optimizer`` with ``optimizer/clip``, ``optimizer/state_codec``
and ``optimizer/update`` inside it, and ``comms.<site>`` around every
collective).  XLA keeps the scope path in each instruction's ``op_name``
metadata.  The trace names each operation by its HLO instruction and
carries no metadata, so ``scope_map`` reads each instruction's path from
the compiled step's text (``compiled.as_text()``) and ``reduce`` joins the
trace's operations to it.

An operation belongs to the innermost scope on its path, and to one part
of it: ``bwd`` under an autodiff transpose (``transpose(jvp(...))``),
``recompute`` under ``rematted_computation`` (``jax.checkpoint``), else
``fwd``.  A fusion without metadata of its own takes the path of the
operation nearest its root.  Control flow (``while``, ``conditional``,
``call``) holds other operations and is no leaf: its children are counted,
it is not, so no time counts twice.  Device time is clipped to the traced
window (``trace.window``), merged per scope on each device and averaged
over devices.  A program without these scopes reads every operation as
unscoped.
"""

from __future__ import annotations

import collections
import re

from bench.lib import trace

SCOPES = ("attention", "mlp", "norm", "moe", "ssm", "xlstm", "embed",
          "head", "optimizer")
OPTIMIZER_PARTS = ("clip", "state_codec", "update")
COMMS = "comms."
PARTS = ("fwd", "bwd", "recompute")
CONTAINERS = ("while", "conditional", "call")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
# a transform wrapped round a scope name: jvp(embed), transpose(jvp(head))
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def scope_map(hlo_text: str) -> dict:
    """``{instruction name: op_name path}`` of a compiled module's text,
    for every instruction that has a path of its own or calls a
    computation that has one."""
    own, calls, body, comp = {}, {}, collections.defaultdict(list), None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        o, c = _OP_NAME.search(line), _CALLS.search(line)
        own[name] = o.group(1) if o else None
        if c:
            calls[name] = c.group(1)
        if comp is not None:
            body[comp].append(name)

    def path(name, depth=0):
        if own.get(name):
            return own[name]
        if name in calls and depth < 8:
            # post-order text: the root is last in its computation
            for inner in reversed(body[calls[name]]):
                p = path(inner, depth + 1)
                if p:
                    return p
        return None

    out = {}
    for name in own:
        p = path(name)
        if p:
            out[name] = p
    return out


def scope_of(path: str) -> tuple:
    """(innermost scope or None, part) of an op_name path."""
    scope, part = None, "fwd"
    for comp in path.split("/"):
        m = _WRAPPED.match(comp)
        while m and m.group(1) not in ("jit", "pjit"):
            if m.group(1) == "transpose" and part == "fwd":
                part = "bwd"
            comp = m.group(2)
            m = _WRAPPED.match(comp)
        if comp == "rematted_computation":
            part = "recompute"
        elif comp in SCOPES or comp.startswith(COMMS):
            scope = comp
        elif comp in OPTIMIZER_PARTS and scope is not None \
                and scope.split("/")[0] == "optimizer":
            scope = "optimizer/" + comp
    return scope, part


def reduce(compact: dict, smap: dict) -> dict:
    """Seconds of device time per scope (``s``) and per part, and the busy
    time that no scope holds (``unscoped_s``), each a mean over devices."""
    lo, hi = trace.window(compact)
    n_dev = len(compact["devices"])
    sums = collections.defaultdict(float)
    unscoped = 0.0
    for ops in compact["devices"]:
        busy, held = [], collections.defaultdict(list)
        for text, s, e in ops:
            if e <= lo or s >= hi:
                continue
            iv = (max(s, lo), min(e, hi))
            busy.append(iv)
            if trace.opcode(text) in CONTAINERS:
                continue
            p = smap.get(trace.op_name(text))
            scope, part = scope_of(p) if p else (None, None)
            if scope is not None:
                held[(scope, part)].append(iv)
        by_scope = collections.defaultdict(list)
        for (scope, part), ivs in held.items():
            sums[(scope, part)] += trace.length(trace.union(ivs))
            by_scope[scope] += ivs
        for scope, ivs in by_scope.items():
            sums[(scope, "s")] += trace.length(trace.union(ivs))
        scoped = trace.union(iv for ivs in by_scope.values() for iv in ivs)
        unscoped += trace.length(trace.subtract(trace.union(busy), scoped))
    table = {}
    for (scope, key), ns in sorted(sums.items()):
        row = table.setdefault(scope, dict.fromkeys(("s",) + PARTS, 0.0))
        row[key] = ns * 1e-9 / n_dev
    return {"scopes": table, "unscoped_s": unscoped * 1e-9 / n_dev}
