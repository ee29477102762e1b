"""What the program tells an observer about itself: the names of its layers
in the compiled step, host spans on the profiler's clock, and a clock of
the time spent compiling.

Scopes.  Each layer enters ``jax.named_scope(<name>)`` where it is
defined, so the training, prefill and decode paths all carry the name in
the ``op_name`` metadata of every operation it lowers to, through
``lax.scan``, ``jax.checkpoint`` and the transpose (forward paths read
``.../attention/...``, backward ``transpose(jvp(...))/.../attention/...``,
recomputation ``.../rematted_computation/attention/...``).  A scope
changes only metadata: instruction names, and so the kernels a trace
shows, stay as they were.  The benchmark reads these names from the
compiled step (``bench/lib/scopes.py``): renaming one is a benchmark
change.

Spans.  ``span(name)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``: host work on the profiler's clock, beside the device's
operations.  Off the profiler it costs about a microsecond.

Compile clock.  From the first import of this module, the process's
tracing, lowering, backend compiles and compile-cache loads are added up
from ``jax.monitoring``.  Events nest (an inner jit traces inside its
caller's trace, a cache load lies inside its backend compile), so each
second is counted once, under the innermost kind: ``snapshot()``'s parts
sum to ``total_s``, the union of all the intervals, which is never more
than the wall time they lie in.
"""

from __future__ import annotations

import time

import jax
from jax import monitoring

# -- scope names (a contract with the benchmark) -----------------------------
ATTENTION = "attention"
MLP = "mlp"
NORM = "norm"
MOE = "moe"
SSM = "ssm"
XLSTM = "xlstm"
EMBED = "embed"
HEAD = "head"             # logits and the cross-entropy
OPTIMIZER = "optimizer"
# entered inside OPTIMIZER: their paths read optimizer/clip, ...
CLIP = "clip"
STATE_CODEC = "state_codec"
UPDATE = "update"
COMMS = "comms."          # see comms_scope


def comms_scope(ledger_tag: str) -> str:
    """The scope around every collective of a site: ``comms.dp:zero1_grad``
    for ledger tag ``dp@zero1_grad`` (an ``@`` would cut the name short
    in the op_name metadata)."""
    return COMMS + ledger_tag.replace("@", ":")


# -- host spans -------------------------------------------------------------
BATCH = "batch"
SYNC = "sync"
CHECKPOINT = "checkpoint"
TUNE = "tune"


def span(name: str):
    """Host span ``repro.<name>`` on the profiler's clock."""
    return jax.profiler.TraceAnnotation("repro." + name)


# -- compile clock ----------------------------------------------------------
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
# innermost first: a second held by several kinds counts under the first
_KINDS = ((_CACHE_LOAD, "cache_load_s"), (_COMPILE, "compile_s"),
          (_LOWER, "lower_s"), (_TRACE, "trace_s"))


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum((e - s for s, e in intervals), 0.0)


class _CompileClock:
    def __init__(self):
        self.spans = {event: [] for event, _ in _KINDS}

    def listen(self):
        monitoring.register_event_time_span_listener(self._span)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _span(self, event, start, end, **kw):
        if event in (_TRACE, _LOWER, _COMPILE):
            self.spans[event].append((start, end))

    def _duration(self, event, secs, **kw):
        # a cache load reports its duration alone, when it has ended
        if event == _CACHE_LOAD:
            end = time.time()
            self.spans[event].append((end - secs, end))

    def snapshot(self) -> dict:
        out, held = {}, []
        for event, key in _KINDS:
            both = _union(held + self.spans[event])
            out[key] = _length(both) - _length(_union(held))
            held = held + self.spans[event]
        out["total_s"] = _length(_union(held))
        # a backend compile event also wraps each executable loaded from
        # the cache: ``compiles`` counts both
        out["compiles"] = len(self.spans[_COMPILE])
        out["cache_loads"] = len(self.spans[_CACHE_LOAD])
        return out


_CLOCK = _CompileClock()
_CLOCK.listen()


def snapshot() -> dict:
    """Seconds of tracing, lowering, backend compiling and cache loading
    since this module was imported (``trace_s``, ``lower_s``,
    ``compile_s``, ``cache_load_s``, each second once; ``total_s`` their
    sum), and the counts of executables obtained (``compiles``) and of
    those loaded from the compile cache (``cache_loads``)."""
    return _CLOCK.snapshot()


def compile_count() -> int:
    """Executables obtained so far, compiled or loaded from the cache."""
    return len(_CLOCK.spans[_COMPILE])


def split(before: dict, after: dict) -> str:
    """The compile clock between two snapshots, as one line."""
    d = {k: after[k] - before[k] for k in after}
    return (f"{d['total_s']:.3f}s (trace {d['trace_s']:.3f}, lower "
            f"{d['lower_s']:.3f}, compile {d['compile_s']:.3f}, cache load "
            f"{d['cache_load_s']:.3f}; {d['compiles']} executables, "
            f"{d['cache_loads']} from the cache)")
