"""repro.obs: the compile clock's arithmetic and its reading of a real
compile, the comms scope names, and host spans."""

import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs


def _clock(spans):
    c = obs._CompileClock()
    for event, ivs in spans.items():
        c.spans[event] = list(ivs)
    return c


def test_clock_counts_each_second_once_under_the_innermost_kind():
    # an outer trace 0-10 holds an inner jit's trace 2-3 and, eagerly, a
    # small compile 4-5; the lowering 10-12, then the backend compile
    # 12-20 with a cache load 13-19 inside it
    s = _clock({obs._TRACE: [(0, 10), (2, 3)], obs._LOWER: [(10, 12)],
                obs._COMPILE: [(4, 5), (12, 20)],
                obs._CACHE_LOAD: [(13, 19)]}).snapshot()
    assert s["cache_load_s"] == 6
    assert s["compile_s"] == 1 + 2
    assert s["lower_s"] == 2
    assert s["trace_s"] == 10 - 1
    assert s["total_s"] == 20 == sum(
        s[k] for k in ("trace_s", "lower_s", "compile_s", "cache_load_s"))
    assert (s["compiles"], s["cache_loads"]) == (2, 1)


def test_clock_reads_a_compile_within_its_wall_time():
    @jax.jit
    def inner(x):
        return jnp.tanh(x) * 0.37

    @jax.jit
    def outer(x):
        return inner(x) + jnp.cumsum(x) * 1.93

    s0, t0 = obs.snapshot(), time.time()
    outer(jnp.arange(13.0)).block_until_ready()
    t1, s1 = time.time(), obs.snapshot()
    d = {k: s1[k] - s0[k] for k in s1}
    assert d["trace_s"] > 0 and d["lower_s"] > 0 and d["compile_s"] > 0
    assert d["total_s"] <= t1 - t0
    assert d["total_s"] == pytest.approx(
        d["trace_s"] + d["lower_s"] + d["compile_s"] + d["cache_load_s"])
    assert d["compiles"] >= 1
    assert "executables" in obs.split(s0, s1)


def test_comms_scope_keeps_the_site_name():
    assert obs.comms_scope("dp@zero1_grad") == "comms.dp:zero1_grad"
    assert obs.comms_scope("tp_bwd") == "comms.tp_bwd"


def test_span_is_a_profiler_annotation():
    with obs.span(obs.BATCH) as sp:
        assert isinstance(sp, jax.profiler.TraceAnnotation)
