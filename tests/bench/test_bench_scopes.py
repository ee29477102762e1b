"""The program's named scopes as the benchmark reads them: the join of a
trace's operations to the compiled step's op_name paths, the reduction to
device time per scope, the scopes in compiled steps (one device, and four
under a compressed policy), the program's compile clock against the
harness's count of compilations, and a trace recorded on the chip."""

import collections
import os
import pathlib
import subprocess
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from bench.lib import harness, scopes, trace  # noqa: E402

harness.use_program(harness.spec.ROOT)

MS = 1_000_000       # ns
CELL = "minitron4b.1chip.opt8"
FWD = "jit(step_fn)/jvp()/while/body/closed_call"
BWD = "jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint"

HLO = f"""HloModule jit_step_fn

%fused_computation.1 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  %exponential.1 = f32[8]{{0}} exponential(%param_0), metadata={{op_name="{FWD}/attention/exp"}}
  ROOT %multiply.2 = f32[8]{{0}} multiply(%exponential.1, %param_0), metadata={{op_name="{FWD}/attention/mul"}}
}}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  ROOT %multiply.3 = f32[8]{{0}} multiply(%param_0.1, %param_0.1), metadata={{op_name="{BWD}/mlp/mul"}}
}}

%body.3 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %arg = (s32[], f32[8]{{0}}) parameter(0)
  %get-tuple-element.1 = f32[8]{{0}} get-tuple-element(%arg), index=1
  %fusion.1 = f32[8]{{0}} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{BWD}/rematted_computation/mlp/dot_general"}}
  ROOT %tuple.2 = (s32[], f32[8]{{0}}) tuple(%get-tuple-element.1, %fusion.2)
}}

ENTRY %main.9 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0), metadata={{op_name="params['w'].v"}}
  %while.4 = (s32[], f32[8]{{0}}) while(%p), condition=%cond.5, body=%body.3, metadata={{op_name="jit(step_fn)/jvp()/while"}}
  %fusion.6 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="jit(step_fn)/optimizer/state_codec/state_codec/jit(bq_encode_ref)/jit(clip)/max"}}
  %bq_decode_pallas.2 = f32[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="jit(step_fn)/optimizer/state_codec/jit(bq_decode_pallas)/pallas_call"}}
  ROOT %copy.7 = f32[8]{{0}} copy(%p)
}}
"""


def test_scope_map_joins_instructions_to_their_paths():
    m = scopes.scope_map(HLO)
    # a fusion without metadata of its own takes its root's path
    assert m["fusion.1"] == f"{FWD}/attention/mul"
    assert m["fusion.2"].endswith("rematted_computation/mlp/dot_general")
    assert m["bq_decode_pallas.2"].startswith("jit(step_fn)/optimizer/")
    assert "copy.7" not in m and "tuple.2" not in m


@pytest.mark.parametrize("path,want", [
    (f"{FWD}/attention/bsd,dh->bsh/dot_general", ("attention", "fwd")),
    (f"{BWD}/mlp/mul", ("mlp", "bwd")),
    (f"{BWD}/rematted_computation/norm/mul", ("norm", "recompute")),
    ("jit(step_fn)/jvp(embed)/jit(_take)/add", ("embed", "fwd")),
    ("jit(step_fn)/transpose(jvp(head))/comms.tp:xent/add",
     ("comms.tp:xent", "bwd")),
    ("jit(step_fn)/optimizer/state_codec/state_codec/jit(bq_encode_ref)"
     "/jit(clip)/max", ("optimizer/state_codec", "fwd")),
    ("jit(step_fn)/optimizer/clip/reduce_sum", ("optimizer/clip", "fwd")),
    ("jit(step_fn)/optimizer/comms.dp:zero1_grad/pad",
     ("comms.dp:zero1_grad", "fwd")),
    ("jit(step_fn)/jvp()/while/body/add", (None, "fwd")),
    ("jit(step_fn)/update/add", (None, "fwd")),
])
def test_innermost_scope_and_part_of_a_path(path, want):
    assert scopes.scope_of(path) == want


def _op(name, opcode="fusion"):
    return f"%{name} = f32[8]{{0:T(128)}} {opcode}(f32[8]{{0}} %p)"


def test_reduction_counts_leaves_once_and_no_container():
    host = [("bench.input", 0, 1 * MS), ("bench.wait", 1 * MS, 20 * MS)]
    dev0 = [(_op("fusion.6"), -5 * MS, -1 * MS),          # before the window
            (_op("while.4", "while"), 2 * MS, 12 * MS),   # holds fusion.1
            (_op("fusion.1"), 2 * MS, 5 * MS),
            (_op("fusion.1"), 4 * MS, 7 * MS),            # overlaps itself
            (_op("bq_decode_pallas.2", "custom-call"), 12 * MS, 15 * MS),
            (_op("fusion.6"), 15 * MS, 16 * MS),
            (_op("copy.7", "copy"), 16 * MS, 18 * MS),    # no scope
            (_op("copy.7", "copy"), 19 * MS, 25 * MS)]    # clipped at 20
    dev1 = [(_op("fusion.1"), 0, 4 * MS),
            (_op("multiply.3"), 5 * MS, 7 * MS),          # not in the map
            (_op("fusion.2"), 7 * MS, 8 * MS)]
    compact = {"devices": [dev0, dev1], "host": host, "lines": {}}
    smap = scopes.scope_map(HLO)
    smap["multiply.3"] = f"{BWD}/mlp/mul"
    r = scopes.reduce(compact, smap)
    t = r["scopes"]
    # device 0: attention 2..7, codec 12..16, unscoped 7..12 (the loop's
    # own time), 16..18 and 19..20; device 1: attention 0..4, mlp 5..8
    assert t["attention"]["s"] == pytest.approx((0.005 + 0.004) / 2)
    assert t["attention"]["fwd"] == pytest.approx(t["attention"]["s"])
    assert t["optimizer/state_codec"]["s"] == pytest.approx(0.004 / 2)
    assert t["mlp"] == pytest.approx({"s": 0.0015, "fwd": 0.0,
                                      "bwd": 0.001, "recompute": 0.0005})
    assert r["unscoped_s"] == pytest.approx((0.008 + 0) / 2)
    busy = trace.reduce(compact)["busy_s"]
    assert busy == pytest.approx(0.012)
    assert sum(row["s"] for row in t.values()) + r["unscoped_s"] == \
        pytest.approx(busy)


def _parts(compiled) -> dict:
    got = collections.defaultdict(set)
    for p in scopes.scope_map(compiled.as_text()).values():
        scope, part = scopes.scope_of(p)
        got[scope].add(part)
    return got


def test_every_scope_of_the_tiny_step_in_each_pass():
    cell = harness.Cell(CELL, overrides=bench_tiny.CELLS[CELL])
    prog = harness.Program(cell)
    corpus = harness.corpus_for(cell, 2 ** 31 + 11)
    state = prog.init_state(2 ** 31 + 11)
    compiled, _, _ = prog.compile(state, prog.feed(corpus, 0))
    got = _parts(compiled)
    for s in ("attention", "mlp", "norm"):             # the layers, remat
        assert got[s] == {"fwd", "bwd", "recompute"}, s
    for s in ("embed", "head"):
        assert got[s] == {"fwd", "bwd"}, s
    for s in ("optimizer", "optimizer/clip", "optimizer/state_codec",
              "optimizer/update"):
        assert got[s] == {"fwd"}, s


@pytest.mark.parametrize("arch,scope", [("qwen3-moe-235b-a22b", "moe"),
                                        ("zamba2-1.2b", "ssm"),
                                        ("xlstm-1.3b", "xlstm")])
def test_block_scopes_of_other_families(arch, scope):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.models.model import Model
    from repro.models.params import MeshInfo
    from repro.train.train_step import batch_specs, make_trainer
    cfg = configs.get(arch).reduced()
    mesh = make_mesh(1, 1)
    mi = MeshInfo.from_mesh(mesh)
    trainer = make_trainer(Model(cfg, mi), mesh)
    state = trainer.init_all(jax.random.key(0))
    rng = np.random.default_rng(0)
    sp = batch_specs(cfg, mi)
    batch = {k: jax.device_put(rng.integers(0, cfg.vocab_size, (2, 16),
                                            dtype=np.int32),
                               NamedSharding(mesh, sp[k]))
             for k in ("tokens", "labels")}
    compiled = trainer.step.lower(*state, batch).compile()
    assert _parts(compiled)[scope] == {"fwd", "bwd"}


MESH4 = """
import collections, sys
sys.path[:0] = [{root!r}, {here!r}]
import bench_tiny
from bench.lib import harness, scopes
harness.use_program(harness.spec.ROOT)
cell = harness.Cell({cell!r}, overrides={{
    **bench_tiny.CELLS[{cell!r}],
    "w": {{"mesh": {{"dp": 2, "tp": 2}}, "scheme": "zhybrid_16_8"}}}})
prog = harness.Program(cell)
corpus = harness.corpus_for(cell, 3)
state = prog.init_state(3)
compiled, _, _ = prog.compile(state, prog.feed(corpus, 0))
got = collections.defaultdict(set)
for p in scopes.scope_map(compiled.as_text()).values():
    s, part = scopes.scope_of(p)
    got[s].add(part)
for s in sorted(got, key=str):
    print(s, sorted(got[s]))
"""


def test_collectives_carry_their_site_through_the_transpose():
    """dp=2 x tp=2 on four CPU devices under the paper's hybrid: each
    collective's scope names its ledger site, in the backward pass too."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    code = MESH4.format(root=str(ROOT), here=str(ROOT / "tests" / "bench"),
                        cell=CELL)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = dict(line.split(" ", 1) for line in p.stdout.splitlines())
    # forward all-gather, backward reduce-scatter, recomputed under remat
    assert got["comms.tp:attn_in"] == "['bwd', 'fwd', 'recompute']"
    assert got["comms.tp:mlp_out"] == "['bwd', 'fwd']"
    assert got["comms.dp:zero1_grad"] == "['fwd']"


def test_compile_clock_agrees_with_the_harness_count():
    from repro import obs
    counter = harness.CompileCounter()
    counter.on = True
    s0, t0 = obs.snapshot(), time.time()
    cell = harness.Cell(CELL, overrides=bench_tiny.CELLS[CELL])
    prog = harness.Program(cell)
    corpus = harness.corpus_for(cell, 2 ** 31 + 29)
    state = prog.init_state(2 ** 31 + 29)
    compiled, _, _ = prog.compile(state, prog.feed(corpus, 0))
    harness.first_steps(prog, compiled, state, corpus, 2 ** 31 + 29)
    t1, s1 = time.time(), obs.snapshot()
    counter.on = False
    d = {k: s1[k] - s0[k] for k in s1}
    assert 0 < d["total_s"] <= t1 - t0
    assert d["compile_s"] > 0 and d["trace_s"] > 0
    # the harness counts backend compiles and cache hits
    assert counter.n == d["compiles"] + d["cache_loads"] > 0


RECORDED = (ROOT / "bench" / "testdata" /
            "minitron4b.1chip.opt8.scopes.trace.json.gz")


# device ms a step of each scope that bench/scope_table.py read on the
# chip for the recorded trace's four steps
SCOPE_TABLE_MS = {
    "attention": 178.9267, "mlp": 70.559, "norm": 1.7507, "embed": 3.3748,
    "head": 30.3239, "optimizer": 19.3668, "optimizer/clip": 1.0728,
    "optimizer/state_codec": 483.6204, "optimizer/update": 16.4743}


def test_scopes_of_a_trace_recorded_on_the_chip():
    """Four steps of minitron4b.1chip.opt8 on a TPU v5e, saved with the
    scope path of each of its operations (``bench/scope_table.py
    --save``)."""
    t = trace.read_saved(str(RECORDED))
    red = trace.reduce(t)
    r = scopes.reduce(t, t["scope_map"])
    ms = {s: 1e3 * row["s"] / 4 for s, row in r["scopes"].items()}
    assert ms == pytest.approx(SCOPE_TABLE_MS, abs=1e-3)
    att = r["scopes"]["attention"]
    assert [1e3 * att[p] / 4 for p in ("fwd", "bwd", "recompute")] == \
        pytest.approx([41.0036, 95.5214, 42.4016], abs=1e-3)
    assert 1e3 * r["unscoped_s"] / 4 == pytest.approx(32.7632, abs=1e-3)
    assert r["unscoped_s"] < 0.05 * red["busy_s"]
    # the codec found by its kernels' names and by its scope agree: every
    # kernel trace.CODEC matches lies under optimizer/state_codec, whose
    # time holds theirs
    codec = {trace.op_name(n) for n, _, _ in t["devices"][0]}
    codec = {x for x in codec if trace.CODEC.match(x)}
    assert len(codec) == 4
    assert {scopes.scope_of(t["scope_map"][x]) for x in codec} == \
        {("optimizer/state_codec", "fwd")}
    assert r["scopes"]["optimizer/state_codec"]["s"] > red["codec_s"]


def test_traced_facts_carry_the_scope_table():
    """The facts of a traced run (``harness.reduce_trace``, as
    ``harness._traced`` builds them) hold, for the recorded trace, the
    device time of each scope that ``bench/scope_table.py`` read, and the
    trace's own reduction beside it."""
    t = trace.read_saved(str(RECORDED))
    f = harness.reduce_trace(t, t["scope_map"])
    ms = {s: 1e3 * row["s"] / 4 for s, row in f["scopes"]["scopes"].items()}
    assert ms == pytest.approx(SCOPE_TABLE_MS, abs=1e-3)
    assert 1e3 * f["scopes"]["unscoped_s"] / 4 == \
        pytest.approx(32.7632, abs=1e-3)
    red = trace.reduce(t)
    assert f["trace"] == red
    assert (f["busy_s"], f["window_s"], f["breakdown"]) == \
        (red["busy_s"], red["window_s"], red["breakdown"])
