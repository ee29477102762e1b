"""The benchmark is driven by its files: every cell names files that
exist, and a new cell, configuration, traffic mix and per-layer metric
are added by adding files and entries, with no edit to code."""

import json
import os
import pathlib
import shutil
import sys
import time

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from bench.lib import harness, spec  # noqa: E402

ROOT = spec.ROOT


def test_every_cell_names_files_that_exist():
    bench = spec.benchmark()
    names = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        cfg = spec.config(c["name"])
        assert (ROOT / c["file"]).is_file()
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert (spec.BENCH / "configs" / f"{c['name']}.py").is_file()
    for w in bench["workloads"]:
        f = spec.workload(w["name"])
        assert f["config"] == w["config"] in names
        assert f["traffic"] == w["traffic"]
        assert f["chips"] == w["chips"]
        spec.traffic(w["traffic"])
        assert set(f["limits"]) == {"loss_gap", "grad_gap", "grad_err", "delta_gap"}
        for kind in ("end_to_end", "per_layer"):
            for m in spec.metrics_of(bench, kind, w["name"]):
                if kind == "per_layer":
                    assert callable(spec.metric(m["name"]).read)
    for m in bench["per_layer"]:
        for cell in m.get("workloads", []):
            spec.cell_entry(bench, cell)
    for f in sorted((spec.BENCH / "workloads").glob("*.json")):
        w = spec.workload(f.stem)
        spec.config(w["config"])
        spec.traffic(w["traffic"])
        assert callable(spec.reference(w["config"]).row_loss)


def test_reference_layout_is_the_programs(monkeypatch):
    harness.use_program(ROOT)
    for c in spec.benchmark()["configs"]:
        cfg = spec.config(c["name"])
        arch = harness.program_arch(cfg)
        from repro.models.model import Model
        from repro.models.params import MeshInfo
        import jax
        from repro.models.params import Pv
        leaves, _ = jax.tree_util.tree_flatten_with_path(
            Model(arch, MeshInfo()).structs(),
            is_leaf=lambda x: isinstance(x, Pv))
        have = {harness._path(p): (tuple(l.v.shape), str(l.v.dtype))
                for p, l in leaves}
        want = {e[0]: (tuple(e[1]), e[2])
                for e in spec.reference(c["name"]).layout(cfg)}
        assert have == want


TINY = {
    "name": "tiny-dense", "source": "https://example.org/tiny",
    "family": "dense", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "hidden_act": "relu2", "num_hidden_layers": 2, "vocab_size": 512,
    "rope_theta": 10000.0, "norm_eps": 1e-06, "tie_word_embeddings": False,
    "plan": [{"kind": "attn", "n": 2}], "reduced": [], "assumed": [],
    "program": {"arch": "minitron-4b",
                "cut": {"n_layers": 2, "vocab_size": 512, "d_model": 64,
                        "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
                        "d_ff": 128, "groups": []}}}


def _copy_benchmark(tmp_path) -> pathlib.Path:
    """The benchmark's files and the program, in a checkout of their own."""
    shutil.copytree(spec.BENCH, tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    os.symlink(ROOT / "src", tmp_path / "src")
    return tmp_path / "bench"


def _add_cell(b, cell, config, traffic):
    """Files and entries of a new cell ``cell`` that runs configuration
    ``config`` (a dict) under ``traffic`` (a dict), with minitron-4b's
    reference and the minitron cell's mesh, optimizer and limits."""
    (b / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    shutil.copy(b / "configs" / "minitron-4b.py",
                b / "configs" / f"{config['name']}.py")
    name = f"seq{traffic['seq']}.batch{traffic['global_batch']}"
    (b / "traffic" / f"{name}.json").write_text(json.dumps(
        {"kind": "train_synthetic", "noise": 0.1, **traffic}))
    w = json.loads((b / "workloads" / "minitron4b.1chip.opt8.json")
                   .read_text())
    w.update(config=config["name"], traffic=name)
    (b / "workloads" / f"{cell}.json").write_text(json.dumps(w))
    bench = json.loads((b.parent / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config["name"],
                             "source": config["source"],
                             "file": f"bench/configs/{config['name']}.json",
                             "reduced": config.get("reduced", []),
                             "why": "test"})
    bench["workloads"].append({"name": cell, "config": config["name"],
                               "traffic": name, "chips": 1, "why": "test"})
    (b.parent / "BENCHMARK.json").write_text(json.dumps(bench))


def _cpu_trace(trace_dir):
    """The CPU profiler's trace in the form ``trace.load`` gives a TPU's:
    the operations that the CPU client's threads run, named by their
    instruction, as one device's, and the benchmark's host spans."""
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(trace_dir).glob(
        "plugins/profile/*/*.xplane.pb"))[-1]
    ops, host = [], []
    for plane in ProfileData.from_file(str(path)).planes:
        for ln in plane.lines:
            for e in ln.events:
                span = (e.name, e.start_ns, e.end_ns)
                if e.name.startswith("bench."):
                    host.append(span)
                elif ln.name.startswith("tf_XLAPjRtCpuClient") and not \
                        e.name.startswith(("ThreadpoolListener", "end: ")):
                    ops.append(span)
    return {"devices": [ops], "host": sorted(host, key=lambda e: e[1]),
            "lines": {}}


def test_a_cells_tiny_version_is_added_by_its_file(tmp_path):
    """One file in ``tests/bench/tiny`` gives a new cell to the control,
    fault and scope tests (``CELLS``) and its configuration to the
    reference test (``REFERENCE``)."""
    _add_cell(_copy_benchmark(tmp_path), "tiny.1chip", TINY,
              {"seq": 64, "global_batch": 2})
    tiny = tmp_path / "tiny"
    shutil.copytree(bench_tiny.TINY, tiny)
    small = {"cell": "tiny.1chip", "c": {"intermediate_size": 64},
             "traffic": {"seq": 32}, "reference": {"seq": 16}}
    (tiny / "tiny.1chip.json").write_text(json.dumps(small))
    cells, ref = bench_tiny.load(tiny, tmp_path)
    assert cells == {**bench_tiny.CELLS, "tiny.1chip": {
        "c": small["c"], "traffic": small["traffic"]}}
    assert ref == {**bench_tiny.REFERENCE, "tiny-dense": (
        "tiny.1chip", {"c": small["c"], "traffic": small["reference"]})}
    # a file named after neither a cell nor its configuration is refused
    (tiny / "stray.json").write_text(json.dumps(small))
    with pytest.raises(ValueError, match="stray"):
        bench_tiny.load(tiny, tmp_path)


# two readers a later PR could add as files: they read the facts' scopes
READERS = {
    "attention_ms_per_step": """def read(f):
    row = f["scopes"]["scopes"].get("attention")
    if row is None or not f["steps"]:
        return None
    return 1e3 * row["s"] / f["steps"]
""",
    "unscoped_ms_per_step": """def read(f):
    if not f["steps"]:
        return None
    return 1e3 * f["scopes"]["unscoped_s"] / f["steps"]
"""}


def test_a_cell_is_added_by_files_alone(tmp_path, monkeypatch):
    """Copy the benchmark, add a configuration, a traffic mix, a cell and
    per-layer metrics as files plus entries, and run the new cell,
    untraced and traced.

    The CPU's profiler does not trace the operations inside the layers'
    loops, so that a traced run here finds no layer's scope: the scope
    table that the traced run puts in the facts is read by a new metric,
    and the same reduction, given one interval for each operation of the
    compiled step, finds attention and the MLP in every pass."""
    b = _copy_benchmark(tmp_path)
    _add_cell(b, "tiny.1chip", TINY, {"seq": 64, "global_batch": 2})
    (b / "metrics" / "steps_traced.py").write_text(
        "def read(f):\n    return float(f['steps'])\n")
    for name, text in READERS.items():
        (b / "metrics" / f"{name}.py").write_text(text)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for name, unit, better, source in (
            ("steps_traced", "steps", "higher", "host_clock"),
            ("attention_ms_per_step", "ms", "lower", "device_trace"),
            ("unscoped_ms_per_step", "ms", "lower", "device_trace")):
        bench["per_layer"].append({"name": name, "unit": unit,
                                   "better": better, "source": source,
                                   "layer": "device", "moves": "tokens_per_s",
                                   "workloads": ["tiny.1chip"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    got = spec.benchmark(tmp_path)
    per_layer = [m["name"] for m in
                 spec.metrics_of(got, "per_layer", "tiny.1chip")]
    assert {"steps_traced", *READERS} <= set(per_layer)
    assert spec.metric("steps_traced", b).read({"steps": 3}) == 3.0
    # per layer: q and o 2*64*64 each, k and v 2*64*32 each, causal
    # attention 2*2*4*16 * (64+1)/2, relu2 MLP 2 * 2*64*128; a head of
    # 2*64*512; backward twice the forward
    layer = (2 * 2 * 64 * 64 + 2 * 2 * 64 * 32 + 2 * 2 * 4 * 16 * 65 / 2
             + 2 * 2 * 64 * 128)
    assert harness.Cell("tiny.1chip", b).flops_per_token == \
        3 * (2 * layer + 2 * 64 * 512)
    res = harness.run("tiny.1chip", 2 ** 33 + 5, 0.5, False,
                      time.perf_counter(), root=tmp_path, require_tpu=False,
                      log=lambda s: None)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"

    # traced: the CPU's trace read as a device's, the chip's peaks
    from bench.lib import peaks, scopes, trace
    monkeypatch.setattr(trace, "load", _cpu_trace)
    monkeypatch.setattr(peaks, "peak", lambda kind: peaks.PEAKS["TPU v5 lite"])
    facts, hlo = {}, []
    traced = harness._traced

    def keep(prog, compiled, *args):
        facts.update(traced(prog, compiled, *args))
        hlo.append(compiled.as_text())
        return facts
    monkeypatch.setattr(harness, "_traced", keep)
    res = harness.run("tiny.1chip", 2 ** 33 + 6, 0.5, True,
                      time.perf_counter(), root=tmp_path, require_tpu=False,
                      log=lambda s: None)
    assert res["correct"] is True
    assert facts["scopes"]["unscoped_s"] > 0
    assert res["metrics"]["unscoped_ms_per_step"]["value"] == \
        1e3 * facts["scopes"]["unscoped_s"] / facts["steps"]
    assert res["metrics"]["steps_traced"]["value"] == facts["steps"] > 0

    # the join alone: each operation of the compiled step runs for 1 ms
    smap = scopes.scope_map(hlo[0])
    ops = [(name, i * 10 ** 6, (i + 1) * 10 ** 6)
           for i, name in enumerate(sorted(smap))]
    compact = {"devices": [ops], "lines": {},
               "host": [("bench.input", 0, 1), ("bench.wait", 1, ops[-1][2])]}
    joined = harness.reduce_trace(compact, smap)
    table = joined["scopes"]["scopes"]
    for s in ("attention", "mlp"):
        assert all(table[s][p] > 0 for p in ("fwd", "bwd", "recompute")), s
    assert spec.metric("attention_ms_per_step", b).read(
        {**joined, "steps": 1}) == 1e3 * table["attention"]["s"]


def test_expert_and_latent_attention_layers_are_counted_from_files(tmp_path):
    """A configuration of Moonlight-16B-A3B's layer kinds (latent
    attention, a dense layer, expert layers holding 8 of 64 experts) loads
    as a cell and is counted with no edit to code; an unknown layer kind
    fails as the cell loads, before anything is compiled."""
    b = _copy_benchmark(tmp_path)
    moon = json.loads((pathlib.Path(__file__).resolve().parent / "data" /
                       "moonlight-16b-a3b.json").read_text())
    _add_cell(b, "moonlight.1chip", moon, {"seq": 8192, "global_batch": 1})
    assert harness.Cell("moonlight.1chip", b).flops_per_token == \
        2_635_032_576
    odd = dict(moon, name="odd", plan=[{"kind": "attn", "n": 1},
                                       {"kind": "mlstm", "n": 1}])
    _add_cell(b, "odd.1chip", odd, {"seq": 8192, "global_batch": 1})
    with pytest.raises(ValueError, match="'odd'.*'mlstm'"):
        harness.Cell("odd.1chip", b)
