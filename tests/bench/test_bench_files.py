"""The benchmark is driven by its files: every cell names files that
exist, and a new cell, configuration, traffic mix and per-layer metric
are added by adding files and entries, with no edit to code."""

import json
import os
import pathlib
import shutil
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

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


def test_a_cell_is_added_by_files_alone(tmp_path):
    """Copy the benchmark, add a configuration, a traffic mix, a cell and
    a per-layer metric as files plus entries, and run the new cell."""
    shutil.copytree(spec.BENCH, tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    os.symlink(ROOT / "src", tmp_path / "src")
    b = tmp_path / "bench"
    (b / "configs" / "tiny-dense.json").write_text(json.dumps(TINY))
    shutil.copy(b / "configs" / "minitron-4b.py",
                b / "configs" / "tiny-dense.py")
    (b / "traffic" / "seq64.batch2.json").write_text(json.dumps(
        {"kind": "train_synthetic", "seq": 64, "global_batch": 2,
         "noise": 0.1}))
    cell = json.loads((b / "workloads" / "minitron4b.1chip.opt8.json")
                      .read_text())
    cell.update(config="tiny-dense", traffic="seq64.batch2")
    (b / "workloads" / "tiny.1chip.json").write_text(json.dumps(cell))
    (b / "metrics" / "steps_traced.py").write_text(
        "def read(f):\n    return float(f['steps'])\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-dense", "source": TINY["source"],
                             "file": "bench/configs/tiny-dense.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.1chip", "config": "tiny-dense",
                               "traffic": "seq64.batch2", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "steps_traced", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "device", "moves": "tokens_per_s",
                               "workloads": ["tiny.1chip"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    got = spec.benchmark(tmp_path)
    per_layer = [m["name"] for m in
                 spec.metrics_of(got, "per_layer", "tiny.1chip")]
    assert "steps_traced" in per_layer
    assert spec.metric("steps_traced", b).read({"steps": 3}) == 3.0
    res = harness.run("tiny.1chip", 2 ** 33 + 5, 0.5, False,
                      time.perf_counter(), root=tmp_path, require_tpu=False,
                      log=lambda s: None)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
