"""One run of one cell: build the program's training step, drive it from
the seed through its first steps, measure a window, check the result.

The program is driven the way its launcher (``repro.launch.train.run``)
builds it: ``make_mesh``, ``Model``, ``make_trainer`` under the cell's
communication policy, batches placed with ``batch_specs`` /
``zigzag_shard_seq`` / ``device_put``, and the jitted ``Trainer.step``
compiled ahead of time.  What the benchmark takes from the program is that
step, its compile-time ledger of wire bytes, and the state it returns.
Traffic, weights, counts and the reference are the benchmark's own.
"""

from __future__ import annotations

import argparse
import collections
import math
import pathlib
import shutil
import sys
import tempfile
import time

from bench.lib import data, flops, reference, scopes, spec, trace, weights

# ArchConfig field each sizes key of a configuration file must match
_FIELDS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim_",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "num_hidden_layers": "n_layers", "rope_theta": "rope_theta",
           "norm_eps": "norm_eps", "hidden_act": "mlp_kind",
           "tie_word_embeddings": "tie_embeddings",
           "mamba_d_state": "ssm_state", "mamba_headdim": "ssm_head_dim",
           "mamba_expand": "ssm_expand", "mamba_d_conv": "conv_kernel"}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def use_program(root: pathlib.Path) -> None:
    """Put the program's sources (``<checkout>/src``) on ``sys.path``."""
    src = root / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"no program at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def require_chip(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {len(devs)} {devs[0].platform} "
                     "device(s); this benchmark measures only on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")


def use_compile_cache() -> None:
    """The program's fixed cache directory inside the checkout (or
    ``JAX_COMPILATION_CACHE_DIR``), with every program cached, the small
    init and placement ones too."""
    import jax
    from repro.launch import runtime
    runtime.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Cell:
    """The files of one cell, read by name."""

    def __init__(self, name: str, bench_dir=spec.BENCH, overrides=None):
        # tests shrink a cell: ``overrides`` updates the cell file ("w"),
        # then the files it names ("traffic", "c")
        overrides = overrides or {}
        self.name = name
        self.w = spec.workload(name, bench_dir)
        self.w.update(overrides.get("w", {}))
        self.traffic = spec.traffic(self.w["traffic"], bench_dir)
        self.traffic.update(overrides.get("traffic", {}))
        self.c = spec.config(self.w["config"], bench_dir)
        self.c.update(overrides.get("c", {}))
        self.ref = spec.reference(self.w["config"], bench_dir)
        self.chips = self.w["chips"]
        self.tokens_per_step = self.traffic["global_batch"] \
            * self.traffic["seq"]
        # counted here, so that a configuration the count cannot read
        # fails before anything is compiled
        self.flops_per_token = flops.train_per_token(self.c,
                                                     self.traffic["seq"])


def program_arch(c: dict):
    """The program's ArchConfig for configuration file ``c``: the arch
    named there, cut as it says, and checked against every size the file
    states."""
    from repro import configs
    from repro.models.config import BlockGroup
    p = c["program"]
    cut = dict(p["cut"])
    groups = tuple(BlockGroup(g["kind"], g["n"], g.get("window", 0))
                   for g in cut.pop("groups"))
    arch = configs.get(p["arch"]).replace(groups=groups, **cut)
    for key, field in _FIELDS.items():
        if key in c and getattr(arch, field) != c[key]:
            raise ValueError(f"the program's {field} is "
                             f"{getattr(arch, field)!r}, the configuration "
                             f"states {key}={c[key]!r}")
    return arch


class Program:
    """The system under test, built for one cell: mesh, model, trainer,
    the compiled step and the batch feed."""

    def __init__(self, cell: Cell):
        import jax
        from jax.sharding import NamedSharding
        from repro.launch import train as launch
        from repro.launch.mesh import make_mesh
        from repro.models.model import Model
        from repro.models.params import MeshInfo
        from repro.train.optimizer import AdamConfig
        from repro.train.train_step import (batch_specs, make_trainer,
                                            zigzag_shard_seq)
        w = cell.w
        self.cell = cell
        self.arch = program_arch(cell.c)
        self.mesh = make_mesh(w["mesh"]["dp"], w["mesh"]["tp"])
        self.mi = MeshInfo.from_mesh(self.mesh)
        self.model = Model(self.arch, self.mi)
        flags = argparse.Namespace(scheme=w["scheme"],
                                   no_compress_below=w["no_compress_below"],
                                   codec_for=list(w["codec_for"]))
        self.trainer = make_trainer(
            self.model, self.mesh, scheme=launch.comm_policy_from_flags(flags),
            opt_cfg=AdamConfig(**w["opt"]))
        self.bspecs = batch_specs(self.arch, self.mi)
        self._zigzag = zigzag_shard_seq
        self._named = lambda sp: NamedSharding(self.mesh, sp)
        self.layout = cell.ref.layout(cell.c)
        self._check_layout()
        self.jax = jax

    # -- weights and feed ----------------------------------------------
    def _leaves(self):
        import jax
        from repro.models.params import Pv
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.model.structs(), is_leaf=lambda x: isinstance(x, Pv))
        return [(_path(p), l) for p, l in flat], treedef

    def _check_layout(self):
        want = {e[0]: (tuple(e[1]), e[2]) for e in self.layout}
        have = {p: (tuple(l.v.shape), str(l.v.dtype))
                for p, l in self._leaves()[0]}
        if want != have:
            diff = sorted(set(want.items()) ^ set(have.items()))
            raise ValueError("the program's parameters are not laid out as "
                             f"the reference declares: {diff[:6]}")

    def init_state(self, seed: int):
        """Weights from the seed (one jitted call, placed as the program
        keeps them), then the program's own optimizer and codec state."""
        params = self.params_from(seed)
        return params, self.trainer.opt_init(params), \
            self.trainer.init_codec_state()

    def params_from(self, seed: int):
        """The program's parameter tree, filled from the seed."""
        import jax
        from repro.models.params import Pv
        leaves, treedef = self._leaves()
        specs = [l.v for l in jax.tree_util.tree_leaves(
            self.model.specs(), is_leaf=lambda x: isinstance(x, Pv))]
        arrs = weights.make(seed, self.layout,
                            {p: self._named(s)
                             for (p, _), s in zip(leaves, specs)})
        return jax.tree_util.tree_unflatten(
            treedef, [Pv(arrs[p], l.spec) for p, l in leaves])

    def feed(self, corpus: data.Corpus, step: int) -> dict:
        b = self._zigzag(corpus.batch_at(step), self.mi.cp)
        return {k: self.jax.device_put(v, self._named(self.bspecs[k]))
                for k, v in b.items()}

    def compile(self, state, batch):
        """AOT-compile the step; returns (compiled, wire bytes per device
        per step by dimension, compiled memory in bytes)."""
        from repro.analysis import roofline
        from repro.core import comms
        params, ostate, cstate = state
        with comms.record_traffic() as events:
            compiled = self.trainer.step.lower(params, ostate, cstate,
                                               batch).compile()
        wire = roofline.ledger_summary(events, train=True)["per_dim"]
        mem = compiled.memory_analysis()
        # elements of one device's flat ZeRO-1 optimizer chunk
        m = ostate["master"]
        self.flat_len = m.shape[0] // self.mesh.devices.size
        self.events = list(events)
        return compiled, wire, mem

    # -- reading the optimizer state ------------------------------------
    def flat_leaves(self):
        """(path, local shape, class, offset, size, model dim) of each
        leaf in the optimizer's flat ZeRO-1 vector: leaves in the
        parameter tree's order, each rank's local shard, class B sharded
        over the model axis, class C replicated."""
        out, off = [], 0
        tp = self.mi.tp
        for path, l in self._leaves()[0]:
            sp = l.spec
            if "data" in sp:
                raise ValueError(f"{path} is ZeRO-3 sharded; not read here")
            dim = sp.index("model") if "model" in sp else None
            shape = tuple(s // tp if i == dim else s
                          for i, s in enumerate(l.v.shape))
            size = math.prod(shape)
            out.append((path, shape, "B" if dim is not None else "C", off,
                        size, dim))
            off += size
        return out

    def _flat2d(self, s):
        """The optimizer's flat state leaf as [tp, per-rank vector]."""
        import jax.numpy as jnp
        tp = self.mi.tp
        if isinstance(s, dict):                        # 8-bit blocks
            q = s["q_hi"].astype(jnp.float32)
            x = q * (s["scale"] * jnp.float32(1.0 / 127.0))
        else:
            x = s
        return x.reshape(tp, -1)

    def grad_reader(self):
        """Jitted: optimizer state after one step -> the gradient it took
        (m = (1 - b1) g after one step), as [tp, per-rank vector]."""
        import jax
        b1 = self.cell.w["opt"]["b1"]
        return jax.jit(lambda ostate: self._flat2d(ostate["m"]) / (1.0 - b1))

    def leaf_arrays(self, g2d) -> dict:
        """Host copy of a flat [tp, per-rank vector] as {path: array in the
        leaf's global shape}."""
        import numpy as np
        g2d = np.asarray(g2d)
        out = {}
        for path, shape, cls, off, size, dim in self.flat_leaves():
            if cls == "C":
                out[path] = g2d[0, off:off + size].reshape(shape)
            else:
                out[path] = np.concatenate(
                    [g2d[r, off:off + size].reshape(shape)
                     for r in range(g2d.shape[0])], axis=dim)
        return out

    def delta_reader(self):
        """Jitted: (optimizer state, initial params) -> per-leaf norms of
        the master's change."""
        import jax
        import jax.numpy as jnp
        from repro.models.params import Pv
        leaves = self.flat_leaves()
        tp = self.mi.tp

        def read(ostate, params0):
            master = self._flat2d(ostate["master"])
            p0 = [l.v for l in jax.tree_util.tree_leaves(
                params0, is_leaf=lambda x: isinstance(x, Pv))]
            rows = []
            for e, p in zip(leaves, p0):
                _, shape, cls, off, size, dim = e
                ranks = range(tp) if cls == "B" else range(1)
                sq = 0.0
                for r in ranks:
                    loc = p if dim is None else jax.lax.slice_in_dim(
                        p, r * shape[dim], (r + 1) * shape[dim], axis=dim)
                    d = master[r, off:off + size] \
                        - loc.reshape(-1).astype(jnp.float32)
                    sq = sq + jnp.sum(d * d)
                rows.append(sq)
            return jnp.stack(rows) ** 0.5
        return jax.jit(read), [e[0] for e in leaves]


def _path(keys) -> str:
    out = []
    for k in keys:
        out.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(out)


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

class CompileCounter:
    """Counts compilations (backend compiles and cache loads) while on."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, name, secs, **kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _ev(self, name, **kw):
        if self.on and name == "/jax/compilation_cache/cache_hits":
            self.n += 1


def first_steps(prog: Program, compiled, state, corpus, seed: int):
    """Steps 0..2 through the window's own call and feed; returns the
    state after them and the program's readings (losses, first gradient
    and master change per leaf)."""
    import numpy as np
    gread = prog.grad_reader()
    dread, names = prog.delta_reader()
    params, ostate, cstate = state
    losses = []
    g2d = None
    for step in range(3):
        params, ostate, cstate, met = compiled(params, ostate, cstate,
                                               prog.feed(corpus, step))
        losses.append(met["loss"])
        if step == 0:
            g2d = gread(ostate)
    params0 = prog.params_from(seed)
    dnorm = dread(ostate, params0)
    del params0
    grad = prog.leaf_arrays(g2d)
    del g2d
    readings = {"loss": [float(x) for x in losses],
                "grad_vec": grad,
                "grad": {p: float(np.linalg.norm(v.ravel()))
                         for p, v in grad.items()},
                "delta": dict(zip(names, map(float, dnorm)))}
    return (params, ostate, cstate), readings


def window(prog: Program, compiled, state, corpus, seconds: float,
           counter: CompileCounter, first_step: int = 3, max_steps=None):
    """Dispatch steps back to back, at most two ahead of the device, until
    ``seconds`` have passed; the window ends when the last step is done.
    Returns (state, losses on the device, steps, window seconds,
    input seconds per step)."""
    import jax
    from jax.profiler import TraceAnnotation
    params, ostate, cstate = state
    losses, inflight = [], collections.deque()
    t_in = 0.0
    counter.on = True
    t0 = time.perf_counter()
    step = first_step
    while True:
        a = time.perf_counter()
        with TraceAnnotation("bench.input"):
            batch = prog.feed(corpus, step)
        t_in += time.perf_counter() - a
        with TraceAnnotation("bench.dispatch"):
            params, ostate, cstate, met = compiled(params, ostate, cstate,
                                                   batch)
        losses.append(met["loss"])
        inflight.append(met["loss"])
        step += 1
        if len(inflight) > 2:
            with TraceAnnotation("bench.wait"):
                inflight.popleft().block_until_ready()
        n = step - first_step
        if (max_steps is not None and n >= max_steps) or (
                max_steps is None and time.perf_counter() - t0 >= seconds):
            break
    with TraceAnnotation("bench.wait"):
        jax.block_until_ready((params, ostate, cstate, met))
    t1 = time.perf_counter()
    counter.on = False
    n = step - first_step
    return (params, ostate, cstate), losses, n, t1 - t0, t_in / n


def memory_peak() -> int:
    import jax
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in jax.local_devices() if d.memory_stats()]
    return int(max(peaks)) if peaks else 0


def device_info() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def check(cell: Cell, readings: dict, seed: int) -> tuple:
    """Run the plain reference over the same three batches from the
    seed; returns (correct, numbers, limits)."""
    corpus = corpus_for(cell, seed)
    ref = reference.train(cell.ref, cell.c, cell.w["opt"], seed,
                          reference.batches_np(corpus))
    numbers = reference.compare(readings, ref)
    limits = cell.w["limits"]
    return reference.verdict(numbers, limits), numbers, limits


def corpus_for(cell: Cell, seed: int) -> data.Corpus:
    t = cell.traffic
    return data.Corpus(cell.c["vocab_size"], t["seq"], t["global_batch"],
                       seed, t["noise"])


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, root: pathlib.Path = spec.ROOT,
        require_tpu: bool = True, overrides=None, log=print,
        save_trace=None) -> dict:
    """One run of one cell; returns the result line's object."""
    use_program(root)
    bench = spec.benchmark(root)
    spec.cell_entry(bench, cell_name)
    cell = Cell(cell_name, root / "bench", overrides)
    import jax
    if require_tpu:
        require_chip(cell.chips)
        use_compile_cache()
    counter = CompileCounter()
    dev = device_info()
    log(f"device: {dev['platform']} {dev['kind']} x{dev['count']}")

    prog = Program(cell)
    corpus = corpus_for(cell, seed)
    state = prog.init_state(seed)
    compiled, wire, mem = prog.compile(state, prog.feed(corpus, 0))
    log("compiled step memory per device: arguments "
        f"{mem.argument_size_in_bytes}, outputs {mem.output_size_in_bytes},"
        f" aliased {mem.alias_size_in_bytes}, temporaries "
        f"{mem.temp_size_in_bytes} bytes")
    log("ledger wire bytes per device per step: " + (", ".join(
        f"{k}={v:.0f}" for k, v in sorted(wire.items())) or "none"))
    state, readings = first_steps(prog, compiled, state, corpus, seed)
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t_start

    facts = None
    if trace:
        # a few untraced steps, then the traced ones, each after the
        # device has drained
        state, _, _, _, _ = window(prog, compiled, state, corpus, 0, counter,
                                   first_step=3, max_steps=2)
        facts = _traced(prog, compiled, state, corpus, counter, cell, mem,
                        wire, save_trace, log)
        state = facts.pop("_state")
        n_steps, losses = facts["steps"], facts.pop("_losses")
    else:
        state, losses, n_steps, win_s, in_s = window(
            prog, compiled, state, corpus, seconds, counter)
    log(f"compilations inside the window: {counter.n}")
    losses = [float(x) for x in losses]
    failed = sum(1 for x in losses if not math.isfinite(x))
    peak = memory_peak()
    log(f"device memory peak_bytes_in_use (fullest chip): {peak}")
    del state, compiled, prog

    t_ref = time.perf_counter()
    ok, numbers, limits = check(cell, readings, seed)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    result = {"correct": bool(ok and failed == 0), "attempted": n_steps,
              "failed": failed}
    metrics = {}
    if trace:
        for m in spec.metrics_of(bench, "per_layer", cell_name):
            val = spec.metric(m["name"], root / "bench").read(facts)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        e2e = {"tokens_per_s": n_steps * cell.tokens_per_step / win_s,
               "setup_s": setup_s}
        for m in spec.metrics_of(bench, "end_to_end", cell_name):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        log(f"window: {n_steps} steps in {win_s:.4f} s; input "
            f"{in_s * 1e3:.3f} ms a step on the host")
    result["metrics"] = metrics
    result["device"] = {**dev, "memory_peak_bytes": peak}
    if trace:
        result["device"]["busy_s"] = facts["busy_s"]
        result["device"]["window_s"] = facts["window_s"]
        result["breakdown"] = facts["breakdown"]
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in limits}
    log(f"loss gap per step {numbers['loss_gaps']} (the third is not "
        f"compared); worst leaves: gradient norm {numbers['grad_leaf']}, "
        f"gradient {numbers['err_leaf']}, change {numbers['delta_leaf']}; "
        f"left out of the change: {numbers['left_out']}")
    return result


def _traced(prog, compiled, state, corpus, counter, cell, mem, wire,
            save_trace, log):
    """The traced steps: profiler on, ``trace_steps`` steps back to back,
    reduced to the facts the per-layer metrics read."""
    import jax
    from bench.lib import codec_bytes, peaks
    k = cell.w["trace_steps"]
    out_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(out_dir)
        try:
            state, losses, n, _, _ = window(
                prog, compiled, state, corpus, 0, counter, first_step=5,
                max_steps=k)
        finally:
            jax.profiler.stop_trace()
        compact = trace.load(out_dir)
        log(f"trace planes and lines: {compact['lines']}")
        if save_trace:
            trace.save(compact, save_trace)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    kind = jax.devices()[0].device_kind
    pk = peaks.peak(kind)
    facts = reduce_trace(compact, scopes.scope_map(compiled.as_text()))
    facts.update({
        "_state": state, "_losses": losses, "steps": n,
        "chips": cell.chips, "peak": pk,
        "tokens_per_step": cell.tokens_per_step,
        "flops_per_token": cell.flops_per_token,
        "wire_bytes_per_step": sum(wire.values()),
        "step_bytes": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                       - mem.alias_size_in_bytes + mem.temp_size_in_bytes),
        "codec_bytes_per_step": codec_bytes.per_step(
            prog.events, cell.w["opt"]["state_bits"], prog.flat_len),
    })
    return facts


def reduce_trace(compact: dict, smap: dict) -> dict:
    """The facts a traced window gives: the trace's reduction
    (``trace.reduce``) and the device time of each named scope
    (``scopes.reduce``, ``smap`` from the compiled step's text), both over
    all the traced steps."""
    red = trace.reduce(compact)
    return {"busy_s": red["busy_s"], "window_s": red["window_s"],
            "breakdown": red["breakdown"], "trace": red,
            "scopes": scopes.reduce(compact, smap)}
