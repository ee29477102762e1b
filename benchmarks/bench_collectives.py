"""Collective wire-bytes per parallelism dimension per scheme.

Paper analog: Fig 1 (communication breakdown) + the core message-size
reduction mechanism of §III.  We trace one training step of a small dense
and a small MoE model on a (2, 4) mesh and read the comms ledger: bytes per
tag (dp / tp / pp / ep / zero) under every scheme, and the reduction vs the
uncompressed baseline.

Second sweep: flat vs hierarchical collectives.  The same all-reduce
payload is traced through the flat ring (whole volume rides the slow
inter-node links at the bottleneck) and the two-level decomposition
(only the 1/n_local outer stage is inter-node), per level-aware scheme —
reporting fast/slow link bytes and the roofline collective seconds.

Third sweep (model layer): the same TP all-reduce and EP all-to-all
payloads through the flat joint-axis collective vs the hierarchical
decomposition on a tp-node-factored mesh, plus full train-step traces on
flat vs node-factored meshes with the per-dimension x level byte
breakdown (which dimension's traffic moved off the slow links)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import compat
from repro import configs
from repro.analysis import roofline as rl
from repro.core import comms, schemes
from repro.models.model import Model
from repro.models.params import MeshInfo
from repro.train.train_step import Trainer, batch_specs


def _trace_step_bytes(arch, scheme, mesh):
    mi = MeshInfo.from_mesh(mesh)
    cfg = configs.get(arch).reduced()
    model = Model(cfg, mi)
    trainer = Trainer(model, mesh, scheme=scheme)
    pstructs = model.structs()
    ostructs = jax.eval_shape(trainer.opt_init, pstructs)
    B, S = 8, 32
    binputs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
               "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    with comms.record_traffic() as events:
        trainer.step.lower(pstructs, ostructs,
                           trainer.codec_structs(), binputs)
    return rl.ledger_summary(events, train=True)


def _trace_payload_events(scheme, hier: bool, elems: int):
    """Trace one all-reduce of ``elems`` f32 per device, flat vs two-level."""
    mesh = compat.make_mesh((2, 4), ("node", "data"))
    if hier:
        fn = lambda a: comms.hier_all_reduce(a, "data", "node", "dp")  # noqa: E731
    else:
        fn = lambda a: comms.psum(a, ("node", "data"), "dp")           # noqa: E731
    sm = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(("node", "data")),),
        out_specs=P(("node", "data")), check_vma=False))
    with schemes.use(scheme), comms.record_traffic() as events:
        sm.lower(jax.ShapeDtypeStruct((8, elems), jnp.float32))
    jax.clear_caches()
    return events


def _hier_sweep(rows):
    """Flat ring vs two-level decomposition on the same DP payload."""
    elems = 1 << 20                                      # 4 MiB f32 / device
    flat_axes = ((("node", "data"),))
    base_slow = None
    for scheme, hier in (("baseline", False), ("zhybrid_16_8", False),
                         ("hier_zpp_8_16", True), ("hier_zpp_4_16", True),
                         ("hier_mzpp_8", True)):
        events = _trace_payload_events(scheme, hier, elems)
        lb = rl.link_bytes(events, train=True,
                           slow_axes=flat_axes if not hier else ())
        secs = rl.collective_seconds(events, train=True,
                                     slow_axes=flat_axes if not hier else ())
        if base_slow is None:
            base_slow = lb["slow"]
        kind = "hier" if hier else "flat"
        rows.append((f"allreduce_4MiB_{kind}_{scheme}",
                     secs * 1e6,                         # roofline us
                     f"slow={lb['slow']/1e6:.2f}MB fast={lb['fast']/1e6:.2f}MB"
                     f" slow_vs_flat_baseline={lb['slow']/max(base_slow,1):.3f}"))
    return rows


def _trace_model_payload(scheme, hier: bool, op: str, elems: int):
    """One TP all-reduce / EP all-to-all over the (joint) model axis,
    flat vs the two-level decomposition on a tp-node-factored mesh."""
    from repro.core.compat import AxisPair
    mesh = compat.make_mesh((2, 4), ("tpnode", "model"))
    axis = AxisPair("tpnode", "model") if hier else ("tpnode", "model")
    if op == "tp_allreduce":
        fn = lambda a: comms.psum(a, axis, "tp")                   # noqa: E731
        shape = (8, elems)
    else:  # ep_all_to_all
        fn = lambda a: comms.all_to_all(a, axis, 0, 0, "ep")       # noqa: E731
        shape = (64, elems // 8)
    sm = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(("tpnode", "model")),),
        out_specs=P(("tpnode", "model")), check_vma=False))
    with schemes.use(scheme), comms.record_traffic() as events:
        sm.lower(jax.ShapeDtypeStruct(shape, jnp.float32))
    jax.clear_caches()
    return events


def _hier_tp_sweep(rows):
    """Model-layer flat vs two-level on the same TP/EP payloads."""
    elems = 1 << 18                                  # 1 MiB f32 / device
    flat_axes = ((("tpnode", "model"),))
    for op in ("tp_allreduce", "ep_all_to_all"):
        base_slow = None
        for scheme, hier in (("baseline", False), ("zhybrid_16_8", False),
                             ("hier_tpp_8_16", True),
                             ("hier_tpp_4_16", True), ("hier_mtpp_8", True)):
            events = _trace_model_payload(scheme, hier, op, elems)
            slow_ax = flat_axes if not hier else ()
            lb = rl.link_bytes(events, train=True, slow_axes=slow_ax)
            secs = rl.collective_seconds(events, train=True,
                                         slow_axes=slow_ax)
            if base_slow is None:
                base_slow = lb["slow"]
            kind = "hier" if hier else "flat"
            rows.append((f"{op}_1MiB_{kind}_{scheme}",
                         secs * 1e6,                 # roofline us
                         f"slow={lb['slow']/1e6:.2f}MB"
                         f" fast={lb['fast']/1e6:.2f}MB"
                         f" slow_vs_flat_baseline="
                         f"{lb['slow']/max(base_slow,1):.3f}"))
    return rows


def _trace_stage_handoff(scheme, hier: bool, elems: int):
    """One pipeline stage handoff (stage_send) per tick on a 4-stage pipe,
    flat joint axis vs the (ppnode, stage) edge-classified decomposition."""
    from repro.core.compat import AxisPair
    mesh = compat.make_mesh((2, 2, 2), ("data", "ppnode", "stage"))
    axis = AxisPair("ppnode", "stage") if hier else ("ppnode", "stage")
    sm = jax.jit(jax.shard_map(
        lambda a: comms.stage_send(a, axis),
        mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False))
    with schemes.use(scheme), comms.record_traffic() as events:
        sm.lower(jax.ShapeDtypeStruct((2, elems), jnp.float32))
    jax.clear_caches()
    return events


def _pp_handoff_sweep(rows):
    """Stage-handoff bytes: the pp=4 pipe spans two nodes (stage 1 -> 2
    crosses the boundary).  Flat baseline prices every handoff on the slow
    link; the hierarchical axis keeps only the node-crossing edge there,
    and the pp_*_outer codec shrinks it further.  The acceptance row:
    inter-node stage-handoff bytes strictly below the uncompressed
    baseline under every compressed scheme."""
    elems = 1 << 18                                  # 1 MiB f32 / device
    flat_axes = ((("ppnode", "stage"),))
    base_slow = None
    for scheme, hier in (("baseline", False), ("zhybrid_16_8", False),
                         ("hier_tpp_8_16", True), ("hier_tpp_4_16", True),
                         ("hier_mtpp_8", True)):
        events = _trace_stage_handoff(scheme, hier, elems)
        slow_ax = flat_axes if not hier else ()
        lb = rl.link_bytes(events, train=True, slow_axes=slow_ax)
        secs = rl.collective_seconds(events, train=True, slow_axes=slow_ax)
        hand = rl.stage_handoff_seconds(events, train=True,
                                        slow_axes=slow_ax)
        if base_slow is None:
            base_slow = lb["slow"]
        else:
            assert 0 < lb["slow"] < base_slow, \
                (scheme, lb["slow"], base_slow)
        kind = "hier" if hier else "flat"
        rows.append((f"pp_handoff_1MiB_{kind}_{scheme}",
                     secs * 1e6,                     # roofline us
                     f"slow={lb['slow']/1e6:.2f}MB fast={lb['fast']/1e6:.2f}MB"
                     f" handoff_us={hand*1e6:.1f}"
                     f" slow_vs_flat_baseline="
                     f"{lb['slow']/max(base_slow,1):.3f}"))
    # bubble column: what the schedule itself costs at a few microbatch
    # counts (per-device occupancy, independent of codec choice)
    for m in (1, 4, 16):
        rows.append((f"pp_bubble_pp4_m{m}",
                     rl.bubble_fraction(4, m) * 100,  # percent
                     f"step_x{rl.pipelined_step_time(1.0, 4, m):.2f}"))
    return rows


def _dim_level_str(led) -> str:
    """per-dimension x level byte breakdown for the printed summary."""
    return ",".join(f"{k}:{v/1e6:.2f}MB"
                    for k, v in sorted(led["per_dim_level"].items()))


def _hier_step_sweep(rows):
    """Full train step: flat (4,2) mesh vs node-factored meshes.

    Three points: flat baseline, dp-node-factored (PR 1's optimizer-only
    hierarchy), and dp+tp-node-factored (model-layer TP/EP/PP collectives
    also two-level).  The note column carries the per-dimension x level
    breakdown — not just the DP payload."""
    arch = "gemma3-1b"
    flat_mesh = compat.make_mesh((4, 2), ("data", "model"))
    dp_mesh = compat.make_mesh((2, 2, 2), ("node", "data", "model"))
    # tp=8 over two 4-device nodes: the flat model axis spans nodes (its
    # whole ring prices slow); factoring it into (tpnode=2, model=4) keeps
    # only the outer stage inter-node
    tpflat_mesh = compat.make_mesh((1, 8), ("data", "model"))
    tp_mesh = compat.make_mesh((1, 2, 4), ("data", "tpnode", "model"))
    for name, mesh, scheme, slow_axes in (
            ("flat", flat_mesh, "zhybrid_16_8", ("data",)),
            ("dpnode", dp_mesh, "hier_zpp_8_16", ("node",)),
            ("tpflat", tpflat_mesh, "zhybrid_16_8", ("model",)),
            ("tpnode", tp_mesh, "hier_tpp_8_16", ())):
        mi = MeshInfo.from_mesh(mesh)
        cfg = configs.get(arch).reduced()
        model = Model(cfg, mi)
        trainer = Trainer(model, mesh, scheme=scheme)
        pstructs = model.structs()
        ostructs = jax.eval_shape(trainer.opt_init, pstructs)
        binputs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                   "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        with comms.record_traffic() as events:
            trainer.step.lower(pstructs, ostructs,
                           trainer.codec_structs(), binputs)
        lb = rl.link_bytes(events, train=True, slow_axes=slow_axes)
        led = rl.ledger_summary(events, train=True)
        rows.append((f"train_step_{arch}_{name}_{scheme}",
                     led["total_bytes"] / 1e6,
                     f"slow={lb['slow']/1e6:.2f}MB {_dim_level_str(led)}"))
        jax.clear_caches()
    return rows


def _pp_step_sweep(rows):
    """Full microbatched 1F1B train step on a stage mesh: flat (dp=2,
    stage=2, model=2) vs pp-node-factored (dp=2, ppnode=2, stage=2) — the
    per-dimension x level breakdown shows the pp handoffs entering the
    ledger, and moving to the outer/inner split once stage boundaries
    cross nodes."""
    from repro.launch.mesh import make_mesh
    from repro.train.train_step import make_trainer
    arch = "qwen2-72b"
    for name, mesh, scheme in (
            ("ppflat", make_mesh(2, 2, pp=2), "zhybrid_16_8"),
            ("ppnode", make_mesh(2, 1, pp=4, pp_nodes=2), "hier_tpp_8_16")):
        cfg = configs.get(arch).reduced()
        mi = MeshInfo.from_mesh(mesh)
        if sum(g.n for g in cfg.layer_groups) % mi.pp:
            cfg = cfg.replace(n_layers=mi.pp, groups=())
        model = Model(cfg, mi)
        trainer = make_trainer(model, mesh, scheme=scheme, n_micro=4)
        pstructs = model.structs()
        ostructs = jax.eval_shape(trainer.opt_init, pstructs)
        binputs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                   "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        with comms.record_traffic() as events:
            trainer.step.lower(pstructs, ostructs,
                           trainer.codec_structs(), binputs)
        led = rl.ledger_summary(events, train=True)
        assert led["per_dim"].get("pp", 0) > 0, "no pp bytes in the ledger"
        rows.append((f"train_step_{arch}_{name}_{scheme}",
                     led["total_bytes"] / 1e6,
                     _dim_level_str(led)))
        jax.clear_caches()
    return rows


def _policy_sweep(rows):
    """Rule-based policy deltas on the same full train-step trace.

    Three policies over gemma3-1b on a (2, 4) mesh: the plain
    zhybrid_16_8 adapter policy, the same policy with a size-threshold
    rule ("never compress payloads < 64 KiB" — latency-bound small
    collectives gain nothing from encode/decode, so they ride raw and
    total wire bytes RISE), and with a per-tensor rule (aggressive bq4 on
    the ZeRO-1 DP gradient flat vector — gradients tolerate aggressive
    rates thanks to their low-rank structure, arXiv:2301.02654 — so the
    `dp@zero1_grad` site's bytes DROP).  The per-site ledger breakdown
    makes both deltas visible; the asserts are the acceptance
    criterion."""
    from repro.core import policy as policy_lib
    mesh = compat.make_mesh((2, 4), ("data", "model"))
    arch = "gemma3-1b"
    base = schemes.get("zhybrid_16_8").as_policy()
    sweeps = (
        ("base", base),
        ("size_threshold", base.with_rules(
            policy_lib.Rule("none", max_bytes=64 << 10),
            name="zhybrid_16_8+raw_small")),
        ("per_tensor", base.with_rules(
            policy_lib.Rule("bq4", dim="dp", name="zero1_grad*"),
            name="zhybrid_16_8+grad_bq4")),
    )
    leds = {}
    for name, pol in sweeps:
        led = _trace_step_bytes(arch, pol, mesh)
        leds[name] = led
        grad = led["per_site"].get("dp@zero1_grad", 0.0)
        rows.append((f"policy_{name}_{pol.name}",
                     led["total_bytes"] / 1e6,
                     f"vs_base="
                     f"{led['total_bytes']/leds['base']['total_bytes']:.3f}"
                     f" dp@zero1_grad={grad/1e6:.3f}MB"))
        jax.clear_caches()
    # acceptance: each rule demonstrably moves wire bytes, in the ledger
    assert leds["size_threshold"]["total_bytes"] \
        > leds["base"]["total_bytes"], "size rule moved no bytes"
    assert 0 < leds["per_tensor"]["per_site"]["dp@zero1_grad"] \
        < leds["base"]["per_site"]["dp@zero1_grad"], \
        "per-tensor rule moved no bytes"
    assert leds["per_tensor"]["total_bytes"] < leds["base"]["total_bytes"]
    return rows


def run():
    mesh = compat.make_mesh((2, 4), ("data", "model"))
    rows = []
    for arch in ("gemma3-1b", "qwen3-moe-235b-a22b"):
        base = None
        for scheme in ("baseline", "naive_mpc", "naive_zfp8",
                       "mzhybrid8", "zhybrid_16_8", "zhybrid_24_8"):
            led = _trace_step_bytes(arch, scheme, mesh)
            tot = led["total_bytes"]
            if scheme == "baseline":
                base = tot
            per_tag = ",".join(f"{k}:{v/1e6:.2f}MB"
                               for k, v in sorted(led["per_tag"].items()))
            rows.append((f"collective_bytes_{arch}_{scheme}",
                         tot / 1e6,  # "us" column reused as MB
                         f"vs_baseline={tot/max(base,1):.3f} {per_tag}"))
            jax.clear_caches()
    _policy_sweep(rows)
    _hier_sweep(rows)
    _hier_tp_sweep(rows)
    _pp_handoff_sweep(rows)
    _hier_step_sweep(rows)
    _pp_step_sweep(rows)
    return rows
