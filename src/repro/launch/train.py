"""Training entrypoint (mesh-parametric; CPU-runnable at reduced scale).

On the CPU the mesh runs on host devices, which JAX gives only to a run
that is on the CPU: ``JAX_PLATFORMS=cpu`` or ``--host-devices N``.  On an
accelerator the mesh takes the devices it has.  ``run(args, cfg)`` is the
same training loop for callers in the same process (``chip_smoke.py``).

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train \
        --arch gemma3-1b --reduced \
        --dp 2 --tp 4 --steps 50 --scheme zhybrid_16_8 --ckpt-dir /tmp/ck

    # pipeline-parallel: 2 stages, 4 microbatches (1F1B), compressed
    # stage handoffs per the active scheme's pp codecs
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train \
        --arch qwen2-72b --reduced \
        --dp 2 --tp 2 --pp 2 --microbatches 4 --scheme hier_tpp_8_16

    # context-parallel long sequences: zigzag sequence sharding over an
    # explicit 'cp' mesh axis; ring attention rotates KV blocks under the
    # scheme's cp_fwd/cp_bwd codecs
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train \
        --arch gemma3-1b --reduced \
        --dp 2 --cp 2 --seq 128 --scheme zhybrid_16_8

    # rule-based policy overrides on top of any scheme: small payloads
    # ride raw, embedding gathers stay mild
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train \
        --arch gemma3-1b --reduced \
        --dp 2 --tp 2 --scheme zhybrid_16_8 \
        --no-compress-below 65536 --codec-for 'embed*=bq16'

    # carried-state codecs on the DP gradient sync: error-feedback bq4
    # (convergence-safe aggressive rate) scoped to the ZeRO-1 grad site;
    # the codec state checkpoints/restores next to the optimizer state
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train \
        --arch gemma3-1b --reduced \
        --dp 4 --tp 2 --scheme zhybrid_16_8 \
        --codec-for 'dp@zero1_grad*=ef:bq4' --ckpt-dir /tmp/ck

Features exercised here: compressed-collective policies (named schemes
are rule presets; --no-compress-below / --codec-for prepend override
rules), ZeRO-1(+3),
microbatched 1F1B pipeline parallelism (--pp/--microbatches),
deterministic resumable data, step/straggler monitoring, atomic async
checkpointing of params AND optimizer state, elastic restart (--resume on
a different --dp/--tp/--pp; Adam moments carry over when the topology
matches, otherwise they reinitialize with a warning).
"""

from __future__ import annotations

import argparse
import json
import os

from repro.launch import runtime


def _restore_opt(trainer, params, opt_dir, step, mesh, checkpoint):
    """Resume the optimizer state saved alongside the params.

    Compat paths: a pre-opt-checkpoint run (no ``opt/`` subdir), an
    elastic restart whose new topology changes the opt-state layout, and
    a ``v`` saved in a layout this run does not keep (``v_layout`` in the
    manifest) all fall back to ``opt_init`` — with a loud warning, since
    that resets the Adam moments (the bug this replaces did it
    silently)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    if not opt_dir or checkpoint.latest_step(opt_dir) != step:
        print("WARNING: no optimizer checkpoint for this step — "
              "reinitializing Adam moments (old param-only checkpoint?)")
        return trainer.opt_init(params)
    ostructs = jax.eval_shape(trainer.opt_init, params)
    osharding = jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), trainer.opt_state_specs(),
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    try:
        ostate, man = checkpoint.restore(opt_dir, ostructs, step=step,
                                         shardings=osharding)
        # untagged checkpoints predate the tag and hold v as is; an 8-bit
        # one of those read as sqrt(v) would make every Adam step too large
        saved = man["extra"].get("v_layout", "v")
        if saved != trainer.opt.v_layout:
            raise ValueError(f"its v is saved as {saved!r}, this run "
                             f"keeps {trainer.opt.v_layout!r}")
        print(f"restored optimizer state at step {step}")
        return ostate
    except (ValueError, AssertionError) as e:
        print(f"WARNING: optimizer state not portable to this topology "
              f"({e}) — reinitializing Adam moments")
        return trainer.opt_init(params)


def _restore_codec(trainer, codec_dir, step, mesh, checkpoint):
    """Resume the carried codec state (ef residuals / plr factors) saved
    alongside the params.

    Loud fallbacks mirror :func:`_restore_opt`: a pre-stateful-codec
    checkpoint (no ``codec/`` subdir) or a topology change that reshapes
    the flat sync vectors reinitializes the state with a warning —
    resetting an error-feedback residual silently would quietly re-bias
    the very gradients the ef codec exists to de-bias."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    template = trainer.codec_structs()
    if not jax.tree_util.tree_leaves(template):
        return {}
    if not codec_dir or checkpoint.latest_step(codec_dir) != step:
        print("WARNING: no codec-state checkpoint for this step — "
              "reinitializing error-feedback/low-rank codec state "
              "(pre-stateful-codec checkpoint?)")
        return trainer.init_codec_state()
    shardings = jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), trainer.codec_state_specs(),
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    try:
        cstate, _ = checkpoint.restore(codec_dir, template, step=step,
                                       shardings=shardings)
        print(f"restored codec state at step {step}")
        return cstate
    except (ValueError, AssertionError) as e:
        print(f"WARNING: codec state not portable to this topology "
              f"({e}) — reinitializing")
        return trainer.init_codec_state()


def _restore_tune(trainer, tune_dir, step, mesh, checkpoint):
    """Resume the self-tuning signal accumulators saved under
    ``<ckpt>/tune/``.

    Loud fallbacks mirror :func:`_restore_codec`: a pre-tune checkpoint
    or a topology change that renames the tunable sites starts the
    controller interval fresh (zeroed accumulators) with a warning.
    Returns ``None`` on fallback — the caller re-derives the rung
    selections from the restored controller state (or the plan)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    if not tune_dir or checkpoint.latest_step(tune_dir) != step:
        print("WARNING: no tune-state checkpoint for this step — "
              "starting the controller interval fresh (zeroed signal "
              "accumulators)")
        return None
    shardings = jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), trainer.tune_state_specs(),
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    try:
        tstate, _ = checkpoint.restore(tune_dir, trainer.tune_structs(),
                                       step=step, shardings=shardings)
        print(f"restored tune state at step {step}")
        return tstate
    except (ValueError, AssertionError) as e:
        print(f"WARNING: tune state not portable to this topology ({e}) — "
              "starting the controller interval fresh")
        return None


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="family-preserving smoke-size config")
    ap.add_argument("--layers", type=int, default=0,
                    help="override the config's layer count (resets "
                         "heterogeneous layer groups to uniform); e.g. "
                         "--reduced keeps 2 layers, but --pp 2 --vpp 2 "
                         "needs pp x vpp = 4")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel stages (explicit 'stage' mesh "
                         "axis; layer groups partition into contiguous "
                         "stages)")
    ap.add_argument("--cp", type=int, default=1,
                    help="context/sequence-parallel degree (explicit 'cp' "
                         "mesh axis): the sequence shards in zigzag "
                         "load-balanced chunks and ring attention rotates "
                         "KV blocks under the scheme's cp codecs)")
    ap.add_argument("--pod", type=int, default=1)
    ap.add_argument("--nodes", default="1",
                    help="factor dp into (node, local) sub-axes for "
                         "hierarchical two-level collectives; an int or "
                         "'NxD' (N nodes x D dp-ranks-per-node)")
    ap.add_argument("--tp-nodes", default="1",
                    help="factor tp into (tpnode, model) sub-axes so the "
                         "model-layer TP/EP/PP collectives run their "
                         "two-level decompositions; an int or 'NxD'")
    ap.add_argument("--pp-nodes", default="1",
                    help="factor pp into (ppnode, stage) sub-axes: stage "
                         "handoffs crossing a node boundary ride the "
                         "aggressive pp_*_outer codec; an int or 'NxD'")
    ap.add_argument("--cp-nodes", default="1",
                    help="factor cp into (cpnode, cp) sub-axes: ring-"
                         "attention KV hops crossing a node boundary ride "
                         "the cp_*_outer codec; an int or 'NxD'")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="split the per-rank batch into N microbatches "
                         "(1F1B schedule on a stage mesh, plain gradient "
                         "accumulation otherwise)")
    ap.add_argument("--vpp", type=int, default=1,
                    help="interleaved virtual pipeline stages: each stage "
                         "rank holds V round-robin depth slices, cutting "
                         "the 1F1B bubble ~1/V at fixed --pp (needs "
                         "--pp > 1 and --microbatches divisible by --pp)")
    ap.add_argument("--remat-policy", default="none",
                    help="activation memory policy for the pipeline tick "
                         "scan: none | full | per_stage:<v,v,...> "
                         "(jax.checkpoint per virtual-stage body), with "
                         "an optional +offload suffix parking matmul "
                         "residuals in pinned host memory")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="run on N host CPU devices (pins JAX_PLATFORMS=cpu)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--scheme", default="baseline")
    ap.add_argument("--no-compress-below", type=int, default=0,
                    metavar="BYTES",
                    help="policy rule: payloads smaller than BYTES ride "
                         "uncompressed (latency-bound small collectives "
                         "gain nothing from encode/decode)")
    ap.add_argument("--codec-for", action="append", default=[],
                    metavar="[DIM@]NAME_GLOB=CODEC",
                    help="policy rule: override the codec for comm sites "
                         "whose name matches the glob, optionally pinned "
                         "to one parallelism dimension (repeatable; e.g. "
                         "embed*=bq16 keeps embedding gathers mild, "
                         "dp@zero1_grad*=ef:bq4 puts error-feedback rate-4 "
                         "on the ZeRO-1 DP gradient sync, dp=plr8 covers a "
                         "whole dimension)")
    ap.add_argument("--tune", action="store_true",
                    help="close the measurement->policy loop in-training: "
                         "per-step compression signals feed a host-side "
                         "controller that walks the tunable DP grad-sync "
                         "sites along the bq16->bq8->ef:bq4->plr ladder "
                         "via runtime rung swaps (no step recompile), "
                         "stamps the heartbeat with the live plan hash, "
                         "and emits <ckpt>/tune_policy.json")
    ap.add_argument("--tune-interval", type=int, default=50,
                    help="steps between controller decision rounds (each "
                         "round drains the signal accumulators, walks the "
                         "ladder, and swaps the rung selections)")
    ap.add_argument("--tune-guard", type=float, default=0.05,
                    help="relative loss-EMA regression between decision "
                         "rounds that vetoes promotions and rolls back "
                         "the most recent one")
    ap.add_argument("--policy-from", default="", metavar="TUNE_POLICY_JSON",
                    help="replay a tuned-policy artifact as a static "
                         "policy: its site rules prepend onto --scheme, "
                         "reproducing the emitting run's final plan table "
                         "bit-exactly (topology mismatches warn loudly)")
    ap.add_argument("--ring-bidir", action="store_true",
                    help="split compressed ring collectives into two "
                         "counter-rotating half-rings (halves per-link "
                         "bytes; falls back to one ring, visibly in the "
                         "ledger, when the payload is under a tile per "
                         "direction)")
    ap.add_argument("--ring-chunks", type=int, default=1,
                    help="stripe each compressed ring collective into N "
                         "independently-pipelined row chunks so chunk k+1's "
                         "encode overlaps chunk k's transfer (bit-exact for "
                         "per-row-scale bq codecs at any count)")
    ap.add_argument("--grad-buckets", type=int, default=1,
                    help="split the flat ZeRO-1 DP gradient sync into N "
                         "bucketed reduce-scatter chains with the clip "
                         "scale applied post-sync, letting each bucket's "
                         "ring hops dispatch as soon as backward produces "
                         "its slice (opt-in: not bit-exact with the "
                         "single-bucket path)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--opt-state-bits", type=int, default=32)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    """The command line as :func:`run` takes it."""
    return _parser().parse_args(argv)


def comm_policy_from_flags(args):
    """The named scheme plus the policy flags' override rules (also the
    serving launcher's).

    The scheme is sugar over rules (the adapter path); the flags prepend
    override rules, first-match-wins.  A malformed ``--codec-for`` raises
    ``ValueError``."""
    from repro.core import policy as policy_lib
    comm_policy = policy_lib.as_policy(args.scheme)
    overrides = []
    if args.no_compress_below > 0:
        overrides.append(policy_lib.Rule(
            "none", max_bytes=args.no_compress_below))
    for spec in args.codec_for:
        pat, _, codec = spec.partition("=")
        if not pat or not codec:
            raise ValueError(
                f"--codec-for wants [DIM@]NAME_GLOB=CODEC, got {spec!r}")
        dim, at, name = pat.partition("@")
        try:
            if at and dim:                       # dp@zero1_grad*=ef:bq4
                overrides.append(policy_lib.Rule(codec, dim=dim,
                                                 name=name or None))
            elif pat in policy_lib.DIMS:         # dp=plr8 (whole dimension)
                overrides.append(policy_lib.Rule(codec, dim=pat))
            else:                                # embed*=bq16 (name glob)
                overrides.append(policy_lib.Rule(codec, name=pat))
        except KeyError as e:                    # eager codec/dim validation
            raise ValueError(f"--codec-for {spec!r}: {e}") from None
    if overrides:
        comm_policy = comm_policy.with_rules(
            *overrides, name=f"{comm_policy.name}+cli")
    return comm_policy


def _n_devices(args) -> int:
    return args.dp * args.tp * args.pp * args.cp * args.pod


def main(argv=None):
    ap = _parser()
    args = ap.parse_args(argv)
    runtime.force_cpu_devices(args.host_devices or _n_devices(args),
                              asked=bool(args.host_devices))
    try:
        comm_policy_from_flags(args)
    except ValueError as e:
        ap.error(str(e))
    runtime.use_compile_cache()
    run(args)


def run(args, cfg=None) -> dict:
    """Train as the command line ``args`` say; return the per-step
    ``loss``, ``grad_norm`` and ``step_time`` lists, the step's
    ``compile_time`` (seconds of tracing, lowering, compiling and cache
    loading on ``repro.obs``'s compile clock), its ledger ``wire_bytes``
    per device per step for each parallelism dimension, and the final
    ``params`` and ``opt_state``.

    ``cfg`` replaces the ``--arch``/``--reduced``/``--layers`` config.
    Each step time ends when the step's outputs are ready on the device;
    the step is compiled ahead of the loop and its compile time is kept
    apart.  Under the profiler each step is a ``train`` step annotation,
    with host spans ``repro.batch``, ``repro.sync``, ``repro.tune`` and
    ``repro.checkpoint`` inside it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from repro import configs, obs
    from repro.analysis import roofline
    from repro.core import comms
    from repro.data.pipeline import DataConfig, SyntheticCorpus
    from repro.launch.mesh import make_mesh, parse_nodes_spec, validate_vpp
    from repro.models.model import Model
    from repro.models.params import MeshInfo
    from repro.train import checkpoint, fault
    from repro.train.optimizer import AdamConfig
    from repro.train.train_step import (batch_specs, make_trainer,
                                        zigzag_shard_seq)

    if cfg is None:
        cfg = configs.get(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
        if args.layers:
            cfg = cfg.replace(n_layers=args.layers, groups=())
    runtime.require_devices(_n_devices(args))
    nodes = parse_nodes_spec(args.nodes, args.dp)
    tp_nodes = parse_nodes_spec(args.tp_nodes, args.tp, flag="--tp-nodes")
    pp_nodes = parse_nodes_spec(args.pp_nodes, args.pp, flag="--pp-nodes")
    cp_nodes = parse_nodes_spec(args.cp_nodes, args.cp, flag="--cp-nodes")
    mesh = make_mesh(args.dp, args.tp, args.pod, nodes=nodes,
                     tp_nodes=tp_nodes, pp=args.pp, pp_nodes=pp_nodes,
                     cp=args.cp, cp_nodes=cp_nodes)
    mi = MeshInfo.from_mesh(mesh)
    validate_vpp(args.vpp, args.pp, args.microbatches)
    model = Model(cfg, mi, vpp=args.vpp)
    comm_policy = comm_policy_from_flags(args)

    if args.policy_from:
        from repro.tune import policy_artifact
        art = policy_artifact.load(args.policy_from)
        for w in fault.tune_restart_warnings(
                art, mi,
                heartbeat_path=os.path.join(args.ckpt_dir, "heartbeat.json")
                if args.ckpt_dir else None):
            print(f"WARNING: {w}")
        comm_policy = policy_artifact.as_policy(art, base=comm_policy)
        print(f"applied tuned policy {args.policy_from}: "
              f"{len(art['rules'])} site rules from step {art['step']} "
              f"(plan {art['plan_hash']})")

    trainer = make_trainer(model, mesh, scheme=comm_policy,
                           tune=args.tune,
                           opt_cfg=AdamConfig(lr=args.lr,
                                              state_bits=args.opt_state_bits,
                                              grad_buckets=args.grad_buckets),
                           n_micro=args.microbatches,
                           ring_bidir=args.ring_bidir,
                           ring_chunks=args.ring_chunks,
                           remat_policy=args.remat_policy)
    data = SyntheticCorpus(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.global_batch, seed=args.seed))

    opt_dir = os.path.join(args.ckpt_dir, "opt") if args.ckpt_dir else ""
    codec_dir = os.path.join(args.ckpt_dir, "codec") if args.ckpt_dir else ""
    tune_dir = os.path.join(args.ckpt_dir, "tune") if args.ckpt_dir else ""
    pending = []

    def save_tune_host():
        """Controller host state: tiny JSON next to the tune_state arrays
        (atomic write + rename, like the heartbeat)."""
        os.makedirs(tune_dir, exist_ok=True)
        tmp = os.path.join(tune_dir, "controller.json.tmp")
        with open(tmp, "w") as f:
            json.dump(ctrl.state_dict(), f)
        os.replace(tmp, os.path.join(tune_dir, "controller.json"))

    def save_all(step, blocking):
        t1 = checkpoint.save(args.ckpt_dir, step, params, blocking=blocking)
        t2 = checkpoint.save(opt_dir, step, ostate,
                             extra={"v_layout": trainer.opt.v_layout},
                             blocking=blocking)
        t3 = checkpoint.save(codec_dir, step, cstate, blocking=blocking)
        ts = [t1, t2, t3]
        if args.tune:
            ts.append(checkpoint.save(tune_dir, step, tstate,
                                      blocking=blocking))
            save_tune_host()
        if not blocking:
            pending.extend(ts)

    start = 0
    resumed = False
    if args.resume and args.ckpt_dir and \
            checkpoint.latest_step(args.ckpt_dir) is not None:
        sh = checkpoint.resharded_specs(model.structs(), mesh)
        params, man = checkpoint.restore(args.ckpt_dir, model.structs(),
                                         shardings=sh)
        start = man["step"]
        ostate = _restore_opt(trainer, params, opt_dir, start, mesh,
                              checkpoint)
        cstate = _restore_codec(trainer, codec_dir, start, mesh, checkpoint)
        resumed = True
        print(f"resumed from step {start} (elastic onto dp={args.dp} "
              f"tp={args.tp} pp={args.pp})")
    else:
        params, ostate, cstate = trainer.init_all(jax.random.key(args.seed))

    tstate = ctrl = trk = None
    if args.tune:
        from repro.tune import policy_artifact, tracker
        from repro.tune.controller import (CompressionController,
                                           ControllerConfig)
        ctrl = CompressionController(
            trainer.policy, trainer.tune_sites(), mesh_info=mi,
            cfg=ControllerConfig(interval=args.tune_interval,
                                 guard=args.tune_guard),
            start_step=start)
        trk = tracker.SignalTracker()
        if resumed:
            ctrl_path = os.path.join(tune_dir, "controller.json")
            if tune_dir and os.path.exists(ctrl_path):
                try:
                    with open(ctrl_path) as f:
                        ctrl.load_state_dict(json.load(f))
                    print(f"restored tune controller (last decision step "
                          f"{ctrl.last_decision_step})")
                except (ValueError, KeyError) as e:
                    print(f"WARNING: tune controller state not portable "
                          f"({e}) — restarting the ladder walk from the "
                          "base scheme")
            else:
                print("WARNING: no tune controller state in checkpoint — "
                      "restarting the ladder walk from the base scheme")
            tstate = _restore_tune(trainer, tune_dir, start, mesh,
                                   checkpoint)
        if tstate is None:
            tstate = trainer.init_tune_state()
        # the rung selections always come from the controller (which just
        # restored its ladder position, or starts at the base scheme's) —
        # the checkpointed part that matters is the signal accumulators
        rep = NamedSharding(mesh, PartitionSpec())
        tstate = {"select": {k: jax.device_put(jnp.int32(v), rep)
                             for k, v in ctrl.select_indices().items()},
                  "sig": tstate["sig"]}

    bspecs = batch_specs(cfg, mi)
    if args.ckpt_dir:
        os.makedirs(args.ckpt_dir, exist_ok=True)
    mon = fault.StepMonitor(
        heartbeat_path=os.path.join(args.ckpt_dir, "heartbeat.json")
        if args.ckpt_dir else None)
    if args.tune:
        mon.tune_plan_hash = ctrl.plan().table_hash()
        mon.tune_decision_step = ctrl.last_decision_step

    def put_batch(step):
        np_batch = zigzag_shard_seq(data.batch(step), mi.cp)
        return {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
                for k, v in np_batch.items()}

    c0 = obs.snapshot()
    with comms.record_traffic() as events:
        if args.tune:
            step_fn = trainer.step_tuned.lower(params, ostate, cstate, tstate,
                                               put_batch(start)).compile()
        else:
            step_fn = trainer.step.lower(params, ostate, cstate,
                                         put_batch(start)).compile()
    c1 = obs.snapshot()
    hist = {"loss": [], "grad_norm": [], "step_time": [],
            "compile_time": c1["total_s"] - c0["total_s"],
            "wire_bytes": roofline.ledger_summary(events,
                                                  train=True)["per_dim"]}
    print(f"compiled the step in {obs.split(c0, c1)}; wire bytes "
          "per device per step: " + (", ".join(
              f"{d}={b:.0f}" for d, b in sorted(hist["wire_bytes"].items()))
              or "none"))
    mem = step_fn.memory_analysis()
    print(f"step memory per device (compiled): arguments "
          f"{mem.argument_size_in_bytes}, outputs {mem.output_size_in_bytes}"
          f", aliased {mem.alias_size_in_bytes}, temporaries "
          f"{mem.temp_size_in_bytes} bytes")

    for step in range(start, start + args.steps):
        with jax.profiler.StepTraceAnnotation("train", step_num=step):
            mon.begin()
            with obs.span(obs.BATCH):
                batch = put_batch(step)
            if args.tune:
                params, ostate, cstate, tstate, metrics = step_fn(
                    params, ostate, cstate, tstate, batch)
            else:
                params, ostate, cstate, metrics = step_fn(params, ostate,
                                                          cstate, batch)
            # the step ends when the device has finished it, not at dispatch
            with obs.span(obs.SYNC):
                jax.block_until_ready((params, ostate, cstate, metrics))
            info = mon.end(step)
            hist["loss"].append(float(metrics["loss"]))
            hist["grad_norm"].append(float(metrics["grad_norm"]))
            hist["step_time"].append(info["dt"])
            if args.tune:
                ctrl.observe_loss(step, hist["loss"][-1])
            if args.tune and (step + 1 - start) % args.tune_interval == 0:
                with obs.span(obs.TUNE):
                    sigs, zeroed = trk.drain(tstate["sig"])
                    for d in ctrl.decide(step, sigs):
                        if d.changed:
                            print(f"tune[{d.site}] step {step}: {d.action} "
                                  f"{d.from_codec} -> {d.to_codec} "
                                  f"({d.reason})")
                    rep = NamedSharding(mesh, PartitionSpec())
                    tstate = {
                        "select": {k: jax.device_put(jnp.int32(v), rep)
                                   for k, v in
                                   ctrl.select_indices().items()},
                        "sig": {k: jax.device_put(jnp.asarray(z), rep)
                                for k, z in zeroed.items()}}
                    mon.tune_plan_hash = ctrl.plan().table_hash()
                    mon.tune_decision_step = step
                    if args.ckpt_dir:
                        policy_artifact.emit(
                            os.path.join(args.ckpt_dir, "tune_policy.json"),
                            ctrl)
            if step % 5 == 0 or step == start + args.steps - 1:
                print(f"step {step:5d} loss={hist['loss'][-1]:.4f} "
                      f"gnorm={hist['grad_norm'][-1]:.3f} "
                      f"dt={info['dt']:.3f}s"
                      + (" STRAGGLER" if info["straggler"] else ""))
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                with obs.span(obs.CHECKPOINT):
                    save_all(step + 1, blocking=False)
    if args.ckpt_dir:
        for t in pending:
            t.join()
        if checkpoint.latest_step(args.ckpt_dir) != start + args.steps:
            with obs.span(obs.CHECKPOINT):
                save_all(start + args.steps, blocking=True)
        print(f"checkpointed at step {start + args.steps}")
    if args.tune:
        if args.ckpt_dir:
            art = policy_artifact.emit(
                os.path.join(args.ckpt_dir, "tune_policy.json"), ctrl)
            print(f"tune_policy.json: plan {art['plan_hash']} "
                  f"({len(art['rules'])} site rules)")
        print("tuned codecs: " + ", ".join(
            f"{k}={v}" for k, v in sorted(ctrl.codec.items())))
    print(f"done: final loss {hist['loss'][-1]:.4f}, "
          f"teacher floor {data.optimal_xent():.4f}, "
          f"stragglers {mon.stragglers}/{mon.steps}")
    return {**hist, "params": params, "opt_state": ostate}


if __name__ == "__main__":
    main()
