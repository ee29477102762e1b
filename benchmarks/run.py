"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  bench_codec        ZFP-rate trade-off microbench        (paper §II-A/IV-C)
  bench_collectives  wire bytes per parallelism dim/scheme (paper Fig 1, §III)
  bench_convergence  loss curves per scheme               (paper Figs 7c-11)
  bench_step_time    measured fused vs three-pass wall time (paper §IV-A)
  bench_serve        serving: prefill/decode rates, continuous batching,
                     at-rest KV codec cost + capacity

A bench module that crashes is recorded as a ``FAILED:...`` CSV row and
the harness keeps going — but the exit code is nonzero if anything
failed (a crashing bench used to exit 0 and green-wash CI).

The bench harness needs a multi-device host mesh to exercise the schemes;
it sets its own 8-device flag (NOT the dry-run's 512) before jax init.
"""

import os

if "XLA_FLAGS" not in os.environ or \
        "device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import importlib     # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

MODULES = ("bench_codec", "bench_collectives", "bench_convergence",
           "bench_step_time", "bench_serve")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=MODULES)
    ap.add_argument("--suggest", nargs="*", metavar="ICI_BW:DCN_BW",
                    help="print the per-level codec suggestion for the "
                         "given link-bandwidth pairs in bytes/s (default: "
                         "a sweep of ICI/DCN ratios) and exit")
    ap.add_argument("--from-ledger", metavar="ARCH",
                    help="with --suggest: price the codec ladder on the "
                         "REAL per-step comms ledger of this arch (one "
                         "recorded dry-run train step on a node-factored "
                         "mesh) instead of a synthetic two-level "
                         "all-reduce")
    ap.add_argument("--remat-tradeoff", metavar="ARCH",
                    help="print the pipeline activation-policy table for "
                         "this arch: per (pp, vpp, n_micro) point, the "
                         "tick-scan stash bytes with/without remat, the "
                         "remat FLOP-seconds paid, and the interleaved "
                         "bubble — the terms --remat-policy / --vpp trade "
                         "against the stage-handoff seconds")
    args = ap.parse_args()
    if args.remat_tradeoff is not None:
        _remat_tradeoff(args.remat_tradeoff)
        return
    if args.suggest is not None:
        events = _ledger_events(args.from_ledger) if args.from_ledger \
            else None
        _suggest(args.suggest, events)
        return
    mods = [args.only] if args.only else list(MODULES)
    print("name,us_per_call,derived")
    failed = []
    for name in mods:
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # keep the harness going; record the failure
            print(f"{name},0.0,FAILED:{e!r}")
            failed.append(name)
            continue
        for r in rows:
            print(f"{r[0]},{r[1]:.2f},{r[2]}")
        print(f"{name}_total,{(time.time() - t0) * 1e6:.0f},wall",
              file=sys.stderr)
    if failed:
        print(f"bench modules FAILED: {', '.join(failed)}", file=sys.stderr)
        raise SystemExit(1)


def _ledger_events(arch: str) -> list:
    """The real per-step ledger of ``arch`` (reduced config): record one
    lowered train step on a node-factored (node=2, data=2, model=2) mesh
    so every hierarchical stage shows up with its level."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.core import comms, compat
    from repro.models.model import Model
    from repro.models.params import MeshInfo
    from repro.train.train_step import Trainer

    mesh = compat.make_mesh((2, 2, 2), ("node", "data", "model"))
    mi = MeshInfo.from_mesh(mesh)
    model = Model(configs.get(arch).reduced(), mi)
    trainer = Trainer(model, mesh, scheme="hier_zpp_8_16")
    pstructs = model.structs()
    ostructs = jax.eval_shape(trainer.opt_init, pstructs)
    binputs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
               "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
    with comms.record_traffic() as events:
        trainer.step.lower(pstructs, ostructs, trainer.codec_structs(),
                           binputs)
    jax.clear_caches()
    return events


def _remat_tradeoff(arch: str) -> None:
    """roofline.remat_tradeoff over the arch's FULL (non-reduced) shape:
    a deterministic table ranking "remat the stash away" against the
    schedule/bubble terms, per (pp, vpp, n_micro) point."""
    from repro import configs
    from repro.analysis import roofline as rl
    cfg = configs.get(arch)
    tokens = 8 * 4096 // 8                  # B=8, S=4096, n_micro=8 slice
    print("pp,vpp,n_micro,ticks,bubble,stash_gb,stash_remat_gb,"
          "remat_extra_s")
    for pp in (4, 8):
        if cfg.n_layers % pp:
            continue
        for vpp in (1, 2, 4):
            if (cfg.n_layers // pp) % vpp:
                continue
            for n_micro in (pp, 4 * pp):
                r = rl.remat_tradeoff(cfg.d_model, tokens,
                                      cfg.n_layers // pp, n_micro, pp, vpp)
                print(f"{pp},{vpp},{n_micro},{r['ticks']},"
                      f"{r['bubble_fraction']:.4f},"
                      f"{r['stash_bytes'] / 1e9:.3f},"
                      f"{r['stash_bytes_remat'] / 1e9:.3f},"
                      f"{r['remat_extra_seconds']:.4f}")


def _suggest(pairs, events=None) -> None:
    """roofline.suggest_scheme over measured (or default) link speeds."""
    from repro.analysis import roofline as rl
    if not pairs:
        pairs = [f"{rl.ICI_BW:.0f}:{rl.ICI_BW / r:.0f}"
                 for r in (1, 2, 8, 16, 32, 64)]
    print("ici_bw,dcn_bw,ratio,scheme,outer_codec")
    for p in pairs:
        ici, dcn = (float(x) for x in p.split(":"))
        s = rl.suggest_scheme(ici, dcn, events=events)
        print(f"{ici:.3g},{dcn:.3g},{s['ratio']:.1f},"
              f"{s['scheme']},{s['outer_codec']}")


if __name__ == "__main__":
    main()
