"""Rate sweep: loss-vs-wire-bytes trade-off across policies (paper Fig 11
analog, plus the beyond-paper rate-4 knee).

Canonical policy-API example: trains the same tiny model under every
registered scheme *as a compiled rule policy* (`Scheme.as_policy()` —
each named scheme is sugar over rules) plus one custom policy built from
one-line override rules (a size threshold and a per-tensor codec), and
prints a table of (final loss, wire MB/step, modeled collective-term
speedup).

    PYTHONPATH=src python examples/compression_sweep.py [--steps 80]
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse

import jax
import numpy as np
from jax.sharding import NamedSharding

from repro.core import compat
from repro import configs
from repro.analysis import roofline as rl
from repro.core import comms, policy as policy_lib, schemes as schemes_lib
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.models.model import Model
from repro.models.params import MeshInfo
from repro.train.optimizer import AdamConfig
from repro.train.train_step import Trainer, batch_specs


def sweep_policies():
    """Every registered scheme through the adapter, plus a custom policy:
    keep zhybrid_16_8's codecs, but never compress payloads under 64 KiB
    (latency-bound small collectives) and push the ZeRO-1 DP gradient
    flat vector down to rate 4 (gradients tolerate aggressive rates —
    their low-rank structure, arXiv:2301.02654)."""
    pols = [schemes_lib.get(n).as_policy() for n in schemes_lib.names()]
    base = schemes_lib.get("zhybrid_16_8").as_policy()
    pols.append(base.with_rules(
        policy_lib.Rule("none", max_bytes=64 << 10),
        policy_lib.Rule("bq4", dim="dp", name="zero1_grad*"),
        name="zhy_16_8+rules"))
    return pols


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=80)
    args = ap.parse_args()

    mesh = compat.make_mesh((4, 2), ("data", "model"))
    mi = MeshInfo.from_mesh(mesh)
    cfg = configs.get("gemma3-1b").reduced().replace(vocab_size=128)
    data = SyntheticCorpus(DataConfig(vocab_size=128, seq_len=32,
                                      global_batch=8, noise=0.05))
    model = Model(cfg, mi)
    bspecs = batch_specs(cfg, mi)

    base_bytes = None
    print(f"{'policy':16s} {'final_loss':>10s} {'wire MB/step':>13s} "
          f"{'coll. reduction':>15s}")
    for pol in sweep_policies():
        # Trainer compiles the policy against the mesh once; the legacy
        # scheme-name path (scheme="zhybrid_16_8") still works via the
        # same adapter and resolves identically.
        trainer = Trainer(model, mesh, scheme=pol,
                          opt_cfg=AdamConfig(lr=3e-3))
        params, ostate, cstate = trainer.init_all(jax.random.key(0))
        with comms.record_traffic() as events:
            trainer.step.lower(
                jax.tree.map(jax.typeof, params),
                jax.tree.map(jax.typeof, ostate),
                jax.tree.map(jax.typeof, cstate),
                {k: jax.typeof(jax.numpy.asarray(v))
                 for k, v in data.batch(0).items()})
        led = rl.ledger_summary(events, train=True)
        if pol.name == "baseline":
            base_bytes = led["total_bytes"]
        losses = []
        for s in range(args.steps):
            b = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
                 for k, v in data.batch(s).items()}
            params, ostate, cstate, m = trainer.step(params, ostate,
                                                     cstate, b)
            losses.append(float(m["loss"]))
        final = float(np.mean(losses[-8:]))
        print(f"{pol.name:16s} {final:10.4f} {led['total_bytes']/1e6:13.2f} "
              f"{base_bytes/max(led['total_bytes'],1):14.2f}x")
        jax.clear_caches()


if __name__ == "__main__":
    main()
