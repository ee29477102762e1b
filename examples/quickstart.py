"""Quickstart: compressed collectives in 60 lines.

Builds a tiny gemma3-family model on a 2x4 host mesh, runs one training
step under the paper's ZHybrid scheme, and prints the collective ledger —
the wire bytes each parallelism dimension pays, before/after compression.

    PYTHONPATH=src python examples/quickstart.py
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import numpy as np
from jax.sharding import NamedSharding

from repro.core import compat
from repro import configs
from repro.analysis import roofline as rl
from repro.core import comms
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.models.model import Model
from repro.models.params import MeshInfo
from repro.train.train_step import Trainer, batch_specs


def main():
    mesh = compat.make_mesh((2, 4), ("data", "model"))
    mi = MeshInfo.from_mesh(mesh)
    cfg = configs.get("gemma3-1b").reduced()
    model = Model(cfg, mi)
    data = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                      global_batch=8))

    for scheme in ("baseline", "zhybrid_16_8"):
        trainer = Trainer(model, mesh, scheme=scheme)
        params, ostate, cstate = trainer.init_all(jax.random.key(0))
        bspecs = batch_specs(cfg, mi)
        batch = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
                 for k, v in data.batch(0).items()}
        # trace once under the ledger to see what crosses the wire
        with comms.record_traffic() as events:
            trainer.step.lower(
                jax.tree.map(lambda x: jax.typeof(x), params),
                jax.tree.map(lambda x: jax.typeof(x), ostate),
                jax.tree.map(lambda x: jax.typeof(x), cstate),
                jax.tree.map(lambda x: jax.typeof(x), batch))
        led = rl.ledger_summary(events, train=True)
        # and actually run a few steps
        losses = []
        for s in range(5):
            b = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
                 for k, v in data.batch(s).items()}
            params, ostate, cstate, m = trainer.step(params, ostate,
                                                     cstate, b)
            losses.append(float(m["loss"]))
        print(f"[{scheme:14s}] losses {['%.3f' % l for l in losses]}  "
              f"wire/step = {led['total_bytes'] / 1e6:.2f} MB  "
              f"per-dim = { {k: round(v / 1e3) for k, v in led['per_tag'].items()} } KB")
        jax.clear_caches()


if __name__ == "__main__":
    main()
