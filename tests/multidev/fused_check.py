"""Fused one-pass ring vs the PR-5 three-pass lowering: bit-exact.

Acceptance check for the fused compress-and-communicate path on a full
``(data=2, stage=2, model=2)`` mesh:

  * compressed psum / reduce-scatter / all-gather-roundtrip over every
    mesh axis AND the joint flat ``("data", "stage")`` axis produce
    BIT-IDENTICAL results whether the ring hops run the fused
    decode-add-encode kernels (wire-only intermediate hops, decode-add
    final hop) or the unfused explicit decode -> add -> encode passes —
    same math, different scheduling, so any numeric drift is a kernel
    bug;
  * the overlap levers are equally bit-exact: ``ring_options`` chunk
    striping (data-independent sub-rings) and the bidirectional split
    under a FIXED bidir setting (bq scales are per 128-lane row);
  * gradients through the fused compressed psum match three-pass
    bit-exactly (the custom_vjp backward rides the same ring);
  * ZeRO-1 grad bucketing (``AdamConfig.grad_buckets``, the async
    dispatch lever) tracks the unbucketed optimizer under the identity
    codec: linear ops, only clip order + concat layout differ.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import contextlib  # noqa: E402
import numpy as np, jax, jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import codecs, comms, compat, policy as policy_lib  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402

mesh = compat.make_mesh((2, 2, 2), ("data", "stage", "model"))
rng = np.random.default_rng(0)


@contextlib.contextmanager
def threepass_codecs():
    """Unfuse the ring-hop ops into explicit decode -> add -> encode
    (the pre-fusion lowering).  Same monkeypatch as
    benchmarks/bench_step_time.py — kept inline because the multidev
    scripts run with PYTHONPATH=src only."""
    def dae(self, wire, local2d, want_sum=True):
        s = kops.bq_decode_blocks(wire, self.bits) + local2d
        return kops.bq_encode_blocks(s, self.bits), s

    def da(self, wire, local2d):
        return kops.bq_decode_blocks(wire, self.bits) + local2d

    def gq_dae(self, wire, local2d, want_sum=True):
        s = self.decode_blocks(wire) + local2d
        return self.encode_blocks(s), s

    def gq_da(self, wire, local2d):
        return self.decode_blocks(wire) + local2d

    saved = [(cls, name, getattr(cls, name))
             for cls in (codecs.BqCodec, codecs.GqCodec)
             for name in ("decode_add_encode_blocks", "decode_add_blocks")]
    codecs.BqCodec.decode_add_encode_blocks = dae
    codecs.BqCodec.decode_add_blocks = da
    codecs.GqCodec.decode_add_encode_blocks = gq_dae
    codecs.GqCodec.decode_add_blocks = gq_da
    try:
        yield
    finally:
        for cls, name, fn in saved:
            setattr(cls, name, fn)


def run(fn, x):
    sm = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P("data", "stage", "model"),),
        out_specs=P("data", "stage", "model"), check_vma=False))
    return np.asarray(jax.block_until_ready(sm(x)))


def plan_for(codec_name):
    pol = policy_lib.CommPolicy(name=f"fc_{codec_name}",
                                rules=(policy_lib.Rule(codec_name),))
    return pol.compile(None)


def collectives(plan, axis, bidir=False, chunks=1):
    """The compressed collective suite under one plan/ring config."""
    def psum(a):
        with policy_lib.use_plan(plan), comms.ring_options(bidir, chunks):
            return comms.psum(a, axis, "dp")

    def rs_ag(a):
        with policy_lib.use_plan(plan), comms.ring_options(bidir, chunks):
            fl = a.reshape(-1)
            ch = comms.reduce_scatter_flat(fl, axis, "dp")
            return comms.all_gather_flat(ch, axis, fl.size,
                                         "zero").reshape(a.shape)

    def grad(a):
        with policy_lib.use_plan(plan), comms.ring_options(bidir, chunks):
            return jax.grad(
                lambda t: jnp.sum(comms.psum(t * t, axis, "dp")))(a)

    return {"psum": psum, "rs_ag": rs_ag, "grad": grad}


def check_bit_exact():
    x = jnp.asarray(rng.normal(size=(2, 2, 2, 8, 2048)).astype(np.float32))
    cases = []
    for codec_name in ("bq8", "bq4", "bq16"):
        for axis in ("data", "stage", "model", ("data", "stage")):
            cases.append((codec_name, axis, False, 1))
        cases.append((codec_name, "data", False, 3))   # chunk striping
        cases.append((codec_name, "data", True, 1))    # bidir split
        cases.append((codec_name, "data", True, 2))    # both levers
    for codec_name, axis, bidir, chunks in cases:
        plan = plan_for(codec_name)
        suite = collectives(plan, axis, bidir, chunks)
        for op, fn in suite.items():
            fused = run(fn, x)
            with threepass_codecs():
                three = run(fn, x)
            assert np.array_equal(fused, three), \
                (codec_name, axis, bidir, chunks, op,
                 np.abs(fused - three).max())
            assert np.isfinite(fused).all(), (codec_name, axis, op)
    print(f"fused == three-pass bit-exact: {len(cases)} ring configs "
          "x psum/rs_ag/grad on (data=2, stage=2, model=2)")

    # sanity: the compressed sum tracks the exact sum within codec error
    plan = plan_for("bq8")
    got = run(collectives(plan, "data")["psum"], x)
    want = np.asarray(x).sum(0, keepdims=True)
    want = np.broadcast_to(want, x.shape)
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 0.05, err
    print(f"bq8 psum vs exact: rel err {err:.2e}")


def check_grad_buckets():
    """Bucketed ZeRO-1 sync tracks the unbucketed optimizer (identity
    codec: linear collectives, only clip order/layout differ)."""
    from repro import configs
    from repro.data.pipeline import DataConfig, SyntheticCorpus
    from repro.models.model import Model
    from repro.models.params import MeshInfo
    from repro.train.optimizer import AdamConfig
    from repro.train.train_step import Trainer, batch_specs

    cfg = configs.get("gemma3-1b").reduced().replace(vocab_size=64)
    data = SyntheticCorpus(DataConfig(vocab_size=64, seq_len=32,
                                      global_batch=8))
    m42 = compat.make_mesh((4, 2), ("data", "model"))
    mi = MeshInfo.from_mesh(m42)

    def losses(grad_buckets):
        model = Model(cfg, mi)
        tr = Trainer(model, m42, scheme="baseline",
                     opt_cfg=AdamConfig(lr=3e-3, warmup=5,
                                        grad_buckets=grad_buckets))
        params, ostate, cstate = tr.init_all(jax.random.key(0))
        bspecs = batch_specs(cfg, mi)
        out = []
        for s in range(6):
            batch = {k: jax.device_put(v, NamedSharding(m42, bspecs[k]))
                     for k, v in data.batch(s).items()}
            params, ostate, cstate, met = tr.step(params, ostate, cstate,
                                                  batch)
            out.append(float(met["loss"]))
        return out

    base, bucketed = losses(1), losses(4)
    assert all(abs(a - b) < 5e-3 for a, b in zip(base, bucketed)), \
        list(zip(base, bucketed))
    print(f"grad_buckets=4 tracks unbucketed: "
          f"max |dloss| {max(abs(a - b) for a, b in zip(base, bucketed)):.1e}"
          f" over 6 steps")


def main():
    check_bit_exact()
    check_grad_buckets()
    print("FUSED RING OK")


if __name__ == "__main__":
    main()
