"""Wall-clock step time of the fused compress-and-communicate path.

Everything else in benchmarks/ prices traffic analytically from the
ledger; this module actually RUNS the jitted programs on the 8-device
host mesh and times them:

  * ``psum_<codec>_fused``      one compressed DP all-reduce of a 4 MiB
                                payload through the one-pass ring (fused
                                decode+add+encode hops, wire-only
                                intermediate hops, decode-add final hop);
  * ``psum_<codec>_threepass``  the SAME collective with the codec hops
                                unfused into explicit decode -> add ->
                                encode passes (the pre-fusion lowering,
                                bit-identical results);
  * ``train_step_*``            a full jitted compressed train step
                                (gemma3-1b reduced, zhybrid_24_8), fused
                                vs three-pass;
  * ``pipelined_step_vpp*``     a full jitted 1F1B pipeline step on a
                                (data=2, stage=2, model=2) mesh at the
                                same (pp, n_micro), plain (vpp=1) vs
                                interleaved virtual stages (vpp=2) —
                                with the analytic roofline bubble of each
                                schedule committed next to the wall time.

Timing protocol: compile + warm once, then best-of-``REPS`` mean over
``ITERS`` back-to-back calls with a trailing ``block_until_ready`` —
min-of-means is robust to scheduler noise on shared CI boxes.

``python -m benchmarks.bench_step_time --write`` refreshes the committed
``BENCH_step_time.json`` baseline; ``--check`` re-measures and fails on
large regressions (see :func:`check_against`): the fused path falling
behind three-pass, or any row blowing far past its recorded baseline.
Absolute wall times are machine-dependent, so the check leans on the
fused/three-pass RATIO and uses a loose absolute guard.
"""

import os

if "device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import contextlib        # noqa: E402
import json              # noqa: E402
import pathlib           # noqa: E402
import time              # noqa: E402

REPS, ITERS = 5, 3
TRAIN_WARMUP, TRAIN_STEPS = 2, 3
BASELINE = pathlib.Path(__file__).resolve().parents[1] / \
    "BENCH_step_time.json"
SCHEMA = "bench_step_time/v1"


@contextlib.contextmanager
def threepass_codecs():
    """Unfuse the ring-hop codec ops into explicit decode -> add -> encode
    passes (the pre-fusion lowering).  Bit-identical to the fused path —
    the fused kernels/oracles compute the same math — so timing deltas are
    pure scheduling/fusion effects."""
    from repro.core import codecs
    from repro.kernels import ops as kops

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

    saved = [(codecs.BqCodec, "decode_add_encode_blocks",
              codecs.BqCodec.decode_add_encode_blocks),
             (codecs.BqCodec, "decode_add_blocks",
              codecs.BqCodec.decode_add_blocks),
             (codecs.GqCodec, "decode_add_encode_blocks",
              codecs.GqCodec.decode_add_encode_blocks),
             (codecs.GqCodec, "decode_add_blocks",
              codecs.GqCodec.decode_add_blocks)]
    codecs.BqCodec.decode_add_encode_blocks = dae
    codecs.BqCodec.decode_add_blocks = da
    codecs.GqCodec.decode_add_encode_blocks = gq_dae
    codecs.GqCodec.decode_add_blocks = gq_da
    try:
        yield
    finally:
        for cls, name, fn in saved:
            setattr(cls, name, fn)


def _time_us(fn, *args):
    import jax
    jax.block_until_ready(fn(*args))        # compile + warm
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / ITERS)
    return best * 1e6


def _psum_us(codec_name: str, elems: int) -> float:
    """One compressed all-reduce of ``elems`` f32 per device over the
    8-ring, under whatever BqCodec hop implementation is active."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import compat, comms, policy as policy_lib

    mesh = compat.make_mesh((8,), ("x",))
    pol = policy_lib.CommPolicy(name=f"bench_{codec_name}",
                                rules=(policy_lib.Rule(codec_name),))
    plan = pol.compile(None)

    def f(a):
        with policy_lib.use_plan(plan):
            return comms.psum(a, "x", "dp")

    sm = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("x"),),
                               out_specs=P("x"), check_vma=False))
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(8, elems)).astype(np.float32))
    us = _time_us(sm, x)
    jax.clear_caches()
    return us


def _train_step_us(scheme: str) -> float:
    """Median wall time of a jitted compressed train step (gemma3-1b
    reduced, (4 data x 2 model) mesh) after warmup."""
    import statistics

    import jax
    from jax.sharding import NamedSharding

    from repro import configs
    from repro.core import compat
    from repro.data.pipeline import DataConfig, SyntheticCorpus
    from repro.models.model import Model
    from repro.models.params import MeshInfo
    from repro.train.optimizer import AdamConfig
    from repro.train.train_step import Trainer, batch_specs

    cfg = configs.get("gemma3-1b").reduced().replace(vocab_size=64)
    data = SyntheticCorpus(DataConfig(vocab_size=64, seq_len=32,
                                      global_batch=8))
    mesh = compat.make_mesh((4, 2), ("data", "model"))
    mi = MeshInfo.from_mesh(mesh)
    model = Model(cfg, mi)
    tr = Trainer(model, mesh, scheme=scheme, opt_cfg=AdamConfig(warmup=5))
    params, ostate, cstate = tr.init_all(jax.random.key(0))
    bspecs = batch_specs(cfg, mi)
    times = []
    for s in range(TRAIN_WARMUP + TRAIN_STEPS):
        batch = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
                 for k, v in data.batch(s).items()}
        jax.block_until_ready(batch)
        t0 = time.perf_counter()
        params, ostate, cstate, m = tr.step(params, ostate, cstate, batch)
        jax.block_until_ready(m)
        times.append(time.perf_counter() - t0)
    jax.clear_caches()
    return statistics.median(times[TRAIN_WARMUP:]) * 1e6


# n_micro = pp keeps the two schedules' bubbles far apart (1/3 vs 1/5)
# so the wall-time ordering is outside host-timing noise
PIPE_PP, PIPE_MICRO, PIPE_STEPS = 2, 2, 5


def _pipelined_step_us(vpp: int) -> float:
    """Median wall time of a jitted 1F1B pipeline step (qwen2-72b reduced
    deepened to 8 uniform layers, (data=2, stage=2, model=2) mesh,
    pp=PIPE_PP, n_micro=PIPE_MICRO) after warmup.  ``vpp=2`` runs the
    interleaved virtual-stage schedule — more, shorter ticks over the
    same per-rank depth."""
    import statistics

    import jax
    from jax.sharding import NamedSharding

    from repro import configs
    from repro.core import compat
    from repro.data.pipeline import DataConfig, SyntheticCorpus
    from repro.models.model import Model
    from repro.models.params import MeshInfo
    from repro.train.optimizer import AdamConfig
    from repro.train.pipeline import PipelineTrainer
    from repro.train.train_step import batch_specs

    cfg = configs.get("qwen2-72b").reduced().replace(
        n_layers=8, groups=(), vocab_size=64)
    data = SyntheticCorpus(DataConfig(vocab_size=64, seq_len=32,
                                      global_batch=8))
    mesh = compat.make_mesh((2, 2, 2), ("data", "stage", "model"))
    mi = MeshInfo.from_mesh(mesh)
    model = Model(cfg, mi, vpp=vpp)
    tr = PipelineTrainer(model, mesh, scheme="zhybrid_24_8",
                         opt_cfg=AdamConfig(warmup=5), n_micro=PIPE_MICRO)
    params, ostate, cstate = tr.init_all(jax.random.key(0))
    bspecs = batch_specs(cfg, mi)
    times = []
    for s in range(TRAIN_WARMUP + PIPE_STEPS):
        batch = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
                 for k, v in data.batch(s).items()}
        jax.block_until_ready(batch)
        t0 = time.perf_counter()
        params, ostate, cstate, m = tr.step(params, ostate, cstate, batch)
        jax.block_until_ready(m)
        times.append(time.perf_counter() - t0)
    jax.clear_caches()
    return statistics.median(times[TRAIN_WARMUP:]) * 1e6


def measure() -> dict:
    """All timed rows, fused and three-pass, in microseconds."""
    import jax

    elems = 1 << 20                                  # 4 MiB f32 per device
    rows = {}
    for codec in ("bq8", "bq4"):
        rows[f"psum_{codec}_fused_us"] = _psum_us(codec, elems)
        with threepass_codecs():
            rows[f"psum_{codec}_threepass_us"] = _psum_us(codec, elems)
    rows["train_step_zhybrid_24_8_fused_us"] = \
        _train_step_us("zhybrid_24_8")
    with threepass_codecs():
        rows["train_step_zhybrid_24_8_threepass_us"] = \
            _train_step_us("zhybrid_24_8")
    from repro.analysis.roofline import bubble_fraction
    for vpp in (1, 2):
        rows[f"pipelined_step_vpp{vpp}_us"] = _pipelined_step_us(vpp)
        # analytic (deterministic) roofline bubble of the realized
        # schedule, committed next to the wall time it explains
        rows[f"pipelined_bubble_vpp{vpp}"] = \
            bubble_fraction(PIPE_PP, PIPE_MICRO, vpp)
    return {"schema": SCHEMA, "device_count": jax.device_count(),
            "backend": jax.default_backend(), "reps": REPS, "iters": ITERS,
            "rows": {k: round(v, 1) for k, v in rows.items()}}


def check_against(baseline: dict, current: dict,
                  ratio_slack: float = 1.25,
                  abs_slack: float = 5.0) -> list:
    """Regression gates, machine-portable:

    * the fused path must stay within ``ratio_slack`` of its three-pass
      twin (fused falling meaningfully BEHIND unfused is the regression
      this benchmark exists to catch);
    * each row must stay under ``abs_slack`` x its committed baseline —
      a loose absolute guard for gross blowups (recompilation per call,
      lost overlap), generous because CI hardware varies;
    * the interleaved schedule must keep its point: the vpp=2 roofline
      bubble strictly below vpp=1 at the same (pp, n_micro), and the
      vpp=2 wall time within ``ratio_slack`` of vpp=1.
    """
    errs = []
    if baseline.get("schema") != SCHEMA:
        errs.append(f"baseline schema {baseline.get('schema')!r} != {SCHEMA}")
        return errs
    rows, base = current["rows"], baseline["rows"]
    for k in base:
        if k not in rows:
            errs.append(f"row {k} missing from current measurement")
    for k, fused in rows.items():
        if k.endswith("_fused_us"):
            three = rows.get(k.replace("_fused_", "_threepass_"))
            if three and fused > three * ratio_slack:
                errs.append(f"{k}: fused {fused:.0f}us > "
                            f"{ratio_slack}x three-pass {three:.0f}us")
        if k in base and rows[k] > base[k] * abs_slack:
            errs.append(f"{k}: {rows[k]:.0f}us > {abs_slack}x baseline "
                        f"{base[k]:.0f}us")
    b1, b2 = rows.get("pipelined_bubble_vpp1"), \
        rows.get("pipelined_bubble_vpp2")
    if b1 is not None and b2 is not None and not b2 < b1:
        errs.append(f"pipelined_bubble_vpp2 {b2:.4f} not strictly below "
                    f"vpp1 {b1:.4f}")
    t1, t2 = rows.get("pipelined_step_vpp1_us"), \
        rows.get("pipelined_step_vpp2_us")
    if t1 and t2 and t2 > t1 * ratio_slack:
        errs.append(f"pipelined_step_vpp2 {t2:.0f}us > {ratio_slack}x "
                    f"vpp1 {t1:.0f}us")
    return errs


def run():
    """run.py harness hook: CSV rows (name, us, derived)."""
    doc = measure()
    rows = []
    r = doc["rows"]
    for k, us in sorted(r.items()):
        note = "-"
        if k.endswith("_fused_us"):
            three = r.get(k.replace("_fused_", "_threepass_"))
            if three:
                note = f"fused_vs_threepass={us / three:.3f}"
        if k == "pipelined_step_vpp2_us" and r.get("pipelined_step_vpp1_us"):
            note = f"vpp2_vs_vpp1={us / r['pipelined_step_vpp1_us']:.3f}"
        rows.append((k[:-3] if k.endswith("_us") else k, us, note))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--write", action="store_true",
                    help=f"refresh the committed baseline {BASELINE.name}")
    ap.add_argument("--check", action="store_true",
                    help="re-measure and compare against the committed "
                         "baseline; nonzero exit on regression")
    args = ap.parse_args()
    doc = measure()
    for k, v in sorted(doc["rows"].items()):
        print(f"{k},{v:.1f}")
    if args.write:
        BASELINE.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
        print(f"wrote {BASELINE}")
    if args.check:
        baseline = json.loads(BASELINE.read_text())
        errs = check_against(baseline, doc)
        if errs:
            print("bench_step_time regression check FAILED:")
            for e in errs:
                print(f"  {e}")
            return 1
        print("bench_step_time regression check OK "
              f"({len(doc['rows'])} rows vs {BASELINE.name})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
