#!/usr/bin/env python3
"""Show that the training path runs on a TPU.

    python3 chip_smoke.py               # one chip: kernels, then training
    python3 chip_smoke.py --four-chips  # dp=2 x tp=2 on a four-chip host

Everything runs in this one process, through the entry points a user
calls.  On one chip the script checks every bq codec kernel (encode,
decode, fused decode-add-encode with and without the running sum,
decode-add and gather-decode, at rates 4/8/16/24) and the low-rank matmul
against their oracles at the size of one minitron-4b FFN gradient (the bq
kernels again with 8 more rows, a partial last row block), then
trains minitron-4b through ``repro.launch.train.run``.  With
``--four-chips`` it runs only the mesh phase: the same model on a
dp=2 x tp=2 mesh under the uncompressed baseline and two compressed
policies.  Each training run is held to a control that takes the same
batches at learning rate 0: the baseline must learn, and each compressed
policy must keep half of what the baseline learned.

The model keeps every published width of minitron-4b and is cut to one
chip's share: 4 of its 32 layers (all layers are alike, so that is a whole
period) and 32,000 of its 256,000 vocabulary rows (the eighth one chip
holds under 8-way tensor parallelism).  Weights are random from a seed.

The last line of standard output is ``{"ok": true, "device": {...}}``,
printed only when every phase passed.  Without a TPU, or when any phase
fails, the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import pathlib
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parent

ARCH = "minitron-4b"
LAYERS = 4        # of 32
VOCAB = 32_000    # of 256,000
SEQ = 2048
# compiled.memory_analysis() of this step for one v5e (opt state at 8
# bits): batch 4 peaks at 14.71 GB of the chip's 17.18 GB (16 GiB), and
# batch 8 at 18.90 GB, which does not fit
BATCH = 4
# twice the lr warmup (AdamConfig.warmup), so that half the steps train at
# the full rate
WARMUP = 10
STEPS = 20
# One chip holds half the four-chip batch.  At the launcher's default lr
# (1e-3) its loss fell below the untrained model's during the warmup, at
# lr up to 7e-4, and rose far above it once the warmup ended (11.61
# against 10.99 at step 12); the 32-bit optimizer state does the same at
# this width.  So one chip trains at 3e-4, and for 30 steps, so that 20
# of them count towards the gain.
ONE_CHIP_LR = 3e-4
ONE_CHIP_STEPS = 30
# one FFN gradient of minitron-4b (3072 x 9216) in rows of 128 values
KERNEL_ROWS = 3072 * 9216 // 128
BITS = (4, 8, 16, 24)

# The first loss is that of random logits: the head is N(0, s^2) and the
# final norm leaves each token's features at unit rms, so a token's logits
# have variance d_model * s^2 and the expected loss is
# ln(V) + d_model * s^2 / 2.  0.3 nats is wide of the batch's sampling
# noise and far inside what a wrong head, norm or loss would give.
FIRST_LOSS_TOL = 0.3
# A compressed policy's first loss against the baseline's.  Before any
# update only the tp codec (bq16, error <= 1/65534 of a block's max)
# separates them: 0.01 nats.
FIRST_STEP_TOL = 0.01
# Training is judged against a control that takes the same batches with
# --lr 0: its losses are the untrained model's on each batch, so control -
# loss is what training bought on that batch.  A training run's loss
# swings by 0.3 nats from step to step early on, more than 20 steps lower
# it, so neither one step nor the first against the last can tell a run
# that learns from one that does not.  The gain is control - loss
# averaged over the steps after the warmup.  A run that does not learn,
# with no update or a zeroed gradient, is the control itself and has a
# gain of 0.  The baseline's gain must exceed GAIN_SE standard errors of
# its per-step values, and a compressed policy must keep GAIN_SHARE of
# the baseline's gain.
GAIN_SE = 3.0
GAIN_SHARE = 0.5
# low-rank matmul: f32 against an f32 oracle summed in another order stays
# near 1e-6 of the largest output; one bf16 pass would be near 1e-3
LOWRANK_TOL = 1e-4

POLICIES = (
    ("baseline", ["--scheme", "baseline"]),
    ("zhybrid_16_8", ["--scheme", "zhybrid_16_8"]),
    ("zhybrid_16_8 dp@zero1_grad*=ef:bq4",
     ["--scheme", "zhybrid_16_8", "--codec-for", "dp@zero1_grad*=ef:bq4"]),
)
CONTROL = ("baseline at lr 0", ["--scheme", "baseline", "--lr", "0"])


class SmokeError(Exception):
    """A phase failed."""


def cut_config():
    from repro import configs
    full = configs.get(ARCH)
    cfg = full.replace(n_layers=LAYERS, vocab_size=VOCAB)
    print(f"model: {ARCH}, cut to one chip's share: layers "
          f"{full.n_layers} -> {cfg.n_layers} (one whole period), vocab "
          f"{full.vocab_size} -> {cfg.vocab_size} (1/8: one chip under "
          f"8-way tensor parallelism); widths as published: d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads / {cfg.n_kv_heads} kv heads "
          f"x {cfg.head_dim_}, {cfg.mlp_kind} FFN {cfg.d_ff}", flush=True)
    return cfg


def check_kernels(rows: int, backend: str) -> None:
    """Every bq kernel and the low-rank matmul against its oracle on
    ``rows`` rows of 128 values.  The bq kernels must be bit-identical
    to the ``kernels/ref.py`` oracles (``backend="jnp"``), on ``rows`` and
    again on ``rows + 8``, which ends in a partial row block."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import lowrank, ops

    k = jax.random.split(jax.random.key(0), 4)
    # block scales over six decades, and all-zero blocks
    x_all = jax.random.normal(k[0], (rows + 8, 128), jnp.float32) \
        * jnp.exp(3.0 * jax.random.normal(k[1], (rows + 8, 1)))
    x_all = x_all.at[:8].set(0.0)
    local_all = jax.random.normal(k[2], (rows + 8, 128), jnp.float32)
    failed = []

    def same(name, got, want):
        got, want = jax.tree.leaves(got), jax.tree.leaves(want)
        if len(got) != len(want):
            failed.append(name)
            print(f"  {name}: {len(got)} outputs, oracle has {len(want)}")
            return
        bad = sum(int(jnp.sum(g != w)) if g.shape == w.shape else g.size
                  for g, w in zip(got, want))
        print(f"  {name}: " + ("bit-identical" if not bad
                               else f"{bad} values differ"), flush=True)
        if bad:
            failed.append(name)

    for n in (rows, rows + 8):
        x, local = x_all[:n], local_all[:n]
        idx = jax.random.randint(k[3], (n // 64,), 0, n // 8)
        print(f"kernels ({backend}) against their oracles, {n} rows x 128:")
        for bits in BITS:
            wire = ops.bq_encode_blocks(x, bits, backend="jnp")
            same(f"bq{bits} encode", ops.bq_encode_blocks(x, bits, backend),
                 wire)
            same(f"bq{bits} decode",
                 ops.bq_decode_blocks(wire, bits, backend),
                 ops.bq_decode_blocks(wire, bits, "jnp"))
            for want_sum in (True, False):
                same(f"bq{bits} decode-add-encode want_sum={want_sum}",
                     ops.bq_decode_add_encode_blocks(
                         wire, local, bits, backend, want_sum=want_sum),
                     ops.bq_decode_add_encode_blocks(
                         wire, local, bits, "jnp", want_sum=want_sum))
            same(f"bq{bits} decode-add",
                 ops.bq_decode_add_blocks(wire, local, bits, backend),
                 ops.bq_decode_add_blocks(wire, local, bits, "jnp"))
            pool = {key: None if a is None else a.reshape(n // 8, 8, -1)
                    for key, a in wire.items()}
            same(f"bq{bits} gather-decode",
                 ops.bq_gather_decode(pool, idx, bits, backend),
                 ops.bq_gather_decode(pool, idx, bits, "jnp"))

    x = x_all[:rows]
    # the plr codec's three products on this gradient's matrix view
    m, ncols = lowrank.mat_shape(rows * 128)
    mat = x.reshape(m, ncols)
    q = lowrank.init_factor(ncols, 8)
    phat = lowrank.orthonormalize(lowrank.matmul_ref(mat, q))
    for name, a, b in (("M @ Q", mat, q), ("M^T @ P", mat.T, phat),
                       ("P @ Q^T", phat, q.T)):
        want = lowrank.matmul_ref(a, b)
        err = float(jnp.max(jnp.abs(lowrank.matmul(a, b, backend) - want))
                    / jnp.max(jnp.abs(want)))
        ok = err <= LOWRANK_TOL
        print(f"  lowrank {name} {a.shape}@{b.shape}: max error "
              f"{err:.3g} of the largest value (limit {LOWRANK_TOL})")
        if not ok:
            failed.append(f"lowrank {name}")
    if failed:
        raise SmokeError(f"kernels differ from their oracles: {failed}")


def train(cfg, batch: int, seq: int, steps: int, argv: list) -> dict:
    from repro.launch import train as launcher
    args = launcher.parse_args(
        ["--arch", ARCH, "--steps", str(steps), "--seq", str(seq),
         "--global-batch", str(batch), "--seed", "0"] + argv)
    print(f"train {' '.join(argv)}: global batch {batch} x {seq} tokens, "
          f"{steps} steps", flush=True)
    return launcher.run(args, cfg=cfg)


def report_steps(hist: dict) -> None:
    times = hist["step_time"]
    print(f"  compile (lower + compile of the step): "
          f"{hist['compile_time']:.3f}s")
    print("  step times (end at block_until_ready), s: "
          + ", ".join(f"{t:.4f}" for t in times))
    if len(times) > 1:
        print(f"  median of steps 1..{len(times) - 1}: "
              f"{statistics.median(times[1:]):.4f}s")
    print("  losses: " + ", ".join(f"{v:.4f}" for v in hist["loss"]))


def check_losses(cfg, losses: list) -> None:
    from repro.models import layers
    s = layers.lm_head_plan(cfg)["lm_head"]["w"].scale
    want = math.log(cfg.vocab_size) + cfg.d_model * s * s / 2
    print(f"  first loss {losses[0]:.4f}, expected {want:.4f} "
          f"(ln {cfg.vocab_size} + {cfg.d_model} x {s}^2 / 2) "
          f"+- {FIRST_LOSS_TOL}; last {losses[-1]:.4f}")
    if not all(math.isfinite(v) for v in losses):
        raise SmokeError(f"non-finite loss: {losses}")
    if abs(losses[0] - want) > FIRST_LOSS_TOL:
        raise SmokeError(f"first loss {losses[0]} is not {want} "
                         f"+- {FIRST_LOSS_TOL}")


def gain(losses: list, control: list) -> tuple[float, float]:
    """Mean and standard error of ``control - losses`` after the warmup."""
    d = [c - v for c, v in zip(control[WARMUP:], losses[WARMUP:])]
    return statistics.mean(d), statistics.stdev(d) / math.sqrt(len(d))


def check_learned(name: str, losses: list, control: list) -> float:
    """The gain of a run that must learn; SmokeError unless it exceeds
    GAIN_SE standard errors."""
    g, se = gain(losses, control)
    print(f"  {name}: gain over the lr 0 control, steps {WARMUP}.."
          f"{len(losses) - 1}: {g:.4f} nats, standard error {se:.4f} "
          f"(must exceed {GAIN_SE} x that)")
    if not g > GAIN_SE * se:
        raise SmokeError(f"{name} did not learn: gain {g} nats, standard "
                         f"error {se}")
    return g


def check_kept(name: str, losses: list, control: list,
               base_gain: float) -> None:
    """SmokeError unless a compressed run kept GAIN_SHARE of the
    baseline's gain."""
    g, _ = gain(losses, control)
    print(f"  {name}: gain {g:.4f} nats, {g / base_gain:.3f} of the "
          f"baseline's (must be at least {GAIN_SHARE})")
    if not g >= GAIN_SHARE * base_gain:
        raise SmokeError(f"{name} lost the baseline's training: gain {g} "
                         f"nats against {base_gain}")


def run_phases(*phases) -> None:
    """Run every phase even after one fails, so that one run on the chip
    reports them all; then raise a SmokeError naming each failure."""
    failed = []
    for phase in phases:
        try:
            phase()
        except SmokeError as e:
            print(f"FAILED: {e}", flush=True)
            failed.append(str(e))
    if failed:
        raise SmokeError("; ".join(failed))


def one_chip(cfg, batch: int = BATCH, seq: int = SEQ,
             steps: int = ONE_CHIP_STEPS, kernel_rows: int = KERNEL_ROWS,
             kernel_backend: str = "pallas") -> None:
    def training():
        import jax
        bits = ["--opt-state-bits", "8"]
        hist = train(cfg, batch, seq, steps,
                     ["--scheme", "baseline", "--lr", str(ONE_CHIP_LR)]
                     + bits)
        report_steps(hist)
        stats = jax.devices()[0].memory_stats()
        if stats:
            print(f"  peak_bytes_in_use {stats['peak_bytes_in_use']} of "
                  f"bytes_limit {stats.get('bytes_limit')}")
        else:
            print("  peak_bytes_in_use: not reported by this backend")
        control = train(cfg, batch, seq, steps, CONTROL[1] + bits)["loss"]
        print("  control losses: " + ", ".join(f"{v:.4f}" for v in control))
        check_losses(cfg, hist["loss"])
        check_learned("baseline", hist["loss"], control)

    run_phases(lambda: check_kernels(kernel_rows, kernel_backend), training)


def placement(tree) -> tuple[dict, int]:
    """Bytes each device holds of ``tree``'s shards, and the tree's own
    bytes."""
    import jax
    per_dev, total = {}, 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_dev[shard.device.id] = (per_dev.get(shard.device.id, 0)
                                        + shard.data.nbytes)
    return per_dev, total


def four_chips(cfg, batch: int = 2 * BATCH, seq: int = SEQ,
               steps: int = STEPS) -> None:
    losses = {}

    def training(name, argv):
        hist = train(cfg, batch, seq, steps,
                     ["--dp", "2", "--tp", "2"] + argv)
        report_steps(hist)
        losses[name] = hist["loss"]
        print("  wire bytes per device per step (ledger counts): "
              + ", ".join(f"{d}={b:.0f}"
                          for d, b in sorted(hist["wire_bytes"].items())))
        for what in ("params", "opt_state"):
            per_dev, total = placement(hist[what])
            print(f"  {what}: {total} bytes, per device "
                  + ", ".join(f"{d}:{b}" for d, b in sorted(per_dev.items())))
            if len(per_dev) != 4 or max(per_dev.values()) > 0.75 * total:
                raise SmokeError(f"{name}: {what} is not sharded over 4 "
                                 f"devices: {per_dev} of {total} bytes")

    def agreement():
        base, control = losses["baseline"], losses[CONTROL[0]]
        check_losses(cfg, base)
        base_gain = check_learned("baseline", base, control)
        for name, got in losses.items():
            if name in ("baseline", CONTROL[0]):
                continue
            d0 = abs(got[0] - base[0])
            print(f"  {name}: |first - baseline| {d0:.2e} (limit "
                  f"{FIRST_STEP_TOL})")
            if d0 > FIRST_STEP_TOL or \
                    not all(math.isfinite(v) for v in got):
                raise SmokeError(f"{name} strays from the baseline: {got} "
                                 f"vs {base}")
            check_kept(name, got, control, base_gain)

    run_phases(*(functools.partial(training, name, argv)
                 for name, argv in POLICIES + (CONTROL,)))
    run_phases(agreement)


def tpu_devices(count: int):
    """The devices JAX found; SystemExit unless ``count`` TPUs."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX found only {d.platform} "
                         "devices")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX found "
                         f"{len(devs)}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the dp=2 x tp=2 phase, on a four-chip host")
    args = ap.parse_args(argv)
    devs = tpu_devices(4 if args.four_chips else 1)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import runtime
    except ImportError as e:
        raise SystemExit(f"chip_smoke: the repo's code is not next to this "
                         f"script ({e})") from None
    runtime.use_compile_cache()
    cfg = cut_config()
    try:
        if args.four_chips:
            four_chips(cfg)
        else:
            one_chip(cfg)
    except SmokeError as e:
        raise SystemExit(f"chip_smoke: FAILED: {e}") from None
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
