"""End-to-end driver: train a ~100M-param dense model for a few hundred
steps under the paper's ZHybrid scheme, with checkpointing + straggler
monitoring + a mid-run elastic restart onto a different mesh.

This is the (b) end-to-end example from the assignment.  It wraps the real
production entrypoint (repro.launch.train) the same way a cluster launcher
would — two "incarnations" of the job, the second resuming the first's
checkpoint on a different topology.

    PYTHONPATH=src python examples/train_small_e2e.py [--steps 300]

(On this CPU container the default is scaled down; pass --full for the
~100M config if you have the patience.)
"""

import argparse
import pathlib
import subprocess
import sys
import tempfile

ROOT = pathlib.Path(__file__).parent.parent


def run_incarnation(args, steps, dp, tp, ckpt, resume):
    cmd = [sys.executable, "-m", "repro.launch.train",
           "--arch", "gemma3-1b",
           "--dp", str(dp), "--tp", str(tp),
           "--steps", str(steps),
           "--seq", str(args.seq), "--global-batch", str(args.batch),
           "--scheme", "zhybrid_16_8",
           "--ckpt-dir", ckpt, "--ckpt-every", "50"]
    if not args.full:
        cmd.append("--reduced")
    if resume:
        cmd.append("--resume")
    env = dict(PYTHONPATH=str(ROOT / "src"), PATH="/usr/bin:/bin")
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "PYTHONPATH")})
    env["JAX_PLATFORMS"] = "cpu"          # the meshes run on host devices
    print("+", " ".join(cmd))
    proc = subprocess.run(cmd, env=env, text=True, capture_output=True)
    print(proc.stdout)
    if proc.returncode != 0:
        print(proc.stderr[-3000:])
        raise SystemExit(proc.returncode)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--full", action="store_true",
                    help="~100M params (slow on CPU)")
    args = ap.parse_args()
    with tempfile.TemporaryDirectory() as ckpt:
        half = args.steps // 2
        print(f"=== incarnation 1: dp=2 tp=4, steps 0..{half} ===")
        run_incarnation(args, half, 2, 4, ckpt, resume=False)
        print(f"=== simulated failure; elastic restart on dp=4 tp=2 ===")
        run_incarnation(args, args.steps - half, 4, 2, ckpt, resume=True)
    print("e2e train + elastic restart complete")


if __name__ == "__main__":
    main()
