"""Serving example: batched, paged-continuous, and disaggregated modes.

Thin wrapper over the production entrypoint (repro.launch.serve) showing
the public API: a batched prefill+decode pass under an uncompressed and a
compressed scheme, a continuous-batching pass over a paged KV pool
quantized at rest (--kv-codec bq8), and a prefill/decode disaggregation
pass whose per-request KV handoff rides the compressed ``kv`` dimension.

    PYTHONPATH=src python examples/serve_batched.py
"""

import pathlib
import subprocess
import sys
import os

ROOT = pathlib.Path(__file__).parent.parent

RUNS = (
    ("batched baseline",
     ["--dp", "2", "--tp", "4", "--batch", "4",
      "--scheme", "baseline"]),
    ("batched compressed",
     ["--dp", "2", "--tp", "4", "--batch", "4",
      "--scheme", "zhybrid_16_8"]),
    ("paged continuous batching, KV quantized at rest",
     ["--mode", "paged", "--slots", "2", "--batch", "6",
      "--block-tokens", "4", "--kv-codec", "bq8"]),
    ("disaggregated prefill/decode, compressed KV handoff",
     ["--mode", "disagg", "--dp", "2", "--tp", "2", "--batch", "4",
      "--kv-codec", "bq16"]),
)


def main():
    for title, extra in RUNS:
        cmd = [sys.executable, "-m", "repro.launch.serve",
               "--arch", "gemma3-1b", "--reduced",
               "--prompt-len", "16", "--gen", "6"] + extra
        env = dict(os.environ)
        env["PYTHONPATH"] = str(ROOT / "src")
        env["JAX_PLATFORMS"] = "cpu"      # the meshes run on host devices
        env.pop("XLA_FLAGS", None)
        print(f"=== {title} ===")
        proc = subprocess.run(cmd, env=env, text=True, capture_output=True)
        print(proc.stdout)
        if proc.returncode != 0:
            print(proc.stderr[-3000:])
            raise SystemExit(proc.returncode)


if __name__ == "__main__":
    main()
