"""Process set-up shared by the launchers and ``chip_smoke.py``: which
devices JAX may give a run, and where its persistent compile cache lives.

Nothing here imports JAX at module level: the CPU device count has to be
in ``XLA_FLAGS`` before JAX starts its backends.
"""

from __future__ import annotations

import os
import pathlib

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/runtime.py);
# a fixed path, because the path is part of every cache key
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def force_cpu_devices(n: int, asked: bool = False) -> None:
    """Give JAX's CPU backend ``n`` devices, on a run that is on the CPU.

    A run is on the CPU when ``JAX_PLATFORMS=cpu``, or when it asked for
    host devices itself (``asked``: the training launcher's
    ``--host-devices``), which pins ``JAX_PLATFORMS=cpu``.  Any other run
    gets the devices its platform has: an accelerator that fails to start
    is an error, never a silent move onto CPU devices."""
    if asked:
        if os.environ.get("JAX_PLATFORMS", "cpu") != "cpu":
            raise ValueError("host devices were asked for, but JAX_PLATFORMS="
                             f"{os.environ['JAX_PLATFORMS']!r}")
        os.environ["JAX_PLATFORMS"] = "cpu"
    if n > 1 and os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n} "
            + os.environ.get("XLA_FLAGS", ""))


def require_devices(n: int) -> None:
    """Print the devices JAX found; fail unless there are at least ``n``."""
    import jax
    devs = jax.devices()
    print(f"devices: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    if len(devs) < n:
        raise SystemExit(
            f"the mesh needs {n} devices and JAX found {len(devs)} "
            f"{devs[0].platform} device(s); for {n} host CPU devices run "
            "with JAX_PLATFORMS=cpu")


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads that itself), and
    otherwise at :data:`CACHE_DIR`."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
