#!/usr/bin/env python3
"""Compile a cell's training step for a described TPU v5e and print what
its memory analysis says, with no chip attached.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py --workload <cell> \
        [--global-batch N ...]

The step is the one ``bench/run.py`` drives (same mesh, model, policy and
optimizer), lowered from shapes on the devices of a described ``v5e:2x2``
topology with the Pallas codec kernels, so the TPU compiler refuses here
what it would refuse on the chip.  ``--global-batch`` compiles other batch
sizes of the cell, to size a batch that fits.  Per device it prints the
arguments, outputs, aliased and temporary bytes, and their total beside
the chip's 16 GiB.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

HBM = 16 * 2 ** 30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--global-batch", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.lib import harness
    harness.use_program(ROOT)
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.kernels import ops
    from repro.launch import mesh as mesh_lib
    from repro.models.params import Pv

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # the launcher's make_mesh takes the first devices JAX reports; hand it
    # the described chips instead
    mesh_lib._first_devices = lambda shape: topo.devices[:_prod(shape)]
    ops.set_default_backend("pallas")
    cell = harness.Cell(args.workload)
    for gb in args.global_batch or [cell.traffic["global_batch"]]:
        cell.traffic["global_batch"] = gb
        prog = harness.Program(cell)
        named = lambda sp: NamedSharding(prog.mesh, sp)      # noqa: E731
        is_pv = lambda x: isinstance(x, Pv)                   # noqa: E731
        params = jax.tree.map(
            lambda s, sp: Pv(jax.ShapeDtypeStruct(s.v.shape, s.v.dtype,
                                                  sharding=named(sp.v)),
                             s.spec),
            prog.model.structs(), prog.model.specs(), is_leaf=is_pv)
        ostate = jax.eval_shape(prog.trainer.opt_init, params)
        ospecs = prog.trainer.opt_state_specs()
        ostate = jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=named(sp)),
            ostate, ospecs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        cstate = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=named(PartitionSpec(tuple(prog.mi.all_axes)))),
            prog.trainer.codec_structs())
        seq = cell.traffic["seq"]
        batch = {k: jax.ShapeDtypeStruct((gb, seq), "int32",
                                         sharding=named(prog.bspecs[k]))
                 for k in ("tokens", "labels")}
        try:
            compiled = prog.trainer.step.lower(params, ostate, cstate,
                                               batch).compile()
        except jax.errors.JaxRuntimeError as e:
            print(f"{args.workload} global batch {gb} x {seq}: refused: "
                  f"{str(e).splitlines()[0]}", flush=True)
            continue
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"{args.workload} global batch {gb} x {seq}: arguments "
              f"{m.argument_size_in_bytes}, outputs "
              f"{m.output_size_in_bytes}, aliased {m.alias_size_in_bytes}, "
              f"temporaries {m.temp_size_in_bytes} bytes a device; total "
              f"{total} ({total / HBM:.1%} of 16 GiB)", flush=True)
    return 0


def _prod(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


if __name__ == "__main__":
    sys.exit(main())
