"""Compile-only tests of the Pallas codec kernels for a described TPU v5e.

Interpret mode on the CPU checks what the kernels compute; only the TPU's
own compiler (Mosaic) says whether it accepts them.  These tests compile
every bq kernel at rates 4/8/16/24 and the low-rank matmul at the size of
one minitron-4b FFN gradient (3072 x 9216 values), and the bq8 codec and
the widest kernel at row counts that end in a partial row block, for a
``v5e:2x2`` topology that is described, not attached.  Nothing runs.

The topology is described inside a module-scoped fixture, never while the
module is imported, so every test worker collects the same tests and only
the worker that runs this file loads the TPU library.  The kernels are
called with ``backend="pallas"``: ``auto`` would see the CPU here and take
the jnp oracles.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import lowrank, ops
from repro.kernels.bq import _hi_dtype, _hi_width

ROWS = 3072 * 9216 // 128    # one minitron-4b FFN gradient, rows of 128
# the 8-bit Adam state of the minitron-4b cell: 523,791,360 values, ragged
# for every row block above 8
STATE_ROWS = 523_791_360 // 128
BITS = (4, 8, 16, 24)
KERNELS = ("encode", "decode", "decode_add_encode", "decode_add_encode_wire",
           "decode_add", "gather_decode")


@pytest.fixture(scope="module")
def one_chip():
    """A sharding on the first chip of a described v5e:2x2, with JAX's
    persistent compilation cache off: a compile for a chip that is not
    attached is written to it but cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _bq_case(kernel, bits, sharding, rows=ROWS):
    """(function, argument shapes) of one bq kernel through the ops layer."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def wire(lead=(rows,)):
        return {"q_hi": s(lead + (_hi_width(bits),), _hi_dtype(bits)),
                "q_lo": s(lead + (128,), jnp.uint8) if bits == 24 else None,
                "scale": s(lead + (1,), jnp.float32)}

    x = s((rows, 128), jnp.float32)
    if kernel == "encode":
        return lambda x: ops.bq_encode_blocks(x, bits, "pallas"), (x,)
    if kernel == "decode":
        return lambda w: ops.bq_decode_blocks(w, bits, "pallas"), (wire(),)
    if kernel in ("decode_add_encode", "decode_add_encode_wire"):
        want_sum = kernel == "decode_add_encode"
        return (lambda w, x: ops.bq_decode_add_encode_blocks(
            w, x, bits, "pallas", want_sum=want_sum), (wire(), x))
    if kernel == "decode_add":
        return (lambda w, x: ops.bq_decode_add_blocks(w, x, bits, "pallas"),
                (wire(), x))
    idx = s((rows // 64,), jnp.int32)       # a paged pool of 8-row blocks
    return (lambda w, i: ops.bq_gather_decode(w, i, bits, "pallas"),
            (wire((rows // 8, 8)), idx))


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("kernel", KERNELS)
def test_bq_kernel_compiles_for_v5e(one_chip, kernel, bits):
    fn, args = _bq_case(kernel, bits, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [ROWS + 8, STATE_ROWS])
@pytest.mark.parametrize("kernel,bits", [
    ("encode", 8), ("decode", 8),
    ("decode_add_encode", 24)])      # the widest: most bytes a row
def test_bq_kernel_compiles_for_v5e_at_ragged_rows(one_chip, kernel, bits,
                                                    rows):
    """Row counts that end in a partial row block, the Adam state's
    among them: Mosaic takes each kernel's block inside its VMEM."""
    fn, args = _bq_case(kernel, bits, one_chip, rows)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the plr codec's three products on the same gradient's matrix view:
# M @ Q, M^T @ P (contraction over all 55,296 rows) and P @ Q^T
_M, _N = lowrank.mat_shape(ROWS * 128)


@pytest.mark.parametrize("a_shape,b_shape", [
    ((_M, _N), (_N, 8)), ((_N, _M), (_M, 8)), ((_M, 8), (8, _N))])
def test_lowrank_matmul_compiles_for_v5e(one_chip, a_shape, b_shape):
    a = jax.ShapeDtypeStruct(a_shape, jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct(b_shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b: lowrank.matmul(a, b, "pallas")).lower(a, b).compile()
    assert "tpu_custom_call" in compiled.as_text()
