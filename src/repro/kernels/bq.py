"""Pallas TPU kernels for the block-quantization (bq) codec.

Layout contract: the ops layer reshapes every tensor into a 2-D
``(M, BLOCK=128)`` matrix, M padded to a multiple of TILE_M=8 (the
sublane count).  A grid step takes a ``(R, 128)`` VMEM block of many rows,
R from :func:`block_rows` (the VMEM its blocks take), with a ragged last
block; 128 matches the VPU lane width, so each row's max-abs reduction
stays within its lanes.

Four kernels:
  * ``bq_encode``            x -> (q_hi[, q_lo], scale)
  * ``bq_decode``            (q_hi[, q_lo], scale) -> x
  * ``bq_decode_add_encode`` fused ring-hop: encode(local + decode(wire)).
    ``want_sum=True`` additionally emits the running f32 sum; the
    intermediate hops of a ring reduce-scatter only forward the wire, so
    the default wire-only variant skips the (M, 128) f32 HBM write
    entirely.  This fusion is the TPU analogue of the paper's
    collective-level optimization of avoiding "superfluous compression
    operations" between ring hops: one HBM round-trip instead of three.
  * ``bq_decode_add``        final ring-hop: local + decode(wire), sum
    only — the reduce-scatter tail that keeps the f32 chunk and sends
    nothing further, so the re-encode is skipped too.

Rate 4 packs two values per byte: byte j of a row holds lane j in its high
nibble and lane j + 64 in its low one, two lane-half slices that Mosaic
lowers without a shape cast across lanes.

All kernels are bit-identical to the ``ref.py`` oracles (same jnp rounding
primitives): validated in ``interpret=True`` mode on CPU, compiled for a
described v5e in ``tests/test_tpu_compile.py``, and checked against the
oracles on the chip by ``chip_smoke.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import BLOCK, _INV_QMAX, _QMAX

# Padding multiple of the (M, 128) layout: one sublane group.  State sizes,
# ring chunks and paged pools are whole multiples of it; a grid step takes
# block_rows() rows, many of these.
TILE_M = 8
# VMEM for the double-buffered blocks of one grid step: half of v5e's
# 16 MiB scoped default, so the kernel body's temporaries fit beside them.
VMEM_BLOCK_BYTES = 8 * 2**20


def _hi_dtype(bits: int):
    return {4: jnp.uint8, 8: jnp.int8, 16: jnp.int16, 24: jnp.int16}[bits]


def _hi_width(bits: int) -> int:
    """Lane width of the q_hi plane (rate 4 nibble-packs 2 values/byte)."""
    return BLOCK // 2 if bits == 4 else BLOCK


def _quantize(x, bits: int):
    """Shared quantization body (must mirror ref.bq_encode_ref exactly)."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax)
    qmax = _QMAX[bits]
    q = jnp.clip(jnp.round(x / scale * qmax), -qmax, qmax).astype(jnp.int32)
    if bits == 4:
        half = q.shape[-1] // 2
        packed = ((q[..., :half] + 8) << 4) | (q[..., half:] + 8)
        return packed.astype(jnp.uint8), None, scale
    if bits == 24:
        return (q >> 8).astype(jnp.int16), (q & 0xFF).astype(jnp.uint8), scale
    return q.astype(_hi_dtype(bits)), None, scale


def _dequantize(q_hi, q_lo, scale, bits: int):
    if bits == 4:
        p = q_hi.astype(jnp.int32)
        q = jnp.concatenate([(p >> 4) - 8, (p & 0xF) - 8], axis=-1)
    elif bits == 24:
        q = q_hi.astype(jnp.int32) * 256 + q_lo.astype(jnp.int32)
    else:
        q = q_hi.astype(jnp.int32)
    return q.astype(jnp.float32) * (scale * _INV_QMAX[bits])


# --------------------------------------------------------------------------
# kernel bodies
# --------------------------------------------------------------------------

def _encode_kernel(x_ref, qhi_ref, scale_ref, *, bits):
    hi, _, scale = _quantize(x_ref[...].astype(jnp.float32), bits)
    qhi_ref[...] = hi
    scale_ref[...] = scale


def _encode24_kernel(x_ref, qhi_ref, qlo_ref, scale_ref, *, bits):
    hi, lo, scale = _quantize(x_ref[...].astype(jnp.float32), bits)
    qhi_ref[...] = hi
    qlo_ref[...] = lo
    scale_ref[...] = scale


def _decode_kernel(qhi_ref, scale_ref, x_ref, *, bits):
    x_ref[...] = _dequantize(qhi_ref[...], None, scale_ref[...], bits)


def _decode24_kernel(qhi_ref, qlo_ref, scale_ref, x_ref, *, bits):
    x_ref[...] = _dequantize(qhi_ref[...], qlo_ref[...], scale_ref[...], bits)


def _dae_kernel(qhi_ref, scale_ref, local_ref, qhi_o, scale_o, sum_o, *, bits):
    s = _dequantize(qhi_ref[...], None, scale_ref[...], bits)
    s = s + local_ref[...].astype(jnp.float32)
    hi, _, sc = _quantize(s, bits)
    qhi_o[...] = hi
    scale_o[...] = sc
    sum_o[...] = s


def _dae24_kernel(qhi_ref, qlo_ref, scale_ref, local_ref,
                  qhi_o, qlo_o, scale_o, sum_o, *, bits):
    s = _dequantize(qhi_ref[...], qlo_ref[...], scale_ref[...], bits)
    s = s + local_ref[...].astype(jnp.float32)
    hi, lo, sc = _quantize(s, bits)
    qhi_o[...] = hi
    qlo_o[...] = lo
    scale_o[...] = sc
    sum_o[...] = s


def _daew_kernel(qhi_ref, scale_ref, local_ref, qhi_o, scale_o, *, bits):
    # wire-only variant: intermediate ring hops never read the f32 sum,
    # so skip its HBM write
    s = _dequantize(qhi_ref[...], None, scale_ref[...], bits)
    s = s + local_ref[...].astype(jnp.float32)
    hi, _, sc = _quantize(s, bits)
    qhi_o[...] = hi
    scale_o[...] = sc


def _daew24_kernel(qhi_ref, qlo_ref, scale_ref, local_ref,
                   qhi_o, qlo_o, scale_o, *, bits):
    s = _dequantize(qhi_ref[...], qlo_ref[...], scale_ref[...], bits)
    s = s + local_ref[...].astype(jnp.float32)
    hi, lo, sc = _quantize(s, bits)
    qhi_o[...] = hi
    qlo_o[...] = lo
    scale_o[...] = sc


def _da_kernel(qhi_ref, scale_ref, local_ref, sum_o, *, bits):
    s = _dequantize(qhi_ref[...], None, scale_ref[...], bits)
    sum_o[...] = s + local_ref[...].astype(jnp.float32)


def _da24_kernel(qhi_ref, qlo_ref, scale_ref, local_ref, sum_o, *, bits):
    s = _dequantize(qhi_ref[...], qlo_ref[...], scale_ref[...], bits)
    sum_o[...] = s + local_ref[...].astype(jnp.float32)


# --------------------------------------------------------------------------
# pallas_call wrappers (operate on (M, 128) matrices, M % TILE_M == 0)
# --------------------------------------------------------------------------

def block_rows(m: int, dtypes) -> int:
    """Rows of one grid step for a kernel whose operands and results, one
    row each, have ``dtypes``: the largest power of two whose
    double-buffered blocks fit in ``VMEM_BLOCK_BYTES``, or all ``m`` rows.

    A row of any block takes 128 lanes in VMEM: a ``(R, 1)`` f32 scale
    costs 512 bytes a row, as much as an f32 row of values."""
    row = 2 * sum(BLOCK * jnp.dtype(d).itemsize for d in dtypes)
    r = TILE_M
    while 2 * r * row <= VMEM_BLOCK_BYTES:
        r *= 2
    return min(r, m)


def _call(kernel, bits, args, out_shape, interpret):
    """Run ``kernel`` over the rows of ``args`` in blocks of
    :func:`block_rows`; the last block may be ragged (rows are quantized
    independently, so its rows past M only give writes that are dropped)."""
    m = args[0].shape[0]
    assert m % TILE_M == 0, f"rows {m} not a multiple of {TILE_M}"
    r = block_rows(m, [a.dtype for a in args] + [o.dtype for o in out_shape])

    def spec(width):
        return pl.BlockSpec((r, width), lambda i: (i, 0))

    return pl.pallas_call(
        functools.partial(kernel, bits=bits),
        grid=(pl.cdiv(m, r),),
        in_specs=[spec(a.shape[1]) for a in args],
        out_specs=[spec(o.shape[1]) for o in out_shape],
        out_shape=out_shape,
        interpret=interpret,
    )(*args)


def _wire_shapes(m: int, bits: int):
    """Shapes of the (q_hi[, q_lo], scale) planes of M rows."""
    hi = jax.ShapeDtypeStruct((m, _hi_width(bits)), _hi_dtype(bits))
    scale = jax.ShapeDtypeStruct((m, 1), jnp.float32)
    if bits == 24:
        return [hi, jax.ShapeDtypeStruct((m, BLOCK), jnp.uint8), scale]
    return [hi, scale]


def _wire_args(q_hi, q_lo, scale, bits: int):
    return [q_hi, q_lo, scale] if bits == 24 else [q_hi, scale]


def _f32(m: int):
    return jax.ShapeDtypeStruct((m, BLOCK), jnp.float32)


def _unwire(out, bits: int):
    """(q_hi, q_lo|None, scale) of a kernel's leading wire outputs."""
    if bits == 24:
        return out[0], out[1], out[2]
    return out[0], None, out[1]


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def bq_encode_pallas(x2d: jnp.ndarray, bits: int, interpret: bool = False):
    """(M, 128) f32 -> (q_hi[, q_lo], scale). Returns (q_hi, q_lo|None, scale)."""
    kern = _encode24_kernel if bits == 24 else _encode_kernel
    out = _call(kern, bits, [x2d], _wire_shapes(x2d.shape[0], bits),
                interpret)
    return _unwire(out, bits)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def bq_decode_pallas(q_hi, q_lo, scale, bits: int, interpret: bool = False):
    """(q_hi[, q_lo], scale) -> (M, 128) f32."""
    kern = _decode24_kernel if bits == 24 else _decode_kernel
    return _call(kern, bits, _wire_args(q_hi, q_lo, scale, bits),
                 [_f32(q_hi.shape[0])], interpret)[0]


@functools.partial(jax.jit,
                   static_argnames=("bits", "want_sum", "interpret"))
def bq_decode_add_encode_pallas(q_hi, q_lo, scale, local, bits: int,
                                want_sum: bool = True,
                                interpret: bool = False):
    """Fused ring hop. Returns (q_hi', q_lo'|None, scale', sum_f32|None).

    ``want_sum=False`` selects the wire-only kernel (no f32 sum output) —
    the shape intermediate reduce-scatter hops want."""
    m = q_hi.shape[0]
    if bits == 24:
        kern = _dae24_kernel if want_sum else _daew24_kernel
    else:
        kern = _dae_kernel if want_sum else _daew_kernel
    out = _call(kern, bits, _wire_args(q_hi, q_lo, scale, bits) + [local],
                _wire_shapes(m, bits) + ([_f32(m)] if want_sum else []),
                interpret)
    return (*_unwire(out, bits), out[-1] if want_sum else None)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def bq_decode_add_pallas(q_hi, q_lo, scale, local, bits: int,
                         interpret: bool = False):
    """Final ring hop: local + decode(wire) -> (M, 128) f32 sum only."""
    kern = _da24_kernel if bits == 24 else _da_kernel
    return _call(kern, bits, _wire_args(q_hi, q_lo, scale, bits) + [local],
                 [_f32(q_hi.shape[0])], interpret)[0]


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def bq_gather_decode_pallas(q_hi, q_lo, scale, idx, bits: int,
                            interpret: bool = False):
    """Paged decode-read: gather quantized rows by block index, then run
    the tiled dequantize kernel over the gathered planes.

    The gather itself stays an XLA dynamic-gather over the COMPRESSED
    planes (the HBM traffic is ``bits``-rate either way); only the
    dequantize arithmetic is kernelized — on the gathered wire bytes, so
    the decoded f32 never round-trips through HBM at rest.  Pool layout
    contract (see :mod:`repro.serve.paged_kv`): ``q_hi`` is
    ``(n_blocks, ..., hi_width)``, ``scale`` is ``(n_blocks, ..., 1)``
    with one scale per 128-element row, same row order.  Returns f32 of
    shape ``idx.shape + pool.shape[1:-1] + (BLOCK,)``.
    """
    take = lambda a: None if a is None else jnp.take(a, idx, axis=0)
    hi, lo, sc = take(q_hi), take(q_lo), take(scale)
    out_shape = sc.shape[:-1] + (BLOCK,)
    m = sc.size
    m_pad = -(-m // TILE_M) * TILE_M
    hi2 = hi.reshape(m, _hi_width(bits))
    lo2 = None if lo is None else lo.reshape(m, BLOCK)
    sc2 = sc.reshape(m, 1)
    if m_pad != m:  # all-zero rows with scale 1 decode to zero
        hi2 = jnp.pad(hi2, ((0, m_pad - m), (0, 0)))
        lo2 = None if lo2 is None else jnp.pad(lo2, ((0, m_pad - m), (0, 0)))
        sc2 = jnp.pad(sc2, ((0, m_pad - m), (0, 0)), constant_values=1.0)
    x2 = bq_decode_pallas(hi2, lo2, sc2, bits, interpret=interpret)
    return x2[:m].reshape(out_shape)
