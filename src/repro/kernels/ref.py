"""Pure-jnp oracles for the block-quantization (bq) codec kernels.

The ``bq`` codec is the TPU-native analogue of fixed-rate ZFP (see DESIGN.md §2):
values are grouped into blocks of ``BLOCK`` consecutive elements, each block is
scaled by its max-abs value, and mantissas are stored as ``bits``-bit
two's-complement integers.  Fixed rate ==> static shapes; block-local scale
==> bounded relative error, exactly the two ZFP properties the paper relies on.

Every Pallas kernel in ``bq.py`` must match these references bit-for-bit
(same jnp rounding ops), which the kernel test-suite asserts.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

BLOCK = 128  # lane-width-aligned compression block (elements per scale)

# mantissa range per supported rate (bits/value on the wire, excl. scale)
# rate 4 is nibble-packed (two values per uint8 byte)
_QMAX = {4: 7, 8: 127, 16: 32767, 24: 8388607}
# decode uses a precomputed f32-exact reciprocal (as a python scalar, so
# pallas kernels don't capture array constants) so eager/jit/pallas paths all
# do a single multiply chain and stay bit-identical (XLA may otherwise
# reassociate the divide).
_INV_QMAX = {b: float(np.float32(1.0) / np.float32(q)) for b, q in _QMAX.items()}


def _check_bits(bits: int) -> None:
    if bits not in _QMAX:
        raise ValueError(f"bq codec supports bits in {sorted(_QMAX)}, got {bits}")


def block_scale_ref(x: jnp.ndarray) -> jnp.ndarray:
    """Per-block scale = max|x| over the last axis, guarded against all-zero blocks.

    x: (..., BLOCK) float32 -> (..., 1) float32
    """
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    return jnp.where(amax == 0.0, 1.0, amax)


def bq_encode_ref(x: jnp.ndarray, bits: int):
    """Quantize (..., BLOCK) float32 into fixed-rate mantissas + per-block scale.

    Returns (q_hi, q_lo, scale):
      bits=4  -> q_hi uint8 (..., BLOCK/2): byte j holds lane j in its high
                 nibble and lane j + BLOCK/2 in its low nibble, q_lo None
      bits=8  -> q_hi int8  (..., BLOCK), q_lo None
      bits=16 -> q_hi int16 (..., BLOCK), q_lo None
      bits=24 -> q_hi int16 (top 16 bits), q_lo uint8 (bottom 8 bits)
      scale   -> float32 (..., 1)
    """
    _check_bits(bits)
    x = x.astype(jnp.float32)
    scale = block_scale_ref(x)
    qmax = _QMAX[bits]
    q = jnp.clip(jnp.round(x / scale * qmax), -qmax, qmax).astype(jnp.int32)
    if bits == 4:
        # nibble-pack lane j with lane j + BLOCK/2: (q+8) fits 4 bits, and
        # pairing the two lane halves needs no shape cast across lanes
        half = q.shape[-1] // 2
        packed = ((q[..., :half] + 8) << 4) | (q[..., half:] + 8)
        return packed.astype(jnp.uint8), None, scale
    if bits == 8:
        return q.astype(jnp.int8), None, scale
    if bits == 16:
        return q.astype(jnp.int16), None, scale
    # bits == 24: split the 24-bit mantissa across an int16 and a uint8 plane.
    hi = (q >> 8).astype(jnp.int16)
    lo = (q & 0xFF).astype(jnp.uint8)
    return hi, lo, scale


def bq_decode_ref(q_hi: jnp.ndarray, q_lo, scale: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Inverse of :func:`bq_encode_ref` -> float32 (..., BLOCK)."""
    _check_bits(bits)
    if bits == 4:
        p = q_hi.astype(jnp.int32)
        q = jnp.concatenate([(p >> 4) - 8, (p & 0xF) - 8], axis=-1)
    elif bits == 24:
        q = q_hi.astype(jnp.int32) * 256 + q_lo.astype(jnp.int32)
    else:
        q = q_hi.astype(jnp.int32)
    return q.astype(jnp.float32) * (scale * _INV_QMAX[bits])


def bq_decode_add_encode_ref(q_hi, q_lo, scale, local: jnp.ndarray, bits: int):
    """Fused ring-hop oracle: encode(local + decode(wire)).

    This is the inner loop of the compression-assisted ring reduce-scatter
    (paper §IV-A): the payload received from the previous rank is decoded,
    accumulated into the local chunk, and re-encoded for the next hop.

    Returns (q_hi', q_lo', scale', sum_f32).
    """
    s = bq_decode_ref(q_hi, q_lo, scale, bits) + local.astype(jnp.float32)
    hi, lo, sc = bq_encode_ref(s, bits)
    return hi, lo, sc, s


def bq_decode_add_ref(q_hi, q_lo, scale, local: jnp.ndarray,
                      bits: int) -> jnp.ndarray:
    """Final ring-hop oracle: local + decode(wire), no re-encode.

    The last reduce-scatter hop of a plain (non-all-reduce) ring keeps the
    f32 sum and sends nothing further, so re-encoding it is wasted work;
    this is the sum-only tail of :func:`bq_decode_add_encode_ref` and is
    bit-identical to its ``sum_f32`` output.
    """
    return bq_decode_ref(q_hi, q_lo, scale, bits) + local.astype(jnp.float32)


def bq_gather_decode_ref(q_hi, q_lo, scale, idx: jnp.ndarray, bits: int):
    """Paged decode-read oracle: gather quantized rows by a leading block
    index, then dequantize.

    This is the attention-read path of the paged KV cache
    (:mod:`repro.serve.paged_kv`): ``q_hi``/``q_lo``/``scale`` are pool
    arrays with a leading block axis, ``idx`` is an integer block table of
    any shape, and the gather touches only the *compressed* planes — the
    HBM read is ``bits``-rate, never the decoded f32.  Returns f32 of
    shape ``idx.shape + pool.shape[1:-1] + (BLOCK,)``.
    """
    _check_bits(bits)
    take = lambda a: None if a is None else jnp.take(a, idx, axis=0)
    return bq_decode_ref(take(q_hi), take(q_lo), take(scale), bits)


def max_abs_error_bound(scale: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Worst-case |x - D(E(x))| per block.

    Half a quantization step, plus a few f32 ulps of the block max for the
    scale/rescale arithmetic itself.  At rate 24 the quantization step
    (~6e-8 * scale) is *below* f32 roundoff, so the ulp term dominates —
    i.e. bq24 is "f32-arithmetic-exact", matching the paper's use of ZFP
    rate:24 as the near-lossless MP setting.
    """
    _check_bits(bits)
    return scale[..., 0] * (0.5 / _QMAX[bits] + 1e-6)
