"""Public, jit-friendly entry points for the bq codec kernels.

Backend dispatch:
  * ``auto``              -> compiled Pallas on TPU, pure-jnp oracle elsewhere
                             (bit-identical math either way — see ref.py)
  * ``jnp``               -> force the oracle (fast on CPU; used by dry-run)
  * ``pallas``            -> force compiled Pallas (TPU)
  * ``pallas_interpret``  -> Pallas interpret mode (CPU kernel validation)

Shape handling: tensors of any shape are flattened, padded to a whole number
of (TILE_M x BLOCK) tiles, and viewed as an (M, 128) block matrix — the layout
the kernels and the ring-collective hops operate on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import functools

from repro.kernels import bq, ref
from repro.kernels.ref import BLOCK

# jitted oracle entry points: the oracle must go through XLA like the kernels
# do, so CPU validation compares compiled-vs-compiled (same fusion decisions).
_encode_ref = functools.partial(jax.jit, static_argnames=("bits",))(ref.bq_encode_ref)
_decode_ref = functools.partial(jax.jit, static_argnames=("bits",))(ref.bq_decode_ref)
_dae_ref = functools.partial(jax.jit, static_argnames=("bits",))(ref.bq_decode_add_encode_ref)
_da_ref = functools.partial(jax.jit, static_argnames=("bits",))(ref.bq_decode_add_ref)
_gather_decode_ref = functools.partial(
    jax.jit, static_argnames=("bits",))(ref.bq_gather_decode_ref)


@functools.partial(jax.jit, static_argnames=("bits",))
def _daew_ref(q_hi, q_lo, scale, local, *, bits):
    """Wire-only fused hop oracle: the running sum is dropped INSIDE the
    jit so XLA provably DCEs its materialization (dropping an output of a
    nested jitted call after the fact does not)."""
    hi, lo, sc, _ = ref.bq_decode_add_encode_ref(q_hi, q_lo, scale, local,
                                                 bits=bits)
    return hi, lo, sc

_TILE_ELEMS = bq.TILE_M * BLOCK

_DEFAULT_BACKEND = "auto"


def set_default_backend(name: str) -> None:
    global _DEFAULT_BACKEND
    assert name in ("auto", "jnp", "pallas", "pallas_interpret"), name
    _DEFAULT_BACKEND = name


def _resolve(backend: str | None) -> str:
    b = backend or _DEFAULT_BACKEND
    if b == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return b


def padded_rows(n: int) -> int:
    """Number of BLOCK-wide rows after padding n elements to whole tiles."""
    n_pad = max(-(-n // _TILE_ELEMS), 1) * _TILE_ELEMS
    return n_pad // BLOCK


def to_blocks(x: jnp.ndarray) -> jnp.ndarray:
    """Flatten + zero-pad to an (M, 128) f32 block matrix."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    m = padded_rows(n)
    flat = jnp.pad(flat, (0, m * BLOCK - n))
    return flat.reshape(m, BLOCK)


def from_blocks(x2d: jnp.ndarray, shape, dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`to_blocks`."""
    n = 1
    for d in shape:
        n *= d
    return x2d.reshape(-1)[:n].reshape(shape).astype(dtype)


# --------------------------------------------------------------------------
# block-matrix level ops (used directly by the ring collectives)
#
# Block matrices may carry leading dims (``[n, M, 128]``: the n shards of a
# gathered wire).  The oracles take any leading shape; the kernels tile a
# 2-D ``(rows, width)`` matrix, so the Pallas branches fold the leading
# dims into rows and unfold the results.
# --------------------------------------------------------------------------

def _rows(a):
    return None if a is None else a.reshape(-1, a.shape[-1])


def _unrows(a, lead):
    return None if a is None else a.reshape(*lead, a.shape[-1])


def bq_encode_blocks(x2d: jnp.ndarray, bits: int, backend: str | None = None):
    """(..., M,128) f32 -> wire dict {q_hi, q_lo|None, scale}."""
    be = _resolve(backend)
    if be == "jnp":
        hi, lo, scale = _encode_ref(x2d, bits=bits)
    else:
        out = bq.bq_encode_pallas(_rows(x2d), bits,
                                  interpret=(be == "pallas_interpret"))
        hi, lo, scale = (_unrows(a, x2d.shape[:-1]) for a in out)
    return {"q_hi": hi, "q_lo": lo, "scale": scale}


def bq_decode_blocks(wire: dict, bits: int, backend: str | None = None) -> jnp.ndarray:
    """wire dict -> (..., M,128) f32."""
    be = _resolve(backend)
    if be == "jnp":
        return _decode_ref(wire["q_hi"], wire["q_lo"], wire["scale"], bits=bits)
    out = bq.bq_decode_pallas(
        _rows(wire["q_hi"]), _rows(wire["q_lo"]), _rows(wire["scale"]), bits,
        interpret=(be == "pallas_interpret"))
    return _unrows(out, wire["scale"].shape[:-1])


def bq_decode_add_encode_blocks(wire: dict, local2d: jnp.ndarray, bits: int,
                                backend: str | None = None,
                                want_sum: bool = True):
    """Fused ring hop: returns (wire', sum_f32 (M,128)|None).

    ``want_sum=False`` skips materializing the f32 running sum — the
    intermediate hops of a ring reduce-scatter only forward the wire."""
    be = _resolve(backend)
    if be == "jnp":
        if want_sum:
            hi, lo, scale, s = _dae_ref(
                wire["q_hi"], wire["q_lo"], wire["scale"], local2d,
                bits=bits)
        else:
            hi, lo, scale = _daew_ref(
                wire["q_hi"], wire["q_lo"], wire["scale"], local2d,
                bits=bits)
            s = None
    else:
        out = bq.bq_decode_add_encode_pallas(
            _rows(wire["q_hi"]), _rows(wire["q_lo"]), _rows(wire["scale"]),
            _rows(local2d), bits, want_sum=want_sum,
            interpret=(be == "pallas_interpret"))
        hi, lo, scale, s = (_unrows(a, local2d.shape[:-1]) for a in out)
    return {"q_hi": hi, "q_lo": lo, "scale": scale}, s


def bq_decode_add_blocks(wire: dict, local2d: jnp.ndarray, bits: int,
                         backend: str | None = None) -> jnp.ndarray:
    """Final ring hop: local + decode(wire) -> (..., M,128) f32, no
    re-encode."""
    be = _resolve(backend)
    if be == "jnp":
        return _da_ref(wire["q_hi"], wire["q_lo"], wire["scale"], local2d,
                       bits=bits)
    out = bq.bq_decode_add_pallas(
        _rows(wire["q_hi"]), _rows(wire["q_lo"]), _rows(wire["scale"]),
        _rows(local2d), bits, interpret=(be == "pallas_interpret"))
    return _unrows(out, local2d.shape[:-1])


def bq_gather_decode(wire: dict, idx, bits: int,
                     backend: str | None = None):
    """Paged decode-read: gather quantized rows of a pool wire dict by a
    leading block index, then dequantize (``repro.serve.paged_kv``).

    ``wire`` holds pool planes with a leading block axis and a trailing
    per-row layout (``q_hi (n_blocks, ..., hi_width)``, ``scale
    (n_blocks, ..., 1)``); ``idx`` is an integer block table of any
    shape.  The gather reads only the compressed planes — the per-read
    HBM traffic is ``bits``-rate.  Returns f32 of shape
    ``idx.shape + pool.shape[1:-1] + (128,)``."""
    be = _resolve(backend)
    if be == "jnp":
        return _gather_decode_ref(wire["q_hi"], wire["q_lo"],
                                  wire["scale"], idx, bits=bits)
    return bq.bq_gather_decode_pallas(
        wire["q_hi"], wire["q_lo"], wire["scale"], idx, bits,
        interpret=(be == "pallas_interpret"))


# --------------------------------------------------------------------------
# tensor-level ops (arbitrary shape; used by one-shot encode/decode paths)
# --------------------------------------------------------------------------

def bq_encode(x: jnp.ndarray, bits: int, backend: str | None = None):
    return bq_encode_blocks(to_blocks(x), bits, backend)


def bq_decode(wire: dict, bits: int, shape, dtype=jnp.float32,
              backend: str | None = None) -> jnp.ndarray:
    return from_blocks(bq_decode_blocks(wire, bits, backend), shape, dtype)


def wire_nbytes(wire) -> int:
    """Actual bytes crossing the interconnect for a wire pytree."""
    return sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(wire))
