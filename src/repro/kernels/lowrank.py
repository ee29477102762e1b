"""Low-rank projection kernels for the ``plr`` codec family.

PowerSGD-style gradient compression (arXiv:1905.13727; the low-rank
gradient structure the paper cites to justify *aggressive* DP compression,
arXiv:2301.02654) factors a gradient matrix ``M (m, n)`` through a warm-
started orthonormal factor ``Q (n, r)``:

    P  = M @ Q          (project onto the carried subspace)
    P^ = orth(P)        (modified Gram-Schmidt, r columns)
    Q' = M^T @ P^       (back-project; the second wire factor)
    M~ = P^ @ Q'^T      (reconstruction, rank <= r)

The wire is ``r * (m + n)`` floats instead of ``m * n`` — the codec-level
pricing in ``analysis.roofline`` uses exactly that ratio.  ``Q`` is the
carried codec state: re-using last step's subspace is one warm power-
iteration step per training step, which is what makes rank-r tracking of
a slowly rotating gradient spectrum work.

Backend contract mirrors ``bq.py``/``ref.py``: a pure-jnp oracle
(``matmul_ref``) and a Pallas TPU kernel (``matmul_pallas``, tiled over
rows with lane-padded operands), dispatched through :func:`matmul` with
the same backend names as :mod:`repro.kernels.ops` (``auto`` / ``jnp`` /
``pallas`` / ``pallas_interpret``).  The Gram-Schmidt orthonormalization
is a small unrolled jnp loop (r <= 32 columns) — deterministic and
identical on every rank, which the distributed all-reduce in
``comms._lowrank_psum_impl`` relies on (every rank must hold the same
``Q``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_M = 8          # sublane-aligned rows per grid step (matches bq.TILE_M)
LANE = 128          # TPU lane width: pallas operands are padded to it
NCOLS_MIN = 128     # narrowest matrix view (one lane tile)
NCOLS_MAX = 512     # widest matrix view of a flattened payload


# --------------------------------------------------------------------------
# matrix view of a flat payload
# --------------------------------------------------------------------------

def mat_shape(n: int) -> tuple[int, int]:
    """(rows, cols) of the near-square matrix view of ``n`` flat elements.

    cols is the power of two nearest sqrt(n) clamped to [NCOLS_MIN,
    NCOLS_MAX]; rows pad up to a multiple of TILE_M so the Pallas grid
    tiles evenly.  Both the codec state template and the wire pricing
    derive from this one function, so they can never disagree."""
    ncols = NCOLS_MIN
    while ncols * ncols < n and ncols < NCOLS_MAX:
        ncols *= 2
    m = max(-(-n // ncols), 1)
    m = -(-m // TILE_M) * TILE_M
    return m, ncols


def rank_for(n: int, rank: int) -> int:
    """Effective rank at payload size ``n``: requested rank clamped to the
    matrix view (you cannot carry more directions than rows/cols)."""
    m, ncols = mat_shape(n)
    return max(1, min(rank, m, ncols))


def to_mat(flat: jnp.ndarray) -> jnp.ndarray:
    """1-D payload -> (m, ncols) f32 matrix view, zero-padded."""
    n = flat.shape[0]
    m, ncols = mat_shape(n)
    flat = jnp.pad(flat.astype(jnp.float32), (0, m * ncols - n))
    return flat.reshape(m, ncols)


def from_mat(mat: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of :func:`to_mat` (strips the zero padding)."""
    return mat.reshape(-1)[:n]


# --------------------------------------------------------------------------
# matmul: jnp oracle + Pallas kernel, ops-style backend dispatch
# --------------------------------------------------------------------------

def matmul_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Oracle: f32 matmul with an f32 accumulator (the kernel's contract).

    ``HIGHEST`` keeps the TPU from rounding the f32 operands to bf16 — on
    the CPU it changes nothing."""
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _mm_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = jnp.dot(a_ref[...], b_ref[...],
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)


def _pad_to(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


@functools.partial(jax.jit, static_argnames=("interpret",))
def matmul_pallas(a: jnp.ndarray, b: jnp.ndarray,
                  interpret: bool = False) -> jnp.ndarray:
    """(m, k) @ (k, n) -> (m, n) f32, tiled over rows of ``a``.

    The factor dims (k = carried rank, n = rank or ncols) are zero-padded
    to the 128 lane width — zeros contribute nothing to the contraction —
    and m to the TILE_M sublane multiple; the kernel keeps the full
    (padded) k and n resident per tile, which fits VMEM for the small
    factor shapes of the plr codec (r <= 32, ncols <= 512)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    mp = -(-m // TILE_M) * TILE_M
    kp = -(-k // LANE) * LANE
    np_ = -(-n // LANE) * LANE
    ap = _pad_to(a.astype(jnp.float32), mp, kp)
    bp = _pad_to(b.astype(jnp.float32), kp, np_)
    out = pl.pallas_call(
        _mm_kernel,
        grid=(mp // TILE_M,),
        in_specs=[pl.BlockSpec((TILE_M, kp), lambda i: (i, 0)),
                  pl.BlockSpec((kp, np_), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((TILE_M, np_), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n]


def matmul(a: jnp.ndarray, b: jnp.ndarray,
           backend: str | None = None) -> jnp.ndarray:
    """Backend-dispatched f32 matmul (same names as ``ops``)."""
    from repro.kernels import ops
    be = ops._resolve(backend)
    if be == "jnp":
        return matmul_ref(a, b)
    return matmul_pallas(a, b, interpret=(be == "pallas_interpret"))


# --------------------------------------------------------------------------
# orthonormalization + deterministic warm start
# --------------------------------------------------------------------------

def orthonormalize(p: jnp.ndarray) -> jnp.ndarray:
    """Modified Gram-Schmidt over the (few) columns of ``p``.

    Rank-deficient inputs produce zero columns (the reconstruction simply
    drops those directions) instead of the backend-dependent arbitrary
    basis a QR would emit — keeping every rank's factors bit-identical,
    which the distributed path requires."""
    assert p.ndim == 2 and p.shape[0] >= p.shape[1], p.shape
    cols = []
    for i in range(p.shape[1]):
        v = p[:, i]
        norm0 = jnp.sqrt(jnp.sum(v * v))
        for u in cols:
            v = v - jnp.dot(u, v) * u
        norm = jnp.sqrt(jnp.sum(v * v))
        # relative tolerance: a column that projections reduced to f32
        # roundoff of its original scale is linearly dependent — zero it
        # instead of normalizing the noise into a spurious direction
        v = jnp.where(norm > 1e-6 * jnp.maximum(norm0, 1e-30),
                      v / jnp.maximum(norm, 1e-30), jnp.zeros_like(v))
        cols.append(v)
    return jnp.stack(cols, axis=1)


def init_factor(ncols: int, rank: int) -> jnp.ndarray:
    """Deterministic warm-start factor Q0 (ncols, rank): orthonormalized
    standard normals from a FIXED seed, so every rank (and every restart
    without a checkpoint) starts in the same subspace."""
    q0 = jax.random.normal(jax.random.PRNGKey(0), (ncols, rank),
                           dtype=jnp.float32)
    return orthonormalize(q0)
