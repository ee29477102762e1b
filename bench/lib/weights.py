"""Weights from the seed, made on the device in one jitted call.

A layout is a list of ``(path, shape, dtype, init, scale)``, declared by
the configuration's plain reference.  Each leaf is drawn from its own key,
``fold_in(key(seed), crc32(path))``, so a leaf's values depend only on the
seed and its path: the program and the reference get the same weights
from the same seed, and neither takes them from the other.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def root_key(seed: int):
    """A key from any non-negative seed, also one wider than 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, path, shape, dtype, init, scale):
    dt = jnp.dtype(dtype)
    if init == "zeros":
        return jnp.zeros(shape, dt)
    if init == "ones":
        return jnp.ones(shape, dt)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)


def make(seed: int, layout, shardings=None) -> dict:
    """``{path: array}`` for ``layout``; ``shardings`` (``{path: Sharding}``)
    places each leaf where the program keeps it."""
    paths = [e[0] for e in layout]

    def build(key):
        return {e[0]: _leaf(key, *e) for e in layout}

    out = None if shardings is None else {p: shardings[p] for p in paths}
    return jax.jit(build, out_shardings=out)(root_key(seed))
