"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle.

Contract asserted here:
  * bit-exact agreement (wire determinism matters — two ranks encoding the
    same tensor must emit identical bytes),
  * the fixed-rate error bound |x - D(E(x))| <= scale * 0.5/qmax per block,
  * idempotence E(D(E(x))) == E(x),
  * shape/dtype sweeps over the padding edge cases.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.core import codecs

BITS = (4, 8, 16, 24)
SHAPES = [(1,), (127,), (128,), (129,), (1024,), (3, 257), (8, 128), (5, 4, 33)]
DTYPES = [np.float32, np.float16]


def _rand(shape, dtype, seed=0, scale=10.0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=shape) * scale).astype(dtype)


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_encode_decode_pallas_matches_ref(bits, shape, dtype):
    x2d = ops.to_blocks(jnp.asarray(_rand(shape, dtype)))
    w_ref = ops.bq_encode_blocks(x2d, bits, backend="jnp")
    w_pal = ops.bq_encode_blocks(x2d, bits, backend="pallas_interpret")
    for k in ("q_hi", "q_lo", "scale"):
        if w_ref[k] is None:
            assert w_pal[k] is None
            continue
        np.testing.assert_array_equal(np.asarray(w_ref[k]), np.asarray(w_pal[k]))
    d_ref = ops.bq_decode_blocks(w_ref, bits, backend="jnp")
    d_pal = ops.bq_decode_blocks(w_pal, bits, backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(d_ref), np.asarray(d_pal))


@pytest.mark.parametrize("bits", BITS)
def test_fused_decode_add_encode_matches_ref(bits):
    x2d = ops.to_blocks(jnp.asarray(_rand((4, 300), np.float32, seed=1)))
    loc = ops.to_blocks(jnp.asarray(_rand((4, 300), np.float32, seed=2)))
    w = ops.bq_encode_blocks(x2d, bits, backend="jnp")
    wr, sr = ops.bq_decode_add_encode_blocks(w, loc, bits, backend="jnp")
    wp, sp = ops.bq_decode_add_encode_blocks(w, loc, bits, backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(sr), np.asarray(sp))
    for k in ("q_hi", "q_lo", "scale"):
        if wr[k] is None:
            continue
        np.testing.assert_array_equal(np.asarray(wr[k]), np.asarray(wp[k]))
    # semantics: sum equals decode(w) + loc
    want = np.asarray(ops.bq_decode_blocks(w, bits, backend="jnp")) + np.asarray(loc)
    np.testing.assert_allclose(np.asarray(sr), want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("bits", BITS)
def test_fused_wire_only_and_decode_add_match_full(bits):
    """The wire-only dae variant and the sum-only decode_add variant are
    each bit-identical to the corresponding half of the full fused hop,
    on both backends."""
    x2d = ops.to_blocks(jnp.asarray(_rand((4, 300), np.float32, seed=5)))
    loc = ops.to_blocks(jnp.asarray(_rand((4, 300), np.float32, seed=6)))
    w = ops.bq_encode_blocks(x2d, bits, backend="jnp")
    w_full, s_full = ops.bq_decode_add_encode_blocks(w, loc, bits,
                                                     backend="jnp")
    for be in ("jnp", "pallas_interpret"):
        w_only, s_none = ops.bq_decode_add_encode_blocks(
            w, loc, bits, backend=be, want_sum=False)
        assert s_none is None
        for k in ("q_hi", "q_lo", "scale"):
            if w_full[k] is None:
                assert w_only[k] is None
                continue
            np.testing.assert_array_equal(np.asarray(w_full[k]),
                                          np.asarray(w_only[k]))
        s_only = ops.bq_decode_add_blocks(w, loc, bits, backend=be)
        np.testing.assert_array_equal(np.asarray(s_full),
                                      np.asarray(s_only))


@pytest.mark.parametrize("bits", BITS)
def test_block_ops_take_leading_dims(bits):
    """A gathered wire is ``[n, M, 128]``: the kernels see its shards as
    rows of one matrix and give what the oracles give, shard for shard."""
    x = jnp.asarray(_rand((3, 16, 128), np.float32, seed=7))
    loc = jnp.asarray(_rand((3, 16, 128), np.float32, seed=8))
    want_w = ops.bq_encode_blocks(x, bits, backend="jnp")
    got_w = ops.bq_encode_blocks(x, bits, backend="pallas_interpret")
    cases = [(got_w, want_w),
             (ops.bq_decode_blocks(want_w, bits, backend="pallas_interpret"),
              ops.bq_decode_blocks(want_w, bits, backend="jnp")),
             (ops.bq_decode_add_encode_blocks(want_w, loc, bits,
                                              backend="pallas_interpret"),
              ops.bq_decode_add_encode_blocks(want_w, loc, bits,
                                              backend="jnp")),
             (ops.bq_decode_add_blocks(want_w, loc, bits,
                                       backend="pallas_interpret"),
              ops.bq_decode_add_blocks(want_w, loc, bits, backend="jnp"))]
    for got, want in cases:
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                        strict=True):
            assert g.shape == w.shape and g.shape[0] == 3
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("bits", BITS)
def test_error_bound(bits):
    x = jnp.asarray(_rand((2048,), np.float32, seed=3, scale=100.0))
    x2d = ops.to_blocks(x)
    w = ops.bq_encode_blocks(x2d, bits, backend="jnp")
    d = ops.bq_decode_blocks(w, bits, backend="jnp")
    err = np.abs(np.asarray(d) - np.asarray(x2d))
    bound = np.asarray(ref.max_abs_error_bound(np.asarray(w["scale"]), bits))
    assert (err.max(axis=-1) <= bound * (1 + 1e-5)).all()


@pytest.mark.parametrize("bits", BITS)
def test_idempotence(bits):
    x2d = ops.to_blocks(jnp.asarray(_rand((777,), np.float32, seed=4)))
    w1 = ops.bq_encode_blocks(x2d, bits, backend="jnp")
    d1 = ops.bq_decode_blocks(w1, bits, backend="jnp")
    w2 = ops.bq_encode_blocks(d1, bits, backend="jnp")
    d2 = ops.bq_decode_blocks(w2, bits, backend="jnp")
    # re-encoding a decoded tensor must be (near-)stable: one more roundtrip
    # may move values by at most one quantization step of the block scale
    step = np.asarray(w1["scale"])[..., 0] / ref._QMAX[bits]
    drift = np.abs(np.asarray(d2) - np.asarray(d1)).max(axis=-1)
    assert (drift <= step * (1 + 1e-5)).all()


# Seeded parameter sweep standing in for the old hypothesis @given cases:
# a deterministic grid over sizes (padding edges), bit rates, magnitudes
# (subnormal-adjacent through 1e30), and per-cell derived seeds covers the
# same round-trip properties without the optional dependency.
_SWEEP_SIZES = (1, 7, 127, 128, 129, 777, 2048, 4096)
_SWEEP_SCALES = (1e-8, 1e-3, 1.0, 1e4, 1e30)


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("scale", _SWEEP_SCALES)
def test_property_roundtrip_bound(bits, scale):
    """Property: relative-to-block-max error bounded for any shape/magnitude."""
    for i, n in enumerate(_SWEEP_SIZES):
        seed = hash((bits, n, i)) % (2**31)
        x = jnp.asarray(_rand((n,), np.float32, seed=seed, scale=scale))
        x2d = ops.to_blocks(x)
        w = ops.bq_encode_blocks(x2d, bits, backend="jnp")
        d = ops.bq_decode_blocks(w, bits, backend="jnp")
        err = np.abs(np.asarray(d) - np.asarray(x2d)).max(axis=-1)
        bound = np.asarray(ref.max_abs_error_bound(np.asarray(w["scale"]), bits))
        assert (err <= bound * (1 + 1e-5) + 1e-37).all(), (bits, n, scale)


@pytest.mark.parametrize("seed", range(20))
def test_property_zero_and_special_blocks(seed):
    """All-zero blocks decode to exactly zero; constant blocks are exact-ish."""
    z = ops.to_blocks(jnp.zeros((512,), jnp.float32))
    for bits in BITS:
        w = ops.bq_encode_blocks(z, bits, backend="jnp")
        d = ops.bq_decode_blocks(w, bits, backend="jnp")
        assert np.asarray(d).max() == 0.0 and np.asarray(d).min() == 0.0
    rng = np.random.default_rng(seed)
    c = float(rng.normal()) or 1.0
    x = ops.to_blocks(jnp.full((256,), c, jnp.float32))
    w = ops.bq_encode_blocks(x, 16, backend="jnp")
    d = ops.bq_decode_blocks(w, 16, backend="jnp")
    np.testing.assert_allclose(np.asarray(d), np.asarray(x), rtol=1e-4)


def test_codec_registry_and_ratio():
    x = jnp.asarray(_rand((513,), np.float32))
    for name, bits_pv in [("none", 32), ("mpc", 32), ("bq4", 4.25),
                          ("bq8", 8.25), ("bq16", 16.25), ("bq24", 24.25)]:
        c = codecs.get(name)
        assert abs(c.wire_bits_per_value() - bits_pv) < 1e-9
        wire, state = c.encode(x)
        assert state is None        # stateless codecs thread no state
        y = c.decode(wire, x.shape, jnp.float32)
        if c.lossless:
            np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    with pytest.raises(KeyError):
        codecs.get("zstd")


def test_to_from_blocks_roundtrip():
    for shape in SHAPES:
        x = jnp.asarray(_rand(shape, np.float32))
        y = ops.from_blocks(ops.to_blocks(x), shape)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_wire_nbytes():
    x = jnp.zeros((1024,), jnp.float32)
    w8, _ = codecs.get("bq8").encode(x)
    w24, _ = codecs.get("bq24").encode(x)
    assert ops.wire_nbytes(w8) == 1024 + 8 * 4        # int8 + 8 block scales
    assert ops.wire_nbytes(w24) == 1024 * 3 + 8 * 4   # int16+uint8 planes
    assert ops.wire_nbytes(codecs.get("none").encode(x)[0]) == 4096
