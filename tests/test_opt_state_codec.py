"""The ZeRO-1 optimizer state codec: at 8 bits m is bq8 and v is kept as
bq8(sqrt(v)) with a floor at decode; at 32 bits the state is m and v as
they are."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.ref import BLOCK
from repro.train.optimizer import Adam, AdamConfig


def _adam(**kw):
    return Adam(AdamConfig(**kw), None)


def _moments(adam, grads):
    """Exact m and v after the gradients ``grads`` (steps x lanes)."""
    m = v = jnp.zeros(grads.shape[1:], jnp.float32)
    for t, g in enumerate(grads):
        _, m, v = adam._adam_update(jnp.asarray(g), m, v, jnp.zeros_like(m),
                                    jnp.int32(t))
    return m, v


def test_state_at_32_bits_is_m_and_v_as_they_are():
    adam = _adam()
    x, m = jnp.arange(4.0), jnp.ones(4)
    assert adam.v_layout == "v"
    assert adam._v_encode(x) is x and adam._v_decode(x, m) is x


def test_8_bit_state_needs_b1_squared_below_b2():
    with pytest.raises(ValueError):
        _adam(state_bits=8, b1=0.9, b2=0.8)
    _adam(b1=0.9, b2=0.8)           # 32-bit state has no floor to bound


@pytest.mark.parametrize("b1,b2", [(0.9, 0.95), (0.9, 0.999)])
def test_v_floor_is_the_least_sqrt_v_of_an_exact_state(b1, b2):
    adam = _adam(state_bits=8, b1=b1, b2=b2)
    steps = 400
    rng = np.random.default_rng(0)
    random = rng.standard_normal((steps, 64))
    # the history that meets Cauchy-Schwarz with equality: g_{t-k} is
    # proportional to (b1 / b2)**k
    k = np.arange(steps)[::-1]
    worst = ((b1 / b2) ** k)[:, None]
    m, v = _moments(adam, np.concatenate([random, worst], 1)
                    .astype(np.float32))
    ratio = np.asarray(jnp.sqrt(v) / jnp.abs(m))
    assert ratio.min() >= adam.v_floor * (1 - 1e-5)
    assert ratio[-1] <= adam.v_floor * (1 + 1e-3)


def test_8_bit_v_block_spanning_254x_keeps_updates_bounded():
    """One lane of a block takes a large gradient once; the others take a
    steady small one, then nothing.  sqrt(v) spans more than 254x in the
    block, so bq8 rounds the small lanes' sqrt(v) to 0, while their m,
    quantized against m's own smaller maximum, survives."""
    adam = _adam(state_bits=8, lr=1.0)
    steps = 20
    g = np.full((steps, BLOCK), 2e-4, np.float32)
    g[:, 0] = 0.0
    g[0, 0] = 1.0
    m, v = _moments(adam, g)
    m_q = adam._state_encode(m)
    v_q = adam._v_encode(v)
    m_d = adam._state_decode(m_q)

    def update(v_d):
        new, _, _ = adam._adam_update(jnp.zeros(BLOCK), m_d, v_d,
                                      jnp.zeros(BLOCK), jnp.int32(steps))
        return np.abs(np.asarray(new))

    bare = jnp.square(adam._state_decode(v_q))
    lost = (np.asarray(bare) == 0) & (np.asarray(m_d) != 0)
    assert lost[1:].all()           # the failure is there to be caught
    assert update(bare).max() > 1e3
    # with the floor, every lane's update stays inside what an exact
    # state allows after steps + 1 steps
    c, t = adam.cfg, steps + 1
    bound = np.sqrt(1 - c.b2 ** t) / (1 - c.b1 ** t) / adam.v_floor
    floored = adam._v_decode(v_q, m_d)
    assert update(floored).max() <= bound * (1 + 1e-5)
    # lanes whose sqrt(v) survived are decoded as they were
    np.testing.assert_array_equal(np.asarray(floored)[0],
                                  np.asarray(bare)[0])
