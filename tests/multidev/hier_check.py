"""Hierarchical two-level collectives: equivalence vs flat lax collectives.

On an 8-device host mesh factored (node=2, data=4):

  * identity codecs -> bit-exact vs the stock lax collective over the
    joint ("node", "data") axis pair (integer-valued payloads make the
    sums order-insensitive, so exact equality is well-defined);
  * lossy level-aware schemes -> within codec error bounds;
  * backward rules -> jax.grad through each hier primitive matches the
    flat collective's grad (exactly under identity codecs, within codec
    tolerance under lossy ones);
  * ledger: hier_zpp_8_16 moves strictly fewer inter-node (outer-stage)
    bytes than the flat zhybrid_16_8 baseline on the same payload.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.analysis import roofline as rl
from repro.core import comms, compat, schemes

NODE, LOCAL = 2, 4
mesh = compat.make_mesh((NODE, LOCAL), ("node", "data"))
rng = np.random.default_rng(0)


def smap(f, in_specs, out_specs):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def ints(shape):
    """Integer-valued f32: float sums are exact in any association order."""
    return jnp.asarray(rng.integers(-8, 9, shape).astype(np.float32))


SPEC = P(("node", "data"))
x = ints((8, 4, 256))          # leading dim -> the 8 joint ranks
big = ints((8, 32, 256))
xf = jnp.asarray(rng.normal(size=(8, 4, 256)).astype(np.float32))

# ---- identity codecs: bit-exact vs the flat lax collective -------------
with schemes.use("baseline"):
    f_h = smap(lambda a: comms.hier_all_reduce(a, "data", "node", "dp"),
               (SPEC,), SPEC)
    f_f = smap(lambda a: lax.psum(a, ("node", "data")), (SPEC,), SPEC)
    np.testing.assert_array_equal(np.asarray(f_h(x)), np.asarray(f_f(x)))

    r_h = smap(lambda a: comms.hier_reduce_scatter(a, "data", "node", 1, "dp"),
               (SPEC,), SPEC)
    r_f = smap(lambda a: lax.psum_scatter(a, ("node", "data"),
                                          scatter_dimension=1, tiled=True),
               (SPEC,), SPEC)
    np.testing.assert_array_equal(np.asarray(r_h(big)), np.asarray(r_f(big)))

    g_h = smap(lambda a: comms.hier_all_gather(a, "data", "node", 1, "zero"),
               (SPEC,), SPEC)
    g_f = smap(lambda a: lax.all_gather(a, ("node", "data"), axis=1,
                                        tiled=True), (SPEC,), SPEC)
    np.testing.assert_array_equal(np.asarray(g_h(x)), np.asarray(g_f(x)))
print("identity hier == flat lax: bit-exact")

# ---- identity grads: bit-exact vs flat ---------------------------------
w = ints((8, 4, 256))
with schemes.use("baseline"):
    def loss_h(a):
        return jnp.sum(comms.hier_all_reduce(a, "data", "node", "dp") * w[0])

    def loss_f(a):
        return jnp.sum(lax.psum(a, ("node", "data")) * w[0])
    gh = smap(jax.grad(loss_h), (SPEC,), SPEC)(x)
    gf = smap(jax.grad(loss_f), (SPEC,), SPEC)(x)
    np.testing.assert_array_equal(np.asarray(gh), np.asarray(gf))

    def loss_rs_h(a):
        return jnp.sum(comms.hier_reduce_scatter(a, "data", "node", 1, "dp")
                       ** 2)

    def loss_rs_f(a):
        return jnp.sum(lax.psum_scatter(a, ("node", "data"),
                                        scatter_dimension=1, tiled=True) ** 2)
    gh = smap(jax.grad(loss_rs_h), (SPEC,), SPEC)(big)
    gf = smap(jax.grad(loss_rs_f), (SPEC,), SPEC)(big)
    np.testing.assert_array_equal(np.asarray(gh), np.asarray(gf))
print("identity hier grads == flat lax grads: bit-exact")

# ---- lossy level-aware schemes: within codec error bounds --------------
for scheme, tol in (("hier_zpp_8_16", 0.35), ("hier_zpp_4_16", 0.8),
                    ("hier_mzpp_8", 0.35), ("zhybrid_16_8", 0.35)):
    with schemes.use(scheme):
        got = np.asarray(smap(
            lambda a: comms.hier_all_reduce(a, "data", "node", "dp"),
            (SPEC,), SPEC)(xf))
        want = np.broadcast_to(np.asarray(xf).sum(0, keepdims=True), xf.shape)
        err = np.abs(got - want).max() / np.abs(want).max()
        assert err <= tol, (scheme, "hier_ar", err)

        got = np.asarray(smap(
            lambda a: comms.hier_reduce_scatter(a, "data", "node", 1, "dp"),
            (SPEC,), SPEC)(big))
        s = np.asarray(big).sum(0)
        want = np.stack([s[i * 4:(i + 1) * 4] for i in range(8)])
        err = np.abs(got - want).max() / np.abs(want).max()
        assert err <= tol, (scheme, "hier_rs", err)

        got = np.asarray(smap(
            lambda a: comms.hier_all_gather(a, "data", "node", 1, "zero"),
            (SPEC,), SPEC)(xf))
        want = np.broadcast_to(np.asarray(xf).reshape(1, 32, 256),
                               (8, 32, 256))
        err = np.abs(got - want).max() / np.abs(want).max()
        assert err <= tol, (scheme, "hier_ag", err)

        # backward-pass codecs: grad finite and close to the analytic grad
        # (y.size is the per-shard size inside shard_map: xf.size / 8)
        def loss(a):
            y = comms.hier_all_reduce(a, "data", "node", "dp")
            return jnp.sum(y * y) / y.size
        g = np.asarray(smap(jax.grad(loss), (SPEC,), SPEC)(xf))
        want_g = 2 * np.asarray(xf).sum(0, keepdims=True) * 8 / (xf.size // 8)
        want_g = np.broadcast_to(want_g, g.shape)
        err = np.abs(g - want_g).max() / np.abs(want_g).max()
        assert np.isfinite(g).all() and err <= 2 * tol, (scheme, "grad", err)
    print(f"{scheme:14s} OK (lossy bounds)")

# ---- ledger: outer-stage bytes strictly below the flat baseline --------
def trace_bytes(scheme, hier):
    with schemes.use(scheme), comms.record_traffic() as events:
        if hier:
            fn = smap(lambda a: comms.hier_all_reduce(a, "data", "node", "dp"),
                      (SPEC,), SPEC)
        else:
            fn = smap(lambda a: comms.psum(a, ("node", "data"), "dp"),
                      (SPEC,), SPEC)
        fn.lower(x)
    return events

flat_ev = trace_bytes("zhybrid_16_8", hier=False)
hier_ev = trace_bytes("hier_zpp_8_16", hier=True)
flat_sum = rl.ledger_summary(flat_ev, train=True)
hier_sum = rl.ledger_summary(hier_ev, train=True)
# the flat collective's ring spans nodes: its whole volume prices as
# slow-link traffic; the hier op's slow-link traffic is its outer stage
flat_slow = rl.link_bytes(flat_ev, train=True,
                          slow_axes=(("node", "data"),))["slow"]
hier_slow = rl.link_bytes(hier_ev, train=True)["slow"]
assert hier_slow == hier_sum["per_level"]["outer"]
assert flat_slow == flat_sum["total_bytes"]
assert 0 < hier_slow < flat_slow, (hier_slow, flat_slow)
print(f"inter-node bytes: hier_zpp_8_16={hier_slow:.0f} < "
      f"flat zhybrid_16_8={flat_slow:.0f} "
      f"({hier_slow / flat_slow:.1%} of flat)")

print("hier comms validated on (node=2, data=4) mesh")
