"""Model-layer hierarchical collectives: equivalence + byte acceptance.

On an 8-device host mesh with the model axis factored (tpnode=2, model=4):

  * identity codecs -> every hierarchical TP/EP op the model layer uses
    (psum / reduce-scatter / all-gather / all-to-all / ppermute, routed
    via an AxisPair axis) is bit-exact against the stock lax collective
    over the joint ("tpnode", "model") axis pair, forward AND grad;
  * end-to-end: a dense and a MoE arch produce bit-identical losses on a
    flat (data=2, model=4) mesh and a tp-node-factored (data=2, tpnode=2,
    model=2) mesh under the baseline scheme (the MoE arch drives the
    hierarchical all-to-all through the expert-parallel token route);
  * ledger acceptance: the hier_tpp_8_16 TP all-reduce moves strictly
    fewer inter-node bytes than the flat TP baseline (zhybrid_16_8 over a
    model axis that spans nodes).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.analysis import roofline as rl
from repro.core import comms, compat, schemes

TPN, TPL = 2, 4
mesh = compat.make_mesh((TPN, TPL), ("tpnode", "model"))
PAIR = compat.AxisPair("tpnode", "model")
JOINT = ("tpnode", "model")
SPEC = P(JOINT)
rng = np.random.default_rng(0)


def smap(f):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(SPEC,),
                                 out_specs=SPEC, check_vma=False))


def ints(shape):
    """Integer-valued f32: float sums are exact in any association order."""
    return jnp.asarray(rng.integers(-8, 9, shape).astype(np.float32))


x = ints((64, 8, 16))        # local [8, 8, 16] per joint rank
y = ints((8, 4, 64))
ring = [(j, (j + 1) % 8) for j in range(8)]
shift = [(j, j + 3) for j in range(5)]

# ---- identity codecs: bit-exact vs the flat lax collective -------------
with schemes.use("baseline"):
    pairs = [
        ("psum", lambda a: comms.psum(a, PAIR, "tp"),
         lambda a: lax.psum(a, JOINT)),
        ("reduce_scatter", lambda a: comms.reduce_scatter(a, PAIR, 1, "tp"),
         lambda a: lax.psum_scatter(a, JOINT, scatter_dimension=1,
                                    tiled=True)),
        ("all_gather", lambda a: comms.all_gather(a, PAIR, 1, "tp"),
         lambda a: lax.all_gather(a, JOINT, axis=1, tiled=True)),
        ("all_to_all00", lambda a: comms.all_to_all(a, PAIR, 0, 0, "ep"),
         lambda a: lax.all_to_all(a, JOINT, 0, 0, tiled=True)),
        ("all_to_all01", lambda a: comms.all_to_all(a, PAIR, 0, 1, "ep"),
         lambda a: lax.all_to_all(a, JOINT, 0, 1, tiled=True)),
        ("ppermute_ring", lambda a: comms.ppermute(a, PAIR, ring, "pp"),
         lambda a: lax.ppermute(a, JOINT, ring)),
        ("ppermute_shift", lambda a: comms.ppermute(a, PAIR, shift, "pp"),
         lambda a: lax.ppermute(a, JOINT, shift)),
    ]
    for name, hier_fn, flat_fn in pairs:
        np.testing.assert_array_equal(
            np.asarray(smap(hier_fn)(x)), np.asarray(smap(flat_fn)(x)),
            err_msg=name)
        gh = smap(jax.grad(lambda a, f=hier_fn: jnp.sum(f(a) ** 2)))(x)
        gf = smap(jax.grad(lambda a, f=flat_fn: jnp.sum(f(a) ** 2)))(x)
        np.testing.assert_array_equal(np.asarray(gh), np.asarray(gf),
                                      err_msg=f"{name} grad")
print("identity hier TP/EP ops == flat lax: bit-exact (fwd + grad)")

# ---- end-to-end: flat vs tp-node-factored mesh, bit-identical loss -----
from repro import configs
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.models.params import MeshInfo

jax.clear_caches()


def loss_on(mesh_, cfg, batch):
    mi = MeshInfo.from_mesh(mesh_)
    m = Model(cfg, mi)
    params = m.init(jax.random.key(1))
    bspecs = {"tokens": P("data", None), "labels": P("data", None)}
    sm = jax.jit(jax.shard_map(
        lambda p, b: m.loss_fn(p, b), mesh=mesh_,
        in_specs=(m.specs(), bspecs),
        out_specs=(P(), {"xent": P(), "tokens": P()}), check_vma=True))
    with schemes.use("baseline"):
        loss, _ = sm(params, batch)
    return float(loss)


for arch in ("gemma3-1b", "qwen3-moe-235b-a22b"):
    cfg = configs.get(arch).reduced()
    B, S = 4, 16
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                   jnp.int32)}
    l_flat = loss_on(make_mesh(2, 4), cfg, batch)
    l_fact = loss_on(make_mesh(2, 4, tp_nodes=2), cfg, batch)
    assert l_flat == l_fact, (arch, l_flat, l_fact)
    print(f"{arch:22s} flat={l_flat:.6f} == tp-factored={l_fact:.6f}")
print("factored-TP model losses match flat: bit-exact")

# ---- ledger acceptance: inter-node TP bytes strictly below flat --------
jax.clear_caches()


def trace_tp_bytes(scheme, hier):
    axis = PAIR if hier else JOINT
    with schemes.use(scheme), comms.record_traffic() as events:
        smap(lambda a: comms.psum(a, axis, "tp")).lower(x)
    jax.clear_caches()
    return events


flat_ev = trace_tp_bytes("zhybrid_16_8", hier=False)
hier_ev = trace_tp_bytes("hier_tpp_8_16", hier=True)
# the flat TP ring spans nodes: its whole volume prices as slow-link
# traffic; the hier op's slow-link traffic is its outer stage only
flat_slow = rl.link_bytes(flat_ev, train=True, slow_axes=(JOINT,))["slow"]
hier_slow = rl.link_bytes(hier_ev, train=True)["slow"]
hier_sum = rl.ledger_summary(hier_ev, train=True)
assert hier_slow == hier_sum["per_level"]["outer"]
assert hier_sum["per_dim_level"]["tp/outer"] == hier_slow
assert 0 < hier_slow < flat_slow, (hier_slow, flat_slow)
print(f"inter-node TP bytes: hier_tpp_8_16={hier_slow:.0f} < "
      f"flat zhybrid_16_8={flat_slow:.0f} "
      f"({hier_slow / flat_slow:.1%} of flat)")

print("tp hier comms validated on (tpnode=2, model=4) mesh")
