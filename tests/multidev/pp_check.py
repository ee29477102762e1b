"""Pipeline-parallel stage axis: 1F1B equivalence + byte acceptance.

On an 8-device host:

  * **bit-exact vs single-stage**: the microbatched 1F1B trainer on a
    ``(data=2, stage=2, model=2)`` mesh under identity codecs produces the
    SAME losses, bit for bit, as the identical microbatched loop on a
    stage-free ``(data=2, model=2)`` mesh, over 10+ optimizer steps with a
    fresh batch each step — the stage partitioning, compressed handoffs
    (identity codecs), stage-replicated grad folds, and per-stage ZeRO
    chunks change nothing numerically;
  * **microbatched == full batch**: gradient accumulation over 4
    microbatches matches the flat full-batch ``Model.loss_fn`` gradients
    leaf-for-leaf (allclose — the only difference is float summation
    order);
  * **ledger acceptance**: under ``hier_tpp_8_16`` on a pp-node-factored
    ``(data, ppnode, stage)`` mesh, the ledger reports nonzero ``pp``
    bytes broken down by level, with inter-node stage-handoff bytes
    strictly below the uncompressed flat baseline.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.analysis import roofline as rl
from repro.core import comms, compat, schemes
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.models.params import MeshInfo, Pv
from repro.train.pipeline import PipelineTrainer, pipeline_loss_fn
from repro.train.train_step import batch_specs

cfg = configs.get("qwen2-72b").reduced()
data = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                  global_batch=8, seed=0))

# ---- 1F1B on (data=2, stage=2, model=2) == microbatched flat, bit-exact --
STEPS, MICRO = 10, 2


def run_losses(mesh):
    mi = MeshInfo.from_mesh(mesh)
    model = Model(cfg, mi)
    tr = PipelineTrainer(model, mesh, scheme="baseline", n_micro=MICRO)
    params, ostate, cstate = tr.init_all(jax.random.key(0))
    bspecs = batch_specs(cfg, mi)
    losses = []
    for step in range(STEPS):
        batch = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
                 for k, v in data.batch(step).items()}
        params, ostate, cstate, m = tr.step(params, ostate, cstate, batch)
        losses.append(float(m["loss"]))
    jax.clear_caches()
    return losses


l_pp = run_losses(make_mesh(2, 2, pp=2))
l_flat = run_losses(make_mesh(2, 2))
assert l_pp == l_flat, ("pipelined losses diverge from flat", l_pp, l_flat)
print(f"1F1B (dp=2, pp=2, tp=2) == flat pp=1: bit-exact over {STEPS} steps "
      f"(final loss {l_pp[-1]:.6f})")

# ---- microbatched grads == full-batch grads (gradient accumulation) -----
mesh = make_mesh(2, 2)
mi = MeshInfo.from_mesh(mesh)
model = Model(cfg, mi)
params = model.init(jax.random.key(1))
bspecs = batch_specs(cfg, mi)
batch = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
         for k, v in data.batch(0).items()}
pspecs = model.specs()


def grads_of(loss_fn):
    def f(p, b):
        with schemes.use("baseline"), comms.vma_mode(False):
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b)
        return loss, g
    sm = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(pspecs, bspecs), out_specs=(P(), pspecs),
        check_vma=False))
    loss, g = sm(params, batch)
    return float(loss), g


loss_mb, g_mb = grads_of(pipeline_loss_fn(model, 4))
loss_fb, g_fb = grads_of(model.loss_fn)
np.testing.assert_allclose(loss_mb, loss_fb, rtol=1e-6)
is_pv = lambda x: isinstance(x, Pv)  # noqa: E731
for a, b in zip(jax.tree_util.tree_leaves(g_mb, is_leaf=is_pv),
                jax.tree_util.tree_leaves(g_fb, is_leaf=is_pv)):
    np.testing.assert_allclose(np.asarray(a.v), np.asarray(b.v),
                               rtol=2e-5, atol=1e-6)
print(f"4-microbatch grads == full-batch grads (loss {loss_mb:.6f})")
jax.clear_caches()

# ---- ledger: pp bytes by level; inter-node handoff below flat baseline --
PPN = compat.AxisPair("ppnode", "stage")
JOINT = ("ppnode", "stage")
hmesh = compat.make_mesh((2, 2, 2), ("data", "ppnode", "stage"))


def trace_handoff(scheme, hier):
    axis = PPN if hier else JOINT
    sm = jax.jit(jax.shard_map(
        lambda a: comms.stage_send(a, axis), mesh=hmesh,
        in_specs=(P("data"),), out_specs=P("data"), check_vma=False))
    with schemes.use(scheme), comms.record_traffic() as events:
        sm.lower(jax.ShapeDtypeStruct((2, 4096), jnp.float32))
    jax.clear_caches()
    return events


flat_ev = trace_handoff("zhybrid_16_8", hier=False)
hier_ev = trace_handoff("hier_tpp_8_16", hier=True)
hier_sum = rl.ledger_summary(hier_ev, train=True)
assert hier_sum["per_dim_level"]["pp/inner"] > 0
assert hier_sum["per_dim_level"]["pp/outer"] > 0
flat_slow = rl.link_bytes(flat_ev, train=True, slow_axes=(JOINT,))["slow"]
hier_slow = rl.link_bytes(hier_ev, train=True)["slow"]
assert hier_slow == hier_sum["per_dim_level"]["pp/outer"]
assert 0 < hier_slow < flat_slow, (hier_slow, flat_slow)
print(f"inter-node stage-handoff bytes: hier_tpp_8_16={hier_slow:.0f} < "
      f"flat zhybrid_16_8={flat_slow:.0f} ({hier_slow / flat_slow:.1%})")

# identity handoff == lax.ppermute shift over the joint axis (fwd + grad)
shift = [(s, s + 1) for s in range(3)]
rng = np.random.default_rng(0)
x = jnp.asarray(rng.integers(-8, 9, (8, 16)).astype(np.float32))
SPEC = P(("data", "ppnode", "stage"))


def smap(f):
    return jax.jit(jax.shard_map(f, mesh=hmesh, in_specs=(SPEC,),
                                 out_specs=SPEC, check_vma=False))


with schemes.use("baseline"):
    pairs = [
        # stage_send / stage_recv over the joint pp rank space of THIS
        # data shard vs the flat lax shift they decompose
        ("stage_send", lambda a: comms.stage_send(a, PPN),
         lambda a: jax.lax.ppermute(a, JOINT, shift)),
        ("stage_recv", lambda a: comms.stage_recv(a, PPN),
         lambda a: jax.lax.ppermute(a, JOINT, [(d, s) for s, d in shift])),
    ]
    for name, hier_fn, flat_fn in pairs:
        np.testing.assert_array_equal(np.asarray(smap(hier_fn)(x)),
                                      np.asarray(smap(flat_fn)(x)),
                                      err_msg=name)
        gh = smap(jax.grad(lambda a, f=hier_fn: jnp.sum(f(a) ** 2)))(x)
        gf = smap(jax.grad(lambda a, f=flat_fn: jnp.sum(f(a) ** 2)))(x)
        np.testing.assert_array_equal(np.asarray(gh), np.asarray(gf),
                                      err_msg=f"{name} grad")
print("identity stage_send/recv == flat lax.ppermute shifts: "
      "bit-exact (fwd+grad)")

print("PP STAGE AXIS OK")
