"""Interleaved virtual-stage (vpp) 1F1B: equivalence + ledger acceptance.

On an 8-device host:

  * **vpp=1 == existing 1F1B, bit-exact**: a ``vpp=1`` model on the
    ``(data=2, stage=2, model=2)`` mesh produces the SAME losses, bit for
    bit, as the identical microbatched loop on a stage-free
    ``(data=2, model=2)`` mesh over 10 optimizer steps — the plain
    schedule is untouched by the interleaving machinery;
  * **vpp=2 == vpp=1 to fp tol**: the interleaved schedule computes the
    same math in a different tick order — losses match to float
    summation-order tolerance over 10 steps;
  * **remat policy is grad-exact**: ``--remat-policy full`` and
    ``per_stage:1`` recompute instead of stash — per-leaf gradients at
    init match the no-remat gradients to float tolerance and a 10-step
    training run tracks the no-remat losses to ~1e-5 relative (XLA may
    fuse the checkpointed body differently, so last-ulp rounding drift —
    Adam-amplified over steps — is the expected compile-level noise), and
    the mixed policy also EXECUTES under a compressed scheme — its
    ``lax.cond`` predicate is tick-keyed (uniform across devices), since
    a device-varying predicate deadlocks stage ranks on the body
    collectives' rendezvous;
  * **ledger acceptance**: the stage-handoff events of the lowered
    pipeline loss carry the schedule's ``vpp`` fact and a tick multiplier
    equal to ``roofline.pipeline_ticks`` (the priced bubble denominator
    IS the tick count the scan executes; handoffs multiply x V), and on
    a pp-node-factored mesh the compressed handoff bytes stay strictly
    below the uncompressed identity baseline.
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
from repro.train.pipeline import PipelineTrainer
from repro.train.train_step import batch_specs

# 4 uniform layers: tiles into pp=2 x vpp=2 round-robin chunks
cfg = configs.get("qwen2-72b").reduced().replace(n_layers=4, groups=())
data = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                  global_batch=8, seed=0))
STEPS, MICRO = 10, 2


def run_losses(mesh, vpp=1, remat_policy=None, scheme="baseline",
               steps=STEPS):
    mi = MeshInfo.from_mesh(mesh)
    model = Model(cfg, mi, vpp=vpp)
    tr = PipelineTrainer(model, mesh, scheme=scheme, n_micro=MICRO,
                         remat_policy=remat_policy)
    params, ostate, cstate = tr.init_all(jax.random.key(0))
    bspecs = batch_specs(cfg, mi)
    losses = []
    for step in range(steps):
        batch = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
                 for k, v in data.batch(step).items()}
        params, ostate, cstate, m = tr.step(params, ostate, cstate, batch)
        losses.append(float(m["loss"]))
    jax.clear_caches()
    return losses

# ---- vpp=1 == the existing 1F1B schedule, bit-exact ----------------------
l_v1 = run_losses(make_mesh(2, 2, pp=2), vpp=1)
l_flat = run_losses(make_mesh(2, 2), vpp=1)
assert l_v1 == l_flat, ("vpp=1 diverges from the plain 1F1B/flat loop",
                        l_v1, l_flat)
print(f"vpp=1 (dp=2, pp=2, tp=2) == existing 1F1B: bit-exact over {STEPS} "
      f"steps (final loss {l_v1[-1]:.6f})")

# ---- vpp=2 == vpp=1 to float tolerance -----------------------------------
l_v2 = run_losses(make_mesh(2, 2, pp=2), vpp=2)
np.testing.assert_allclose(l_v2, l_v1, rtol=2e-5)
print(f"vpp=2 interleaved == vpp=1 to fp tol over {STEPS} steps "
      f"(final loss {l_v2[-1]:.6f}, |d|={max(abs(a - b) for a, b in zip(l_v1, l_v2)):.2e})")

# ---- remat policies: grad-exact vs no-remat ------------------------------
from repro.train.pipeline import pipeline_loss_fn  # noqa: E402

rmesh = make_mesh(2, 2, pp=2)
rmi = MeshInfo.from_mesh(rmesh)
rmodel = Model(cfg, rmi, vpp=2)
rparams = rmodel.init(jax.random.key(0))
rbspecs = batch_specs(cfg, rmi)
rbatch = {k: jax.device_put(v, NamedSharding(rmesh, rbspecs[k]))
          for k, v in data.batch(0).items()}
rpspecs = rmodel.specs()
is_pv = lambda x: isinstance(x, Pv)  # noqa: E731


def grads_of(loss_fn):
    def f(p, b):
        with schemes.use("baseline"), comms.vma_mode(False):
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b)
        return loss, g
    sm = jax.jit(jax.shard_map(
        f, mesh=rmesh, in_specs=(rpspecs, rbspecs),
        out_specs=(P(), rpspecs), check_vma=False))
    loss, g = sm(rparams, rbatch)
    return float(loss), g


l0, g0 = grads_of(pipeline_loss_fn(rmodel, MICRO))
for pol in ("full", "per_stage:1"):
    l_r, g_r = grads_of(pipeline_loss_fn(rmodel, MICRO, remat_policy=pol))
    np.testing.assert_allclose(l_r, l0, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_r, is_leaf=is_pv),
                    jax.tree_util.tree_leaves(g0, is_leaf=is_pv)):
        np.testing.assert_allclose(np.asarray(a.v), np.asarray(b.v),
                                   rtol=2e-5, atol=1e-6,
                                   err_msg=f"remat {pol} grads")
jax.clear_caches()
# a full training run under remat tracks the no-remat losses (only
# compile-level last-ulp drift, Adam-amplified, separates them)
for pol in ("full", "per_stage:1"):
    l_r = run_losses(make_mesh(2, 2, pp=2), vpp=2, remat_policy=pol)
    np.testing.assert_allclose(l_r, l_v2, rtol=1e-5)
print(f"remat policies (full, per_stage:1) grad-exact vs no-remat: "
      f"per-leaf grads at init to fp tol, {STEPS}-step losses track")

# per_stage under a COMPRESSED scheme must execute, not just lower: the
# mixed-policy lax.cond predicate has to be uniform across devices — a
# device-varying predicate parks stage ranks in different branches and
# their body collectives deadlock on mismatched rendezvous (regression:
# this hung before the predicate was keyed on the tick)
l_hier = run_losses(make_mesh(2, 2, pp=2), vpp=2,
                    remat_policy="per_stage:1", scheme="hier_tpp_8_16",
                    steps=2)
assert all(np.isfinite(l_hier)), l_hier
np.testing.assert_allclose(l_hier, l_v2[:2], rtol=1e-3)
print(f"per_stage:1 under hier_tpp_8_16 executes (no SPMD deadlock): "
      f"losses {[f'{x:.4f}' for x in l_hier]}")

# ---- ledger: handoff mult == executed ticks, vpp fact, hier < baseline ---
# pp-node-factored mesh: pp = ppnode x stage = 4, so vpp=2 needs 8 layers
cfg8 = cfg.replace(n_layers=8)
hmesh = compat.make_mesh((2, 2, 2, 1), ("data", "ppnode", "stage", "model"))
HM, HPP = 4, 4


def trace_pipeline(vpp, scheme_name):
    from repro.train.pipeline import pipeline_loss_fn
    mi = MeshInfo.from_mesh(hmesh)
    model = Model(cfg8, mi, vpp=vpp)
    lf = pipeline_loss_fn(model, HM)
    bspecs = batch_specs(cfg8, mi)

    def f(p, b):
        with schemes.use(scheme_name), comms.vma_mode(False):
            return lf(p, b)[0]

    sm = jax.jit(jax.shard_map(
        f, mesh=hmesh, in_specs=(model.specs(), bspecs), out_specs=P(),
        check_vma=False))
    bstructs = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
                "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    with comms.record_traffic() as events:
        sm.lower(model.structs(), bstructs)
    jax.clear_caches()
    return events


for vpp in (1, 2):
    ev = trace_pipeline(vpp, "hier_tpp_8_16")
    hand = [e for e in ev
            if rl.tag_dim(e["tag"]) == "pp" and e["op"] == "ppermute"]
    assert hand, "no stage-handoff events recorded"
    t = rl.pipeline_ticks(HPP, HM, vpp)
    for e in hand:
        assert e["mult"] == t, (vpp, e["mult"], t)
        assert e["vpp"] == vpp, e
    # the priced bubble's denominator is exactly the executed tick count
    assert rl.bubble_fraction(HPP, HM, vpp) == (HPP - 1) / t
    if vpp == 2:
        hier_b = rl.link_bytes(hand, train=True)
        base_hand = [e for e in trace_pipeline(2, "baseline")
                     if rl.tag_dim(e["tag"]) == "pp"
                     and e["op"] == "ppermute"]
        base_b = rl.link_bytes(base_hand, train=True,
                               slow_axes=tuple({e["axis"]
                                                for e in base_hand}))
        hier_tot = hier_b["fast"] + hier_b["slow"]
        base_tot = base_b["fast"] + base_b["slow"]
        assert 0 < hier_tot < base_tot, (hier_tot, base_tot)
        print(f"vpp=2 handoff events: mult={t} ticks (x{vpp} per mb), "
              f"compressed bytes {hier_tot:.0f} < baseline {base_tot:.0f} "
              f"({hier_tot / base_tot:.1%})")
print("handoff ledger: mult == pipeline_ticks, vpp fact recorded, "
      "per-level bytes below baseline")

# ---- stage_ring_send identity == flat lax.ppermute full ring -------------
ring_mesh = compat.make_mesh((2, 4), ("data", "stage"))
ring = [(s, (s + 1) % 4) for s in range(4)]
rng = np.random.default_rng(0)
x = jnp.asarray(rng.integers(-8, 9, (8, 16)).astype(np.float32))
SPEC = P(("data", "stage"))


def smap(f):
    return jax.jit(jax.shard_map(f, mesh=ring_mesh, in_specs=(SPEC,),
                                 out_specs=SPEC, check_vma=False))


with schemes.use("baseline"):
    hier_fn = lambda a: comms.stage_ring_send(a, "stage")  # noqa: E731
    flat_fn = lambda a: jax.lax.ppermute(a, "stage", ring)  # noqa: E731
    np.testing.assert_array_equal(np.asarray(smap(hier_fn)(x)),
                                  np.asarray(smap(flat_fn)(x)))
    gh = smap(jax.grad(lambda a: jnp.sum(hier_fn(a) ** 2)))(x)
    gf = smap(jax.grad(lambda a: jnp.sum(flat_fn(a) ** 2)))(x)
    np.testing.assert_array_equal(np.asarray(gh), np.asarray(gf))
print("identity stage_ring_send == flat lax.ppermute ring: "
      "bit-exact (fwd+grad)")

print("VPP INTERLEAVED OK")
