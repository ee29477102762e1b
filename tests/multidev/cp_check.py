"""Context-parallel (cp) axis: ring attention equivalence + byte acceptance.

On an 8-device host:

  * **ring == full attention**: :func:`ring_attention` on a
    ``(data=2, cp=2, model=2)`` mesh — zigzag-sharded sequence, KV blocks
    rotating around the cp ring under identity codecs — matches a
    single-device :func:`full_attention` reference within fp tolerance
    (the log-sum-exp merge order is the only difference) across causal,
    sliding-window and ``k_valid`` masking configs;
  * **cp=2 training == cp=1**: short seeded training runs on the cp mesh
    (head attention mode, and ring mode with the tp KV gather) produce
    the same losses as the identical model on a cp-free mesh, within fp
    tolerance, with the host batch zigzag-permuted exactly as
    ``repro.launch.train`` does;
  * **ledger attribution**: the ring-KV hops land in the ``cp`` ledger
    dimension — ``cp@ring_kv`` tags, ``per_dim["cp"] > 0`` and ZERO
    ``pp``-dimension bytes on a pipeline-free mesh (regression for the
    old mislabeled ``pp@ring_kv`` site);
  * **compressed < uncompressed**: on a cp-node-factored
    ``(data, cpnode, cp)`` mesh, a hier scheme's node-crossing ring hops
    put strictly fewer bytes on the slow link than the identity-codec
    baseline, with per-level ``cp/inner`` / ``cp/outer`` breakdown.
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
from repro.models.attention import full_attention, ring_attention
from repro.models.model import Model
from repro.models.params import MeshInfo
from repro.train.train_step import (Trainer, batch_specs, zigzag_seq_indices,
                                    zigzag_shard_seq)

# ---- ring_attention == full_attention under zigzag cp sharding ----------
B, S, H, KV, hd = 2, 32, 4, 2, 16
CP = 2
mesh = make_mesh(2, 2, cp=CP)
mi = MeshInfo.from_mesh(mesh)
rng = np.random.default_rng(0)
q = rng.standard_normal((B, S, H, hd), np.float32)
k = rng.standard_normal((B, S, KV, hd), np.float32)
v = rng.standard_normal((B, S, KV, hd), np.float32)
pos = np.broadcast_to(np.arange(S, dtype=np.int32)[None], (B, S)).copy()
kval = rng.random((B, S)) < 0.8
idx = zigzag_seq_indices(CP, S)

QS, PS = P("data", "cp"), P("data", "cp")


def ring_sharded(causal, window, k_valid):
    def f(q, k, v, pos, vl):
        with schemes.use("baseline"), comms.vma_mode(False):
            return ring_attention(q, k, v, pos, pos, mi, causal, window,
                                  k_valid=vl if k_valid else None)
    sm = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(QS, QS, QS, PS, PS), out_specs=QS,
        check_vma=False))
    # zigzag host permutation, then contiguous cp sharding — rank i holds
    # global half-chunks i and 2cp-1-i, exactly the training layout
    out = sm(q[:, idx], k[:, idx], v[:, idx], pos[:, idx],
             jnp.asarray(kval[:, idx]))
    return np.asarray(out)


for causal, window, k_valid in [(True, 0, False), (True, 8, False),
                                (False, 0, True), (True, 0, True)]:
    ref = full_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         jnp.asarray(pos), jnp.asarray(pos), causal, window,
                         k_valid=jnp.asarray(kval) if k_valid else None)
    got = ring_sharded(causal, window, k_valid)
    np.testing.assert_allclose(got, np.asarray(ref)[:, idx], rtol=2e-5,
                               atol=2e-5,
                               err_msg=f"{causal=} {window=} {k_valid=}")
print(f"ring == full attention on (data=2, cp=2, model=2): "
      f"causal/window/k_valid all within fp tolerance")
jax.clear_caches()

# ---- cp=2 training == cp=1, head and ring attention modes ---------------
cfg = configs.get("qwen2-72b").reduced()
data = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                  global_batch=8, seed=0))
STEPS = 5


def run_losses(cfg, mesh, scheme="baseline"):
    mi = MeshInfo.from_mesh(mesh)
    model = Model(cfg, mi)
    tr = Trainer(model, mesh, scheme=scheme)
    params, ostate, cstate = tr.init_all(jax.random.key(0))
    bspecs = batch_specs(cfg, mi)
    losses = []
    for step in range(STEPS):
        np_batch = zigzag_shard_seq(data.batch(step), mi.cp)
        batch = {kk: jax.device_put(vv, NamedSharding(mesh, bspecs[kk]))
                 for kk, vv in np_batch.items()}
        params, ostate, cstate, m = tr.step(params, ostate, cstate, batch)
        losses.append(float(m["loss"]))
    jax.clear_caches()
    return losses


for mode in ("head", "ring"):
    mcfg = cfg.replace(attn_mode=mode)
    l_cp = run_losses(mcfg, make_mesh(2, 2, cp=2))
    l_flat = run_losses(mcfg, make_mesh(2, 2))
    np.testing.assert_allclose(l_cp, l_flat, rtol=1e-4, atol=1e-5,
                               err_msg=f"attn_mode={mode}")
    print(f"cp=2 training == cp=1 ({mode} mode) over {STEPS} steps "
          f"(final loss {l_cp[-1]:.6f} vs {l_flat[-1]:.6f})")

# compressed KV hops: the same cp mesh trains under a real codec scheme
l_z = run_losses(cfg, make_mesh(2, 2, cp=2), scheme="zhybrid_16_8")
assert all(np.isfinite(l_z)), l_z
assert l_z[-1] < l_z[0], ("compressed cp run did not descend", l_z)
print(f"cp=2 zhybrid_16_8 run finite and descending "
      f"({l_z[0]:.4f} -> {l_z[-1]:.4f})")

# ---- ledger: ring-KV bytes attributed to cp, never pp -------------------
mesh = make_mesh(2, 2, cp=2)
mi = MeshInfo.from_mesh(mesh)
model = Model(cfg, mi)
bspecs = batch_specs(cfg, mi)
pspecs = model.specs()


def fwd(p, b):
    with schemes.use("zhybrid_16_8"), comms.vma_mode(False):
        return model.loss_fn(p, b)[0]


sm = jax.jit(jax.shard_map(fwd, mesh=mesh, in_specs=(pspecs, bspecs),
                           out_specs=P(), check_vma=False))
shapes = jax.eval_shape(model.init, jax.random.key(0))
bshapes = {kk: jax.ShapeDtypeStruct((8, 16), jnp.int32)
           for kk in ("tokens", "labels")}
with comms.record_traffic() as events:
    sm.lower(shapes, bshapes)
tags = {ev["tag"] for ev in events}
assert any(t.startswith("cp@ring_kv") for t in tags), tags
assert not any(rl.tag_dim(t) == "pp" for t in tags), \
    ("ring-KV hops leaked into the pp dimension", tags)
summ = rl.ledger_summary(events, train=True)
assert summ["per_dim"]["cp"] > 0
assert rl.cp_ring_seconds(events, train=True) > 0
print(f"ledger: ring-KV hops ride the cp dimension "
      f"({summ['per_dim']['cp']:.0f} bytes, zero pp bytes)")
jax.clear_caches()

# ---- hier cp ring: compressed inter-node hops < uncompressed baseline ---
hmesh = make_mesh(2, 1, cp=4, cp_nodes=2)
CPAX = compat.AxisPair("cpnode", "cp")
RING = [(j, (j + 1) % 4) for j in range(4)]


def trace_ring(scheme):
    smh = jax.jit(jax.shard_map(
        lambda a: comms.ppermute(a, CPAX, RING, comms.site("cp", "ring_kv")),
        mesh=hmesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False))
    with schemes.use(scheme), comms.record_traffic() as ev:
        smh.lower(jax.ShapeDtypeStruct((2, 4096), jnp.float32))
    jax.clear_caches()
    return ev


base_ev = trace_ring("baseline")
comp_ev = trace_ring("hier_tpp_8_16")
comp_sum = rl.ledger_summary(comp_ev, train=True)
assert comp_sum["per_dim_level"]["cp/inner"] > 0
assert comp_sum["per_dim_level"]["cp/outer"] > 0
base_slow = rl.link_bytes(base_ev, train=True)["slow"]
comp_slow = rl.link_bytes(comp_ev, train=True)["slow"]
assert comp_slow == comp_sum["per_dim_level"]["cp/outer"]
assert 0 < comp_slow < base_slow, (comp_slow, base_slow)
print(f"inter-node ring-KV bytes: hier_tpp_8_16={comp_slow:.0f} < "
      f"baseline={base_slow:.0f} ({comp_slow / base_slow:.1%})")

print("CP RING OK")
