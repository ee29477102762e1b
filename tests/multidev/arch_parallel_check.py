"""Cross-mesh consistency: every arch must produce the same loss on a
(1,1) mesh and a (data=2, model=4) mesh under the baseline scheme, and a
close loss under compressed schemes."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import configs
from repro.models.model import Model
from repro.models.params import MeshInfo
from repro.core import compat, schemes

rng = np.random.default_rng(0)

def make_batch(cfg, B=4, S=16):
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)}
    specs = {"tokens": P("data", None), "labels": P("data", None)}
    if cfg.encoder_layers:
        batch["frames"] = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)), jnp.float32)
        specs["frames"] = P("data", "model", None)
    if cfg.mrope:
        batch["vision"] = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)), jnp.float32)
        batch["vis_mask"] = jnp.asarray(rng.integers(0, 2, (B, S)) > 0)
        batch["pos3"] = jnp.asarray(np.broadcast_to(np.arange(S)[None, :, None], (B, S, 3)).astype(np.int32))
        specs["vision"] = P("data", "model", None)
        specs["vis_mask"] = P("data", "model")
        specs["pos3"] = P("data", "model", None)
    return batch, specs

def loss_on_mesh(cfg, shape, scheme, batch_and_specs, params_src=None):
    mesh = compat.make_mesh(shape, ("data", "model"))
    mi = MeshInfo.from_mesh(mesh)
    m = Model(cfg, mi)
    params = m.init(jax.random.key(1))
    batch, bspecs = batch_and_specs
    def step(params, batch):
        return m.loss_fn(params, batch)
    sm = jax.jit(jax.shard_map(step, mesh=mesh,
                               in_specs=(m.specs(), bspecs),
                               out_specs=(P(), {"xent": P(), "tokens": P()}),
                               check_vma=True))
    with schemes.use(scheme):
        loss, met = sm(params, batch)
    return float(loss)

fails = []
for arch in configs.ARCH_IDS:
    cfg = configs.get(arch).reduced()
    bs = make_batch(cfg)
    l1 = loss_on_mesh(cfg, (1, 1), "baseline", bs)
    l2 = loss_on_mesh(cfg, (2, 4), "baseline", bs)
    lz = loss_on_mesh(cfg, (2, 4), "zhybrid_24_8", bs)
    base_ok = abs(l1 - l2) < 2e-3
    z_ok = abs(l1 - lz) < 0.15
    status = "OK" if (base_ok and z_ok) else "FAIL"
    if status == "FAIL":
        fails.append(arch)
    print(f"{arch:22s} 1x1={l1:.5f} 2x4={l2:.5f} zhy={lz:.5f} {status}")
assert not fails, fails
print("PARALLEL CONSISTENCY OK")
