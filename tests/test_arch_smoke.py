"""Per-architecture smoke tests (1 CPU device, reduced configs).

Instantiates the REDUCED config of each assigned architecture and runs one
forward/train step, asserting output shapes and finite values — per the
assignment brief.  (Full configs are exercised via the dry-run only.)
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core import comms, compat, schemes
from repro.models.model import Model
from repro.models.params import MeshInfo, count_params

_MESH = None


def mesh1():
    global _MESH
    if _MESH is None:
        _MESH = compat.make_mesh((1, 1), ("data", "model"))
    return _MESH


def make_batch(cfg, B=2, S=16, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)}
    specs = {"tokens": P("data", None), "labels": P("data", None)}
    if cfg.encoder_layers:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, S, cfg.d_model)), jnp.float32)
        specs["frames"] = P("data", "model", None)
    if cfg.mrope:
        batch["vision"] = jnp.asarray(
            rng.normal(size=(B, S, cfg.d_model)), jnp.float32)
        batch["vis_mask"] = jnp.asarray(rng.integers(0, 2, (B, S)) > 0)
        batch["pos3"] = jnp.asarray(np.broadcast_to(
            np.arange(S)[None, :, None], (B, S, 3)).astype(np.int32))
        specs["vision"] = P("data", "model", None)
        specs["vis_mask"] = P("data", "model")
        specs["pos3"] = P("data", "model", None)
    return batch, specs


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_reduced_forward_and_grad(arch):
    cfg = configs.get(arch).reduced()
    mesh = mesh1()
    mi = MeshInfo.from_mesh(mesh)
    model = Model(cfg, mi)
    params = model.init(jax.random.key(1))
    batch, bspecs = make_batch(cfg)

    def step(params, batch):
        (loss, met), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
        gn = sum(jnp.sum(jnp.abs(g.astype(jnp.float32)))
                 for g in jax.tree.leaves(grads))
        gn = jax.lax.psum(comms.varying_all(gn, ("data", "model")),
                          ("data", "model"))
        return loss, met["xent"], gn

    sm = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(model.specs(), bspecs),
        out_specs=(P(), P(), P())))
    with schemes.use("baseline"):
        loss, xent, gn = sm(params, batch)
    assert np.isfinite(float(loss)), arch
    assert np.isfinite(float(gn)) and float(gn) > 0, arch
    # untrained loss should be near ln(V)
    assert abs(float(xent) - np.log(cfg.vocab_size)) < 1.0, arch


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_full_config_dims(arch):
    """The FULL configs carry the exact assigned dims (no allocation)."""
    cfg = configs.get(arch)
    brief = {
        "gemma3-1b": (26, 1152, 4, 1, 6912, 262144),
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "gemma3-4b": (34, 2560, 8, 4, 10240, 262144),
        "minitron-4b": (32, 3072, 24, 8, 9216, 256000),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "kimi-k2-1t-a32b": (61, 7168, 64, 8, 18432, 163840),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 0, 151936),
        "qwen2-vl-72b": (80, 8192, 64, 8, 29568, 152064),
    }[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab_size) == brief
    n_group_layers = sum(g.n for g in cfg.layer_groups)
    expect = cfg.n_layers + (cfg.encoder_layers or 0)
    if cfg.attn_every:   # zamba2: shared-attn insertions add groups
        expect += sum(1 for g in cfg.layer_groups
                      if g.kind == "shared_attn")
    assert n_group_layers == expect, (arch, n_group_layers, expect)


def test_param_counts_plausible():
    """Parameter counts are in the right ballpark for the headline sizes."""
    mi = MeshInfo()
    for arch, lo, hi in [("gemma3-1b", 0.7e9, 2.1e9),
                         ("qwen2-72b", 60e9, 85e9),
                         ("kimi-k2-1t-a32b", 0.8e12, 1.3e12),
                         ("qwen3-moe-235b-a22b", 180e9, 300e9),
                         ("xlstm-1.3b", 0.8e9, 2.0e9),
                         ("zamba2-1.2b", 0.8e9, 2.0e9)]:
        cfg = configs.get(arch)
        n = count_params(Model(cfg, mi).plan)
        assert lo <= n <= hi, (arch, n)
