"""Pipeline-parallel stage axis: single-device units + the 8-device check.

Single-device here: stage partitioning of layer plans, stage-axis
resolution (MeshInfo / comm_axes / physical specs), the roofline's bubble
+ stage-handoff terms, the per-level codec autotune, and the elastic-pp
checkpoint reshape.  The multi-device 1F1B equivalence matrix lives in
``tests/multidev/pp_check.py`` (subprocess, own XLA flag).
"""

import os
import types

import numpy as np
import pytest

from repro.analysis import roofline as rl
from repro.core import compat
from repro.launch import mesh as meshlib
from repro.models import transformer
from repro.models.config import ArchConfig, BlockGroup
from repro.models.params import D, MeshInfo, local_shape, physical_spec
from repro.train import checkpoint


def _cfg(groups):
    return ArchConfig(name="t", family="dense", n_layers=sum(g.n for g in groups),
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=512, groups=tuple(groups))


# --------------------------------------------------------------------------
# stage partitioning
# --------------------------------------------------------------------------

def test_stage_partition_uniform():
    cfg = _cfg([BlockGroup("attn", 8)])
    assert transformer.stage_partition(cfg, 4) == (BlockGroup("attn", 2),)
    assert transformer.stage_partition(cfg, 1) == (BlockGroup("attn", 8),)


def test_stage_partition_regroups_mixed_kinds():
    # per-stage structure [attn, attn, moe] tiles twice
    cfg = _cfg([BlockGroup("attn", 2), BlockGroup("moe", 1),
                BlockGroup("attn", 2), BlockGroup("moe", 1)])
    assert transformer.stage_partition(cfg, 2) == \
        (BlockGroup("attn", 2), BlockGroup("moe", 1))


def test_stage_partition_rejects_uneven_and_nonuniform():
    with pytest.raises(ValueError, match="do not split"):
        transformer.stage_partition(_cfg([BlockGroup("attn", 3)]), 2)
    # same count, different windows per stage -> not SPMD-uniform
    cfg = _cfg([BlockGroup("attn", 1, window=8), BlockGroup("attn", 1)])
    with pytest.raises(ValueError, match="not identical"):
        transformer.stage_partition(cfg, 2)
    with pytest.raises(ValueError, match="cannot hold"):
        transformer.stage_partition(
            _cfg([BlockGroup("mamba", 2), BlockGroup("shared_attn", 2)]), 2)


def test_stage_stacked_plan_specs():
    cfg = _cfg([BlockGroup("attn", 4)])
    mi = MeshInfo(tp=2, dp=2, pp=2, stage_axis="stage")
    plan = transformer.model_plan(cfg, mi)
    for d in _plan_defs(plan["groups"][0]):
        assert d.spec[0] == "stage" and d.shape[0] == 2, d
        assert d.shape[1] == 2  # 4 layers over 2 stages
    # embedding / final norm stay stage-replicated
    for d in _plan_defs({"e": plan["embed"], "n": plan["final_norm"]}):
        assert "stage" not in d.spec


def _plan_defs(plan):
    import jax
    from repro.models.params import ParamDef
    return jax.tree_util.tree_leaves(
        plan, is_leaf=lambda x: isinstance(x, ParamDef))


# --------------------------------------------------------------------------
# axis resolution
# --------------------------------------------------------------------------

def _fake_mesh(**axes):
    return types.SimpleNamespace(
        axis_names=tuple(axes),
        devices=types.SimpleNamespace(shape=tuple(axes.values())))


def test_stage_axis_resolution():
    flat = _fake_mesh(data=2, stage=2, model=2)
    assert meshlib.comm_axes(flat, "stage") == "stage"
    fact = _fake_mesh(data=2, ppnode=2, stage=2)
    assert meshlib.comm_axes(fact, "stage") == \
        compat.AxisPair(meshlib.PP_NODE_AXIS, meshlib.STAGE_AXIS)
    mi = MeshInfo.from_mesh(fact)
    assert mi.pp == 4 and mi.pp_node == 2
    assert mi.stage_axes == compat.AxisPair("ppnode", "stage")
    assert mi.sp_axes == ("ppnode", "stage")
    assert mi.all_axes == ("data", "ppnode", "stage", "model")
    # a stage-free mesh resolves to None / empty
    mi0 = MeshInfo.from_mesh(_fake_mesh(data=2, model=2))
    assert mi0.stage_axes is None and mi0.sp_axes == ()
    with pytest.raises(AssertionError):
        meshlib.comm_axes(_fake_mesh(data=2, model=2), "stage")


def test_stage_physical_spec_and_local_shape():
    d = D((4, 2, 8, 16), spec=("stage", None, None, "model"))
    mi = MeshInfo(tp=2, dp=2, pp=4, pp_node=2,
                  stage_axis="stage", pp_node_axis="ppnode")
    from jax.sharding import PartitionSpec as P
    assert physical_spec(d.spec, mi) == \
        P(("ppnode", "stage"), None, None, "model")
    assert local_shape(d, mi) == (1, 2, 8, 8)
    mi_flat = MeshInfo(tp=2, dp=2, pp=4, stage_axis="stage")
    assert physical_spec(d.spec, mi_flat) == P("stage", None, None, "model")


# --------------------------------------------------------------------------
# roofline: bubble + per-level codec autotune
# --------------------------------------------------------------------------

def test_bubble_fraction():
    assert rl.bubble_fraction(1, 8) == 0.0
    assert rl.bubble_fraction(4, 1) == pytest.approx(3 / 4)
    assert rl.bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert rl.bubble_fraction(2, 14) == pytest.approx(1 / 15)
    # step time inflates by 1 / (1 - bubble)
    assert rl.pipelined_step_time(1.0, 4, 4) == pytest.approx(7 / 4)
    assert rl.pipelined_step_time(2.0, 1, 1) == 2.0


def test_suggest_scheme_tracks_link_ratio():
    bw = rl.ICI_BW
    # fast inter-node links: no need to compress the outer stage harder
    mild = rl.suggest_scheme(bw, bw / 2)
    assert mild["scheme"] == "hier_zpp_16_16" and mild["outer_codec"] == "bq16"
    # ~16x slower DCN: rate-8 outer stage rebalances the pools
    mid = rl.suggest_scheme(bw, bw / 16)
    assert mid["scheme"] == "hier_zpp_8_16" and mid["outer_codec"] == "bq8"
    # ~32x: the aggressive rate-4 rung — ERROR-FEEDBACK wrapped (same wire
    # bytes as raw bq4, convergence-safe), so raw bq4 is never suggested
    hard = rl.suggest_scheme(bw, bw / 32)
    assert hard["scheme"] == "hier_zpp_ef4_16" \
        and hard["outer_codec"] == "ef:bq4"
    # extreme ratio: the low-rank rung (rank*(m+n) wire) is the last resort
    assert rl.suggest_scheme(bw, bw / 1000)["scheme"] == "hier_zpp_plr8_16"
    # the decision rule: picked candidate's slow pool no longer dominates
    c = mid["candidates"]["hier_zpp_8_16"]
    assert c["slow_s"] <= c["fast_s"]
    # the plr rung must price strictly below the rate-4 rung on the slow
    # pool (that is the whole point of the low-rank wire)
    cand = rl.suggest_scheme(bw, bw / 1000)["candidates"]
    assert cand["hier_zpp_plr8_16"]["slow_s"] \
        < cand["hier_zpp_ef4_16"]["slow_s"]
    # pricing is exposed for every rung, with the codecs the registered
    # scheme ACTUALLY resolves for dp_inner/dp_outer
    assert set(mid["candidates"]) == \
        {"hier_zpp_16_16", "hier_zpp_8_16", "hier_zpp_ef4_16",
         "hier_zpp_plr8_16"}
    from repro.core import schemes
    for name, info in mid["candidates"].items():
        assert schemes.get(name).codec("dp_outer").name == \
            info["outer_codec"], name
        assert schemes.get(name).codec("dp_inner").name == "bq16", name


def test_stage_handoff_seconds_filters_pp_events():
    mk = dict(dtype="float32", mult=1, remat=False, bidir=False,
              bwd_op="ppermute", op="ppermute", n=4, elems=1000,
              codec_fwd="none", codec_bwd="none")
    ev = [dict(mk, tag="pp", axis="stage", level="outer"),
          dict(mk, tag="tp_fwd", axis="model", level="flat")]
    pp_s = rl.stage_handoff_seconds(ev, train=False)
    all_s = rl.collective_seconds(ev, train=False)
    assert 0 < pp_s < all_s
    assert pp_s == pytest.approx(1000 * 4 / rl.DCN_BW)


# --------------------------------------------------------------------------
# elastic-pp checkpoint reshape
# --------------------------------------------------------------------------

def test_stage_reshape_refactors_stage_dim():
    a = np.arange(2 * 3 * 4 * 5).reshape(2, 3, 4, 5)
    # pp=2 -> pp=1 (merge), pp=2 -> pp=3 of 2 layers, pp=1 -> pp=2
    assert checkpoint.stage_reshape(a, (6, 4, 5)).shape == (6, 4, 5)
    assert checkpoint.stage_reshape(a, (3, 2, 4, 5)).shape == (3, 2, 4, 5)
    flat = a.reshape(6, 4, 5)
    out = checkpoint.stage_reshape(flat, (2, 3, 4, 5))
    np.testing.assert_array_equal(out, a)  # stage-major IS layer order
    with pytest.raises(ValueError):
        checkpoint.stage_reshape(a, (5, 4, 5))
    with pytest.raises(ValueError):  # per-layer shape must be preserved
        checkpoint.stage_reshape(a, (2, 3, 5, 4))


def test_checkpoint_restore_reshapes_mismatched_leaves(tmp_path):
    import jax
    from repro.models.params import Pv
    tree = {"g": Pv(np.arange(24.0).reshape(2, 3, 4), ("stage", None, None)),
            "e": Pv(np.ones((4, 4)), (None, None))}
    checkpoint.save(tmp_path, 3, tree)
    like = {"g": Pv(jax.ShapeDtypeStruct((6, 4), np.float32),
                    (None, None)),
            "e": Pv(jax.ShapeDtypeStruct((4, 4), np.float32),
                    (None, None))}
    out, man = checkpoint.restore(tmp_path, like)
    assert man["step"] == 3
    np.testing.assert_array_equal(np.asarray(out["g"].v),
                                  np.arange(24.0).reshape(6, 4))
    assert out["g"].spec == (None, None)  # target plan's spec wins


# --------------------------------------------------------------------------
# pp=1 gradient accumulation covers every family the flat trainer does
# --------------------------------------------------------------------------

def test_microbatch_grad_accum_supports_shared_attn():
    """zamba2's shared_attn can't be *staged* (cross-stage weight sharing)
    but plain microbatching (pp=1) must keep working — regression for the
    flat _stage_body dropping the shared-weights argument."""
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.models.model import Model
    from repro.train.pipeline import PipelineTrainer
    from repro.train.train_step import make_trainer
    mesh = meshlib.make_mesh(1, 1)
    model = Model(configs.get("zamba2-1.2b").reduced(),
                  MeshInfo.from_mesh(mesh))
    tr = make_trainer(model, mesh, n_micro=2)
    assert isinstance(tr, PipelineTrainer)
    pstructs = model.structs()
    ostructs = jax.eval_shape(tr.opt_init, pstructs)
    binputs = {"tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32),
               "labels": jax.ShapeDtypeStruct((4, 16), jnp.int32)}
    tr.step.lower(pstructs, ostructs, tr.codec_structs(),
                  binputs)  # must trace cleanly


@pytest.mark.parametrize("arch", ["whisper-base", "qwen2-vl-72b"])
def test_microbatch_grad_accum_encoder_and_vision(arch):
    """pp=1 microbatching covers enc-dec and M-RoPE archs: the 2-microbatch
    pipeline loss matches the flat full-batch loss."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro import configs
    from repro.core import comms, schemes
    from repro.models.model import Model
    from repro.train.pipeline import pipeline_loss_fn
    from repro.train.train_step import batch_specs
    mesh = meshlib.make_mesh(1, 1)
    cfg = configs.get(arch).reduced()
    model = Model(cfg, MeshInfo.from_mesh(mesh))
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    B, S = 4, 16
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                   jnp.int32)}
    if cfg.encoder_layers:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, S, cfg.d_model)).astype(np.float32))
    if cfg.mrope:
        batch["vision"] = jnp.asarray(
            rng.normal(size=(B, S, cfg.d_model)).astype(np.float32))
        batch["vis_mask"] = jnp.asarray(
            rng.integers(0, 2, (B, S)).astype(bool))
        batch["pos3"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :, None], (B, S, 3))
    bspecs = batch_specs(cfg, model.mi)

    def run(loss_fn):
        def f(p, b):
            with schemes.use("baseline"), comms.vma_mode(False):
                return loss_fn(p, b)[0]
        sm = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(model.specs(), bspecs), out_specs=P(),
            check_vma=False))
        return float(sm(params, batch))

    l_mb = run(pipeline_loss_fn(model, 2))
    l_fb = run(model.loss_fn)
    np.testing.assert_allclose(l_mb, l_fb, rtol=1e-6)


# --------------------------------------------------------------------------
# interleaved virtual stages: round-robin partition + tick schedule
# --------------------------------------------------------------------------

def test_stage_partition_vpp_round_robin():
    cfg = _cfg([BlockGroup("attn", 8)])
    # vpp=1 IS the contiguous layout — same per-stage structure
    assert transformer.stage_partition(cfg, 4, 1) == \
        transformer.stage_partition(cfg, 4)
    # pp=2 x vpp=2 -> 4 chunks of 2 layers each
    assert transformer.stage_partition(cfg, 2, 2) == (BlockGroup("attn", 2),)
    assert transformer.stage_partition(cfg, 2, 4) == (BlockGroup("attn", 1),)
    # the error names the interleaved layout, not just "pp"
    with pytest.raises(ValueError, match=r"do not split into pp=2 x vpp=3"):
        transformer.stage_partition(cfg, 2, 3)


def test_chunk_layer_ranges_cover_every_layer_once():
    ranges = transformer.chunk_layer_ranges(8, 2, 2)
    assert set(ranges) == {(s, v) for s in range(2) for v in range(2)}
    covered = []
    for (s, v), (lo, hi) in ranges.items():
        assert hi - lo == 2
        assert lo == (v * 2 + s) * 2  # round-robin: chunk c = v*pp + s
        covered += list(range(lo, hi))
    # every layer assigned exactly once
    assert sorted(covered) == list(range(8))
    # vpp=1 degenerates to the contiguous split
    assert transformer.chunk_layer_ranges(8, 4) == \
        {(s, 0): (2 * s, 2 * s + 2) for s in range(4)}


def test_stage_stacked_plan_specs_vpp():
    cfg = _cfg([BlockGroup("attn", 8)])
    mi = MeshInfo(tp=2, dp=2, pp=2, stage_axis="stage")
    plan = transformer.model_plan(cfg, mi, vpp=2)
    for d in _plan_defs(plan["groups"][0]):
        # leading (vpp, pp) dims: vpp replicated, pp sharded over "stage"
        assert d.spec[:2] == (None, "stage"), d
        assert d.shape[:2] == (2, 2), d
        assert d.shape[2] == 2  # 8 layers over 2x2 chunks
    # embedding / final norm placement unchanged by interleaving
    for d in _plan_defs({"e": plan["embed"], "n": plan["final_norm"]}):
        assert "stage" not in d.spec


def test_interleaved_schedule_simulation():
    """numpy re-implementation of the tick decode in train/pipeline.py:
    every (rank, virtual slice, microbatch) cell runs exactly once, each
    chunk consumes its predecessor's output from the previous tick, and
    per-rank idle ticks == pp - 1 — so the bubble the roofline prices is
    exactly the tick count the scan executes."""
    for pp, V, M in [(2, 2, 4), (4, 2, 8), (4, 4, 4), (2, 1, 3), (4, 1, 4)]:
        T = rl.pipeline_ticks(pp, M, V)
        assert T == M * V + pp - 1
        done, idle = {}, {s: 0 for s in range(pp)}
        for t in range(T):
            for s in range(pp):
                u = t - s
                if not (0 <= u < M * V):
                    idle[s] += 1
                    continue
                g, r = u // (pp * V), u % pp
                v = (u % (pp * V)) // pp
                m = g * pp + r
                assert (s, v, m) not in done
                done[(s, v, m)] = t
        # exactly once per (rank, slice, microbatch)
        assert len(done) == pp * V * M
        assert set(done) == {(s, v, m) for s in range(pp)
                             for v in range(V) for m in range(M)}
        # chunk c = v*pp + s consumes chunk c-1's output from tick t-1
        for (s, v, m), t in done.items():
            c = v * pp + s
            if c:
                assert done[((c - 1) % pp, (c - 1) // pp, m)] == t - 1
        # the priced bubble: pp-1 idle ticks per rank out of T
        assert all(idle[s] == pp - 1 for s in range(pp))
        assert rl.bubble_fraction(pp, M, V) == pytest.approx((pp - 1) / T)


def test_bubble_fraction_vpp():
    assert rl.pipeline_ticks(4, 4) == 7
    assert rl.pipeline_ticks(4, 4, 2) == 11
    assert rl.pipeline_ticks(1, 8, 4) == 8  # no stage axis: one pass per mb
    assert rl.bubble_fraction(4, 4, 2) == pytest.approx(3 / 11)
    assert rl.bubble_fraction(4, 4, 4) == pytest.approx(3 / 19)
    # interleaving strictly shrinks the bubble at fixed (pp, n_micro)
    assert rl.bubble_fraction(4, 4, 2) < rl.bubble_fraction(4, 4, 1)
    assert rl.pipelined_step_time(1.0, 4, 4, 2) == pytest.approx(11 / 8)


def test_parse_remat_policy():
    from repro.train.pipeline import parse_remat_policy as prp
    assert prp(None, 2) == ("none", (False, False), False)
    assert prp("none", 2) == ("none", (False, False), False)
    assert prp("full", 2) == ("full", (True, True), False)
    assert prp("full+offload", 2) == ("full", (True, True), True)
    assert prp("per_stage:1", 3) == ("per_stage", (False, True, False), False)
    assert prp("per_stage:0,2+offload", 3) == \
        ("per_stage", (True, False, True), True)
    # uniform per_stage specs canonicalize to full / none
    assert prp("per_stage:0,1", 2) == ("full", (True, True), False)
    assert prp("per_stage:", 2) == ("none", (False, False), False)
    with pytest.raises(ValueError, match="out of range"):
        prp("per_stage:2", 2)
    with pytest.raises(ValueError, match="needs remat"):
        prp("none+offload", 2)
    with pytest.raises(ValueError, match="unknown"):
        prp("sometimes", 2)
    with pytest.raises(ValueError, match="comma list"):
        prp("per_stage:a,b", 2)


def test_activation_stash_and_remat_tradeoff():
    d, tok, lpr, m, pp = 64, 128, 8, 4, 4
    t = rl.pipeline_ticks(pp, m)
    carry = tok * d * 2
    full = rl.activation_stash_bytes(d, tok, lpr, m, pp)
    remat = rl.activation_stash_bytes(d, tok, lpr, m, pp, remat=True)
    assert remat == t * carry  # only the scan carry survives under remat
    assert full == t * (carry + lpr * tok * d * 8.0 * 2)
    assert remat < full
    # vpp splits the per-tick layer stash by V (more, smaller ticks)
    v2 = rl.activation_stash_bytes(d, tok, lpr, 2 * pp, pp, vpp=2)
    assert v2 == rl.pipeline_ticks(pp, 2 * pp, 2) * \
        (carry + lpr / 2 * tok * d * 8.0 * 2)
    r = rl.remat_tradeoff(d, tok, lpr, m, pp, vpp=2, handoff_s=0.5)
    assert r["ticks"] == rl.pipeline_ticks(pp, m, 2)
    assert r["bubble_fraction"] == rl.bubble_fraction(pp, m, 2)
    assert r["bytes_saved"] == r["stash_bytes"] - r["stash_bytes_remat"] > 0
    assert r["remat_extra_seconds"] > 0
    assert r["stage_handoff_seconds"] == 0.5


def test_stage_reshape_interleaved_vpp_dim():
    # (vpp=2, pp=2, layers=3, d=4): the v-major flatten of the leading
    # (vpp, pp) dims is chunk order == contiguous layer order
    a = np.arange(2 * 2 * 3 * 4).reshape(2, 2, 3, 4)
    np.testing.assert_array_equal(
        checkpoint.stage_reshape(a, (4, 3, 4)), a.reshape(4, 3, 4))
    flat = checkpoint.stage_reshape(a, (12, 4))
    np.testing.assert_array_equal(flat, a.reshape(12, 4))
    # flat -> interleaved and interleaved -> different contiguous topology
    np.testing.assert_array_equal(
        checkpoint.stage_reshape(flat, (2, 2, 3, 4)), a)
    np.testing.assert_array_equal(
        checkpoint.stage_reshape(a, (2, 6, 4)), a.reshape(2, 6, 4))
    # incompatible target fails LOUDLY, naming the interleaved layout
    with pytest.raises(ValueError, match=r"interleaved \(vpp=2, pp=2"):
        checkpoint.stage_reshape(a, (5, 4))


# --------------------------------------------------------------------------
# the 8-device pipeline equivalence matrix (subprocess)
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.multidev
def test_pp_1f1b_equivalence_and_bytes():
    from test_comms_multidev import run_script
    out = run_script("pp_check.py", timeout=1800)
    assert "bit-exact over 10 steps" in out
    assert "PP STAGE AXIS OK" in out


@pytest.mark.slow
@pytest.mark.multidev
def test_vpp_interleaved_equivalence():
    from test_comms_multidev import run_script
    out = run_script("vpp_check.py", timeout=1800)
    assert "== existing 1F1B: bit-exact" in out
    assert "vpp=2 interleaved == vpp=1" in out
    assert "grad-exact vs no-remat" in out
    assert "VPP INTERLEAVED OK" in out
