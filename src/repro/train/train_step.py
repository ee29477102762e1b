"""The jitted, shard_map'd training step.

One step = forward -> backward -> (compressed) gradient sync -> ZeRO-1
update -> (compressed) param all-gather, all inside a single XLA program so
the latency-hiding scheduler can overlap ring hops with compute.

Codec state: stateful codecs (``ef:*`` error-feedback residuals, ``plr*``
low-rank warm factors) carry a per-site state pytree that threads through
the jitted step NEXT TO ``opt_state``::

    params, opt_state, codec_state, metrics = trainer.step(
        params, opt_state, codec_state, batch)

The template is enumerated once per (plan, model) by
:meth:`Trainer.codec_sites` + ``CommPlan.codec_state_template`` — one slot
per stateful grad-sync site, keyed by the site's ledger tag; stateless
policies yield an EMPTY pytree (zero cost, nothing donated, nothing
checkpointed).  The step binds the state around the optimizer with
``comms.codec_state_io`` so the sync sites can read/write their slots.

Note on ``check_vma=False``: the updated class-B/C params come out of an
all-gather over the data axis — *values* replicated, but typed "varying"
by the vma system, which would reject the replicated out_specs.  The math
is validated by the cross-mesh consistency tests (same loss on (1,1) and
(2,4) meshes), so the step runs with vma checking off, classic shard_map
semantics.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import comms, compat
from repro.core import policy as policy_lib
from repro.models.model import Model
from repro.models.params import MeshInfo
from repro.train.optimizer import Adam, AdamConfig, _split_classes


def batch_specs(cfg, mi: MeshInfo):
    """PartitionSpecs for the training batch dict.

    With a cp axis the sequence dim of tokens/labels shards over the
    (possibly node-factored) cp axes: each cp rank's contiguous mesh slice
    holds its zigzag sequence chunk — the host side feeds batches through
    :func:`zigzag_shard_seq` so contiguous device slicing delivers the
    load-balanced (non-contiguous) token sets."""
    seq = tuple(mi.cp_phys_axes) or None
    sp = {"tokens": P(mi.batch_axes, seq), "labels": P(mi.batch_axes, seq)}
    if cfg.encoder_layers:
        sp["frames"] = P(mi.batch_axes, mi.tp_axes, None)
    if cfg.mrope:
        sp["vision"] = P(mi.batch_axes, mi.tp_axes, None)
        sp["vis_mask"] = P(mi.batch_axes, mi.tp_axes)
        sp["pos3"] = P(mi.batch_axes, mi.tp_axes, None)
    return sp


def zigzag_seq_indices(cp: int, S: int):
    """Global sequence order whose contiguous cp-sharding yields the
    zigzag (causal load-balanced) chunks: rank i owns half-chunks i and
    2cp-1-i of length S/(2cp).  Matches ``Model._positions`` exactly —
    ``indices[r * S//cp + j]`` is the global position of cp rank r's
    j-th local token."""
    import numpy as np
    assert S % (2 * cp) == 0, \
        f"seq len {S} must divide 2*cp={2 * cp} for zigzag cp sharding"
    c = S // (2 * cp)
    parts = []
    for i in range(cp):
        parts.append(np.arange(i * c, (i + 1) * c))
        parts.append(np.arange((2 * cp - 1 - i) * c, (2 * cp - i) * c))
    return np.concatenate(parts)


def zigzag_shard_seq(batch: dict, cp: int) -> dict:
    """Host-side seq permutation of tokens/labels for a cp mesh (identity
    when cp == 1).  Labels ride the same permutation, so each position
    keeps its own next-token target."""
    if cp <= 1:
        return batch
    idx = zigzag_seq_indices(cp, batch["tokens"].shape[1])
    out = dict(batch)
    for key in ("tokens", "labels"):
        out[key] = batch[key][:, idx]
    return out


METRIC_SPECS = {"loss": P(), "xent": P(), "tokens": P(),
                "grad_norm": P(), "lr": P()}


class Trainer:
    """Builds the jitted train/init steps for (model, policy, optimizer).

    ``scheme`` is anything :func:`repro.core.policy.compile_plan` accepts:
    a registered scheme name, a :class:`~repro.core.schemes.Scheme` (the
    adapter path — every named scheme is sugar over rules), or a
    :class:`~repro.core.policy.CommPolicy` of explicit rules.  It is
    compiled against the model's mesh ONCE here; the jitted step binds
    the resulting immutable :class:`~repro.core.policy.CommPlan`, so no
    comms call re-resolves a thread-local scheme at trace time."""

    def __init__(self, model: Model, mesh, scheme="baseline",
                 opt_cfg: AdamConfig | None = None, ring_bidir: bool = False,
                 ring_chunks: int = 1, tune: bool = False):
        self.model = model
        self.mesh = mesh
        self.policy = policy_lib.as_policy(scheme)
        self.plan = self.policy.compile(model.mi)
        self.ring_bidir = ring_bidir
        self.ring_chunks = ring_chunks
        self.tune = bool(tune)
        self.opt = Adam(opt_cfg or AdamConfig(), model.mi)
        self._check_mesh()
        self._build()

    # ------------------------------------------------------------------
    def _check_mesh(self):
        assert self.model.mi.pp == 1, \
            "mesh has a pipeline stage axis — use " \
            "repro.train.pipeline.PipelineTrainer (or make_trainer)"

    def _loss_fn(self):
        """The per-step loss callable (inside shard_map); the pipeline
        trainer overrides this with the microbatched 1F1B schedule."""
        return self.model.loss_fn

    # ------------------------------------------------------------------
    def opt_state_specs(self):
        from repro.models.params import physical_spec
        mi = self.model.mi
        leaves, _, classes = _split_classes(self.model.structs())
        fsdp = []
        for l, c in zip(leaves, classes):
            if c != "A":
                fsdp.append(None)
            else:
                sp = physical_spec(l.spec, mi)
                fsdp.append({"master": sp, "m": sp, "v": sp})
        # the ZeRO-1 flat chunk is a *different* vector on every stage /
        # model rank (it flattens that rank's local B/C shards), so its
        # global layout shards over the joint (stage?, model, data) axes —
        # this is what makes a host round-trip (checkpoint save/restore of
        # opt_state) lossless instead of silently keeping one replica.
        joint = tuple(mi.sp_axes) + tuple(mi.mp_axes) + (mi.data_axis,)
        zero1 = P(joint)
        if self.opt.cfg.state_bits == 8:
            mv = {"q_hi": zero1, "q_lo": None, "scale": zero1}
        else:
            mv = zero1
        return {"fsdp": fsdp, "master": zero1, "m": mv, "v": mv, "step": P()}

    # ------------------------------------------------------------------
    # codec state: template, specs, and host-side init
    # ------------------------------------------------------------------
    def _local_leaves(self):
        """(local_shape, class) per param leaf — the shard shapes the
        optimizer sees inside shard_map (via ``params.local_shape``, the
        one canonical spec-to-mesh-axis division)."""
        import types

        from repro.models.params import local_shape
        mi = self.model.mi
        leaves, _, classes = _split_classes(self.model.structs())
        return [(local_shape(types.SimpleNamespace(shape=l.v.shape,
                                                   spec=l.spec), mi),
                 c, l.spec)
                for l, c in zip(leaves, classes)]

    def _axis_sizes(self):
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    def _axsize(self, axes) -> int:
        if axes is None:
            return 1
        sizes = self._axis_sizes()
        if isinstance(axes, str):
            return sizes[axes]
        return math.prod(sizes[a] for a in axes)

    def _fold_specs(self):
        """(dim, name, axes, elems) of the optimizer's whole-grad fold
        psums — the cp / tp class-C / pp stage-replicated sites of
        :meth:`Adam.apply`.  Carried-state codecs may ride these (flat or
        two-level), so they join the codec-state enumeration; with
        stateless codecs resolved there the slots never materialize."""
        mi = self.model.mi
        local = self._local_leaves()
        out = []
        if mi.cp > 1:
            out.append(("cp", "grad_seq_rep", mi.cp_axes,
                        sum(math.prod(sh) for sh, _, _ in local)))
        if mi.tp > 1:
            n_c = sum(math.prod(sh) for sh, c, _ in local if c == "C")
            out.append(("tp", "grad_rep", mi.tp_axes, n_c))
        if mi.pp > 1:
            n_s = sum(math.prod(sh) for sh, c, sp in local
                      if c != "A" and "stage" not in sp)
            out.append(("pp", "grad_stage_rep", mi.stage_axes, n_s))
        return [f for f in out if f[3] > 0]

    def codec_sites(self):
        """The carried-state-capable comm sites this trainer's step emits
        — the optimizer's flat ZeRO-1 dp/zero sync plus the per-leaf fsdp
        grad psums of node/pod meshes — with their per-rank payload
        shapes.  Mirrors :meth:`repro.train.optimizer.Adam.apply` exactly
        (site names, pinned levels, payload sizes), so the template built
        from it matches what the traced step reads."""
        from repro.kernels import ops
        from repro.kernels.ref import BLOCK
        mi = self.model.mi
        local = self._local_leaves()
        n = sum(math.prod(shape) for shape, c, _ in local if c != "A")
        hier = mi.node_axis is not None
        f32 = jnp.float32
        sites = []
        # class-A (fsdp) leaves: one dp psum per leaf on node/pod meshes
        for i, (shape, c, _) in enumerate(local):
            if c != "A":
                continue
            if hier:
                sites.append((comms.Site("dp", f"grad_fsdp{i}",
                                         level="outer"), shape, f32))
            if mi.pod_axis:
                sites.append((comms.Site("dp", f"grad_fsdp{i}_pod"),
                              shape, f32))
        # whole-grad fold psums (cp / tp class-C / pp stage-replicated):
        # flat sites on plain axes; per-LEVEL sites on node-factored
        # (AxisPair) axes, matching _stateful_hier_psum's stage slots
        for dim, name, axes, elems in self._fold_specs():
            if isinstance(axes, compat.AxisPair):
                cl = ops.padded_rows(
                    -(-elems // self._axsize(axes.inner))) * BLOCK
                sites.append((comms.Site(dim, name, "bwd", level="inner"),
                              (elems,), f32))
                sites.append((comms.Site(dim, name, "bwd", level="outer"),
                              (cl,), f32))
            else:
                sites.append((comms.Site(dim, name, "bwd"), (elems,), f32))
        # flat ZeRO-1 sync, one site chain per grad-sync bucket (a single
        # suffix-free chain when bucketing is off — the historic tags)
        bucketed = self.opt.cfg.grad_buckets > 1
        for b, (lo, hi) in enumerate(self.opt._bucket_bounds(n)):
            sfx = str(b) if bucketed else ""
            cl = self.opt._chunk_len(hi - lo)
            sites.append((comms.Site("dp", f"zero1_grad{sfx}",
                                     level="inner" if hier else None),
                          (hi - lo,), f32))
            if hier:
                sites.append((comms.Site("dp", f"zero1_grad{sfx}",
                                         level="outer"), (cl,), f32))
            if mi.pod_axis:
                sites.append((comms.Site("dp", f"zero1_grad{sfx}_pod"),
                              (cl,), f32))
            sites.append((comms.Site("zero", f"zero1_param{sfx}",
                                     level="inner" if hier else None),
                          (cl,), f32))
        return sites

    def codec_state_template(self) -> dict:
        """Per-rank (local) ShapeDtypeStructs of the codec-state pytree;
        empty for stateless policies — no pytree bloat in the step.  A
        tuned trainer adds (or widens) a UNION slot per tunable site: the
        EF residual AND the warm low-rank factor, so every ladder rung's
        state is live whichever rung the controller selects."""
        tmpl = self.plan.codec_state_template(self.codec_sites())
        if self.tune:
            tmpl = {**tmpl, **self._tune_union_template()}
        return tmpl

    def _codec_joint_spec(self):
        # every state leaf varies per rank in general (residuals track
        # each rank's own gradient shard), so dim 0 shards honestly over
        # the joint of ALL mesh axes — host round-trips are lossless
        return P(tuple(self.model.mi.all_axes))

    def codec_state_specs(self) -> dict:
        spec = self._codec_joint_spec()
        return jax.tree.map(lambda _: spec, self.codec_state_template())

    def _codec_rep(self) -> int:
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        rep = 1
        for a in self.model.mi.all_axes:
            rep *= sizes[a]
        return rep

    def codec_structs(self) -> dict:
        """GLOBAL ShapeDtypeStructs of the codec state (for ``.lower``
        tracing and checkpoint restore)."""
        rep = self._codec_rep()
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((l.shape[0] * rep,) + l.shape[1:],
                                           l.dtype),
            self.codec_state_template())

    def init_codec_state(self) -> dict:
        """Device-resident initial codec state (host-built: zeros for
        error-feedback residuals, the deterministic warm factor for plr —
        identical on every rank, stored per-rank under the joint spec).
        Derives its slots from the SAME ``plan.stateful_sites`` resolution
        as the template, so init and traced-step expectations never
        desync."""
        rep = self._codec_rep()
        sharding = NamedSharding(self.mesh, self._codec_joint_spec())
        out = {}
        for key, (c, shape, dtype) in \
                self.plan.stateful_sites(self.codec_sites()).items():
            st = c.init_state(shape, dtype)
            out[key] = jax.tree.map(
                lambda l: jax.device_put(
                    jnp.tile(l, (rep,) + (1,) * (l.ndim - 1)), sharding), st)
        if self.tune:
            from repro.kernels import lowrank
            from repro.tune import ladder
            for key, (s, elems) in self.tune_sites().items():
                _, ncols = lowrank.mat_shape(elems)
                st = {"residual": jnp.zeros((elems,), jnp.float32),
                      "q": lowrank.init_factor(
                          ncols, lowrank.rank_for(elems,
                                                  ladder.PLR_MAX_RANK))}
                out[key] = jax.tree.map(
                    lambda l: jax.device_put(
                        jnp.tile(l, (rep,) + (1,) * (l.ndim - 1)),
                        sharding), st)
        return out

    # ------------------------------------------------------------------
    # runtime-tunable sites (the self-tuning controller's swap surface)
    # ------------------------------------------------------------------
    def tune_sites(self) -> dict:
        """``{ledger_tag: (Site, per_rank_elems)}`` of the runtime-tunable
        sites: the flat ZeRO-1 dp grad-sync chain — the paper's
        aggressive-DP compression target.  Only sum collectives over
        nontrivial axes qualify (the tuned switch carries reduce-scatter
        and all-reduce rungs); the pod hop and the param gather stay on
        their plan-static codecs."""
        mi = self.model.mi
        local = self._local_leaves()
        n = sum(math.prod(shape) for shape, c, _ in local if c != "A")
        hier = mi.node_axis is not None
        bucketed = self.opt.cfg.grad_buckets > 1
        out = {}
        for b, (lo, hi) in enumerate(self.opt._bucket_bounds(n)):
            sfx = str(b) if bucketed else ""
            if self._axsize(mi.data_axis) > 1:
                s = comms.Site("dp", f"zero1_grad{sfx}",
                               level="inner" if hier else None)
                out[s.ledger_tag] = (s, hi - lo)
            if hier:
                s = comms.Site("dp", f"zero1_grad{sfx}", level="outer")
                out[s.ledger_tag] = (s, self.opt._chunk_len(hi - lo))
        return out

    def _tune_union_template(self) -> dict:
        from repro.kernels import lowrank
        from repro.tune import ladder
        out = {}
        for key, (s, elems) in self.tune_sites().items():
            _, ncols = lowrank.mat_shape(elems)
            r = lowrank.rank_for(elems, ladder.PLR_MAX_RANK)
            out[key] = {
                "residual": jax.ShapeDtypeStruct((elems,), jnp.float32),
                "q": jax.ShapeDtypeStruct((ncols, r), jnp.float32)}
        return out

    def tune_state_specs(self) -> dict:
        """tune_state is replicated: rung selections are host-fed ints
        (identical on every rank by construction — all devices must take
        the same switch branch) and the signal accumulators come out of a
        full-mesh psum."""
        spec = {key: P() for key in self.tune_sites()}
        return {"select": dict(spec), "sig": dict(spec)}

    def tune_structs(self) -> dict:
        """ShapeDtypeStructs matching :meth:`init_tune_state` (replicated,
        so global shape == per-rank shape) — the checkpoint-restore
        template for the ``<ckpt>/tune/`` subdir."""
        from repro.tune import tracker
        keys = list(self.tune_sites())
        return {
            "select": {k: jax.ShapeDtypeStruct((), jnp.int32)
                       for k in keys},
            "sig": {k: jax.ShapeDtypeStruct((tracker.SIG_LEN,), jnp.float32)
                    for k in keys}}

    def init_tune_state(self) -> dict:
        """Device-resident ``{"select", "sig"}`` — rung indices seeded
        from the compiled plan's own resolution at each site (a tuned run
        starts exactly where its static scheme stands) and zeroed signal
        accumulators."""
        from repro.tune import ladder, tracker
        sharding = NamedSharding(self.mesh, P())
        sel, sig = {}, {}
        for key, (s, elems) in self.tune_sites().items():
            c = self.plan.codec_pair(s, elems * 4)[0].name
            sel[key] = jax.device_put(
                jnp.int32(ladder.rung_or_default(c)), sharding)
            sig[key] = jax.device_put(
                jnp.zeros((tracker.SIG_LEN,), jnp.float32), sharding)
        return {"select": sel, "sig": sig}

    # ------------------------------------------------------------------
    def _build(self):
        model, opt = self.model, self.opt
        pspecs = model.specs()
        bspecs = batch_specs(model.cfg, model.mi)
        ospecs = self.opt_state_specs()
        cspecs = self.codec_state_specs()

        loss_fn = self._loss_fn()

        def step_fn(params, opt_state, codec_state, batch):
            with policy_lib.use_plan(self.plan), comms.vma_mode(False), \
                    comms.ring_options(self.ring_bidir, self.ring_chunks):
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
                # the optimizer's sync sites read/write their codec-state
                # slots through this io region; everything the model emits
                # under autodiff stays stateless (guarded in comms)
                with comms.codec_state_io(codec_state) as cio:
                    params, opt_state, stats = opt.apply(params, grads,
                                                         opt_state)
                codec_state = cio.collect()
            return params, opt_state, codec_state, \
                {"loss": loss, **metrics, **stats}

        def opt_init_fn(params):
            with comms.vma_mode(False):
                return opt.init(params)

        self.opt_init = jax.jit(jax.shard_map(
            opt_init_fn, mesh=self.mesh, in_specs=(pspecs,),
            out_specs=ospecs, check_vma=False))
        self.step = jax.jit(
            jax.shard_map(step_fn, mesh=self.mesh,
                          in_specs=(pspecs, ospecs, cspecs, bspecs),
                          out_specs=(pspecs, ospecs, cspecs,
                                     METRIC_SPECS),
                          check_vma=False),
            donate_argnums=(0, 1, 2))

        if self.tune:
            tspecs = self.tune_state_specs()
            mi_axes = tuple(model.mi.all_axes)

            def step_tuned_fn(params, opt_state, codec_state, tune_state,
                              batch):
                with policy_lib.use_plan(self.plan), comms.vma_mode(False), \
                        comms.ring_options(self.ring_bidir,
                                           self.ring_chunks):
                    (loss, metrics), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(params, batch)
                    with comms.codec_state_io(codec_state) as cio:
                        with comms.tune_io(tune_state["select"],
                                           tune_state["sig"],
                                           axes=mi_axes) as tio:
                            params, opt_state, stats = opt.apply(
                                params, grads, opt_state)
                            sig = tio.collect()
                    codec_state = cio.collect()
                tune_state = {"select": tune_state["select"], "sig": sig}
                return params, opt_state, codec_state, tune_state, \
                    {"loss": loss, **metrics, **stats}

            # tune_state is NOT donated: the host re-feeds the same select
            # scalars every step and drains sig on the controller cadence
            self.step_tuned = jax.jit(
                jax.shard_map(step_tuned_fn, mesh=self.mesh,
                              in_specs=(pspecs, ospecs, cspecs, tspecs,
                                        bspecs),
                              out_specs=(pspecs, ospecs, cspecs, tspecs,
                                         METRIC_SPECS),
                              check_vma=False),
                donate_argnums=(0, 1, 2))

    def init_all(self, key):
        """Initialize params + optimizer state + codec state (device-
        resident, sharded).  Returns ``(params, opt_state, codec_state)``;
        the codec state is ``{}`` under stateless policies."""
        params = self.model.init(key)
        return params, self.opt_init(params), self.init_codec_state()


def make_trainer(model: Model, mesh, scheme="baseline",
                 opt_cfg: AdamConfig | None = None, n_micro: int = 1,
                 ring_bidir: bool = False, ring_chunks: int = 1,
                 remat_policy: str | None = None, tune: bool = False):
    """Trainer factory: the flat single-program step on an unfactored
    batch, or the microbatched 1F1B pipeline trainer when the mesh has a
    stage axis, gradient accumulation (``n_micro > 1``), or an activation
    ``remat_policy`` is requested.  A model built with ``vpp > 1`` runs
    the interleaved virtual-stage schedule automatically.  ``tune``
    additionally builds ``step_tuned`` — the 5-arg step whose dp sync
    sites dispatch on the runtime rung indices in ``tune_state``."""
    if model.mi.pp > 1 or n_micro > 1 or remat_policy not in (None, "none"):
        from repro.train.pipeline import PipelineTrainer
        return PipelineTrainer(model, mesh, scheme=scheme, opt_cfg=opt_cfg,
                               n_micro=n_micro, ring_bidir=ring_bidir,
                               ring_chunks=ring_chunks,
                               remat_policy=remat_policy, tune=tune)
    return Trainer(model, mesh, scheme=scheme, opt_cfg=opt_cfg,
                   ring_bidir=ring_bidir, ring_chunks=ring_chunks,
                   tune=tune)
