"""Microbatched pipeline-parallel training over the ``stage`` mesh axis.

The schedule is the SPMD form of GPipe/1F1B: one program runs on every
stage rank; the local batch splits into ``n_micro`` microbatches and the
step executes ``T = n_micro + pp - 1`` *ticks*.  At tick ``t`` stage ``s``
processes microbatch ``t - s`` (masked outside the fill/drain window):

    tick          0     1     2     3       (pp = 2, n_micro = 3)
    stage 0     mb0   mb1   mb2    --
    stage 1      --   mb0   mb1   mb2      -> loss(mb) as each drains

* the **first** stage injects the embedded microbatch entering the pipe;
* every other stage consumes the activation handed off by its
  predecessor via :func:`repro.core.comms.stage_send` — a partial shift
  along the stage axis that encodes under the scheme's ``pp_fwd`` codec
  (``pp_fwd_inner`` / ``pp_fwd_outer`` when the stage axis is
  node-factored) and whose ``custom_vjp`` backward returns the activation
  gradient upstream under ``pp_bwd`` — PP traffic finally rides the
  compression path and the per-dimension ledger;
* the **last** stage drains: final norm + LM head + vocab-parallel
  cross-entropy per microbatch, accumulated into the global token mean.

**Interleaved virtual stages** (``vpp = V > 1``) cut the fill/drain
bubble ~``1/V`` at fixed ``pp``: each rank holds ``V`` *round-robin*
depth slices (chunk ``c = v * pp + s`` lives on rank ``s`` as slice
``v``), so a tick runs ``1/V`` of a rank's depth and the step stretches
to ``T = n_micro * V + pp - 1`` shorter ticks — same ``pp - 1`` fill
ticks over more of them:

    tick          0       1       2       3       4    (pp=2, M=2, V=2)
    stage 0    mb0.v0  mb1.v0  mb0.v1  mb1.v1    --
    stage 1      --    mb0.v0  mb1.v0  mb0.v1  mb1.v1  -> drain at v1

Rank ``s`` at tick ``t`` decodes its work from ``u = t - s``: microbatch
group ``g = u // (pp*V)``, slot ``r = u % pp``, virtual slice
``v = (u % (pp*V)) // pp``, microbatch ``m = g * pp + r`` (microbatches
advance in groups of ``pp``, hence ``n_micro % pp == 0``).  The handoff
becomes a full ring (:func:`repro.core.comms.stage_ring_send`): the chunk
after the last rank's slice ``v`` is the first rank's slice ``v + 1``, so
the activation wraps ``pp-1 -> 0`` — handoff count per microbatch is
``x V``, every hop still under the ``pp_fwd`` / ``pp_bwd`` codecs, and
each ledger event carries a ``vpp`` fact for the roofline.

**Activation memory policy** (``--remat-policy``): autodiff through the
tick scan stashes residuals for all ``T`` ticks; ``full`` wraps each
(virtual-)stage body in ``jax.checkpoint`` so only the tick carry
survives, ``per_stage:<v,...>`` checkpoints the tick slots where stage 0
runs the named slices — the choice is keyed on the tick, not the
device-varying slice index, so every rank takes the same ``lax.cond``
branch (the body's TP/EP collectives sit inside the branches; a
device-varying predicate deadlocks SPMD ranks on mismatched rendezvous)
and each rank checkpoints ``|set|/V`` of its live ticks, the named
slices rotated by its fill offset.  Note jax conds carry the union of
branch residuals, so mixed policies bound recompute, not peak stash.
A ``+offload``
suffix additionally parks matmul residuals in pinned host memory where
the runtime supports it.  The handoff collective stays OUTSIDE the
checkpoint so remat never re-runs pp traffic.

Autodiff through the tick scan yields the interleaved backward schedule
(gradient accumulation across microbatches comes out of the scan-reverse
for free); the optimizer then syncs gradients over ``data`` exactly as in
the flat trainer — per-stage param subsets keep ZeRO-1 chunks local to
each stage rank, while the stage-*replicated* embedding / head / final
norm fold their partial grads over the stage axis (``pp_bwd`` codec)
inside :meth:`repro.train.optimizer.Adam.apply`.

With identity codecs the pipelined step is bit-exact against the same
microbatched loop on a stage-free mesh (``tests/multidev/pp_check.py``),
and ``vpp=1`` is bit-exact against the plain schedule
(``tests/multidev/vpp_check.py``); with a ``hier_tpp_*`` scheme the
stage handoffs crossing a node boundary ride the aggressive outer codec.
``pp == 1`` degenerates to plain gradient accumulation — microbatching
without pipelining.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax import lax

from repro.analysis.roofline import pipeline_ticks
from repro.core import compat
from repro.models import layers, transformer
from repro.models.model import _LB_COEF, Model
from repro.train.train_step import Trainer

_F32 = jnp.float32


def parse_remat_policy(spec, vpp: int):
    """``--remat-policy`` spec -> ``(mode, flags, offload)``.

    ``mode`` is one of ``none`` / ``full`` / ``per_stage`` (uniform specs
    canonicalize: ``per_stage:`` naming every slice is ``full``, naming
    none is ``none``); ``flags`` is a length-``vpp`` tuple of
    checkpoint-this-virtual-slice booleans; ``offload`` marks the
    ``+offload`` suffix."""
    if spec is None or spec == "none":
        return "none", (False,) * vpp, False
    offload = False
    if spec.endswith("+offload"):
        offload, spec = True, spec[: -len("+offload")]
    if spec == "none":
        raise ValueError("--remat-policy none+offload: offload stashes "
                         "checkpoint residuals — it needs remat enabled")
    if spec == "full":
        return "full", (True,) * vpp, offload
    if spec.startswith("per_stage:"):
        body = spec[len("per_stage:"):]
        try:
            idx = sorted({int(tok) for tok in body.split(",") if tok != ""})
        except ValueError:
            raise ValueError(
                f"bad --remat-policy spec {spec!r}: per_stage wants a "
                "comma list of virtual-stage indices, e.g. per_stage:0,2"
            ) from None
        bad = [i for i in idx if not 0 <= i < vpp]
        if bad:
            raise ValueError(f"--remat-policy {spec!r}: virtual stage(s) "
                             f"{bad} out of range for vpp={vpp}")
        flags = tuple(i in idx for i in range(vpp))
        if all(flags):
            return "full", flags, offload
        if not any(flags):
            return "none", flags, False
        return "per_stage", flags, offload
    raise ValueError(f"unknown --remat-policy {spec!r} (expected none | "
                     "full | per_stage:<v,v,...>, optionally +offload)")


def _remat_wrap(fn, offload: bool):
    """``jax.checkpoint`` around a (virtual-)stage body.  ``offload``
    additionally parks matmul residuals in pinned host memory."""
    if offload:
        pol = jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host")
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def _stage_body(model: Model, params, x, pos, cross=None, cross_pos=None,
                pos3=None):
    """One stage's layer stack: ``run_stage`` on a stage mesh, the full
    decoder on a flat one (so pp=1 runs the identical per-layer ops —
    including shared_attn / cross-attention / M-RoPE, which only the flat
    path allows)."""
    if model.mi.pp > 1:
        return model.run_stage(params, x, pos)
    x, _, aux = model.run_decoder(params, x, pos, "train", cross=cross,
                                  cross_pos=cross_pos, pos3=pos3)
    return x, aux


def pipeline_loss_fn(model: Model, n_micro: int, remat_policy=None):
    """Build the microbatched 1F1B loss callable (runs inside shard_map).

    Same ``(params, batch) -> (loss, metrics)`` contract as
    ``Model.loss_fn``: global-mean token cross-entropy (+ MoE aux),
    scalar, replicated over every mesh axis.  ``model.vpp > 1`` selects
    the interleaved virtual-stage schedule; ``remat_policy`` is a
    :func:`parse_remat_policy` spec string bounding the tick-scan
    activation stash."""
    cfg, mi = model.cfg, model.mi
    assert mi.pp == 1 or (not cfg.encoder_layers and not cfg.mrope), \
        "encoder / vision inputs are not pipelineable (cross-stage " \
        "context) — pp=1 gradient accumulation supports them"
    pp, M = mi.pp, n_micro
    V = getattr(model, "vpp", 1)
    if V > 1:
        assert pp > 1, "vpp > 1 (interleaved virtual stages) needs pp > 1"
        assert M % pp == 0, (
            f"interleaved 1F1B needs n_micro divisible by pp (n_micro={M}, "
            f"pp={pp}) — the round-robin decode walks microbatches in "
            "groups of pp")
    rmode, rflags, roffload = parse_remat_policy(remat_policy, V)
    stage_ax = mi.stage_axes
    T = pipeline_ticks(pp, M, V)

    def loss_fn(params, batch):
        from repro.core import comms
        B, S = batch["tokens"].shape
        assert B % M == 0, f"local batch {B} not divisible by {M} microbatches"
        mb = {k: v.reshape((M, B // M) + v.shape[1:])
              for k, v in batch.items()}
        sidx = compat.axis_index(stage_ax) if pp > 1 else 0
        # S is already cp-local (batch_specs shards seq over the cp axes);
        # _positions maps the tp sub-slice to global zigzag positions
        pos = model._positions(B // M, S // mi.tp if mi.tp > 1 else S)

        def tick_plain(carry, t):
            y, num, den, aux = carry
            # 1. handoff: my previous tick's output moves one stage down
            #    the pipe (pp_fwd codec; bwd returns the grad under pp_bwd)
            recv = comms.stage_send(y, stage_ax,
                                    comms.site("pp", "stage_handoff")) \
                if pp > 1 else None
            # 2. stage-0 input: the microbatch entering the pipe this tick
            #    (clamped during drain — those outputs never reach the
            #    last stage within T ticks, so their grads are zero)
            bt = {k: lax.dynamic_index_in_dim(v, jnp.clip(t, 0, M - 1), 0,
                                              keepdims=False)
                  for k, v in mb.items()}
            cross = cross_pos = None
            if cfg.encoder_layers:  # pp == 1 only (asserted above)
                cross, cross_pos = model._encode(params, bt["frames"],
                                                 "train")
            e = model._embed_input(params, bt)
            x_in = jnp.where(sidx == 0, e, recv) if pp > 1 else e
            # 3. this stage's layers (optionally under jax.checkpoint —
            #    the handoff above stays outside, remat never re-sends)
            pos3 = bt.get("pos3") if cfg.mrope else None

            def run(p, x):
                return _stage_body(model, p, x, pos, cross=cross,
                                   cross_pos=cross_pos, pos3=pos3)

            body = _remat_wrap(run, roffload) if rflags[0] else run
            y, aux_t = body(params, x_in)
            # 4. drain: head + per-token xent for the microbatch leaving
            #    the pipe; only the last stage past the fill window counts
            xo = layers.norm(params["final_norm"], y, cfg, mi)
            logits = layers.lm_head_logits(params, xo, cfg, mi)
            lab = lax.dynamic_index_in_dim(
                mb["labels"], jnp.clip(t - (pp - 1), 0, M - 1), 0,
                keepdims=False)
            ltok, w = layers.vocab_parallel_xent(logits, lab, cfg, mi)
            valid = (t >= pp - 1) & (sidx == pp - 1)
            num = num + jnp.where(valid, jnp.sum(ltok), 0.0)
            den = den + jnp.where(valid, jnp.sum(w), 0.0)
            # 5. aux terms count the ticks this stage held a real microbatch
            live = (t >= sidx) & (t < sidx + M)
            aux = jax.tree.map(
                lambda a, b: a + jnp.where(live, b, 0.0), aux, aux_t)
            return comms.varying_all((y, num, den, aux), mi.all_axes), None

        def tick_interleaved(carry, t):
            y, num, den, aux = carry
            # 1. handoff: a full ring — the chunk after the last rank's
            #    slice v is the FIRST rank's slice v+1, so the activation
            #    wraps pp-1 -> 0 (pp_fwd codec, grads back under pp_bwd)
            recv = comms.stage_ring_send(y, stage_ax,
                                         comms.site("pp", "stage_handoff"))
            # 2. round-robin decode: who am I this tick?  u = t - sidx;
            #    microbatches advance in groups of pp, each group runs its
            #    pp*V chunks in chunk order offset by the rank's slot
            u = t - sidx
            live = (u >= 0) & (u < M * V)
            uc = jnp.clip(u, 0, M * V - 1)
            g = uc // (pp * V)
            r = uc % pp
            vslice = (uc % (pp * V)) // pp
            m = g * pp + r
            bt = {k: lax.dynamic_index_in_dim(v, m, 0, keepdims=False)
                  for k, v in mb.items()}
            e = model._embed_input(params, bt)
            # only chunk 0 (stage 0's slice 0) takes the embedded input;
            # every other chunk consumes the ring handoff
            x_in = jnp.where((sidx == 0) & (vslice == 0), e, recv)

            # 3. the live virtual slice's layers, under the remat policy
            #    (handoff stays outside the checkpoint)
            def run(p, x, v):
                return model.run_stage(p, x, pos, v=v)

            if rmode == "none":
                y, aux_t = run(params, x_in, vslice)
            elif rmode == "full":
                y, aux_t = _remat_wrap(run, roffload)(params, x_in, vslice)
            else:  # per_stage: cond traces BOTH branches — mute the
                # checkpointed twin so the ledger counts each op once
                ckpt = _remat_wrap(run, roffload)

                def muted(p, x, v):
                    with comms.mute_ledger():
                        return ckpt(p, x, v)

                # the predicate MUST be uniform across devices: the body's
                # TP/EP collectives sit inside both branches, and ranks
                # taking different branches rendezvous on different ops
                # (deadlock under compressed schemes).  Keying on the tick
                # alone — stage 0's slice this tick — keeps every rank on
                # the same branch; each rank still checkpoints |set|/V of
                # its live ticks, the named slices rotated by its fill
                # offset.
                vtick = (jnp.clip(t, 0, M * V - 1) % (pp * V)) // pp
                y, aux_t = lax.cond(jnp.asarray(rflags)[vtick], muted, run,
                                    params, x_in, vslice)
            # 4. drain: the last rank's LAST slice hands to the head —
            #    bt already holds this tick's decoded microbatch m
            xo = layers.norm(params["final_norm"], y, cfg, mi)
            logits = layers.lm_head_logits(params, xo, cfg, mi)
            ltok, w = layers.vocab_parallel_xent(logits, bt["labels"], cfg,
                                                 mi)
            valid = live & (vslice == V - 1) & (sidx == pp - 1)
            num = num + jnp.where(valid, jnp.sum(ltok), 0.0)
            den = den + jnp.where(valid, jnp.sum(w), 0.0)
            # 5. aux: every live tick ran 1/V of this rank's layers, so
            #    summing live ticks matches the plain schedule's scale
            aux = jax.tree.map(
                lambda a, b: a + jnp.where(live, b, 0.0), aux, aux_t)
            return comms.varying_all((y, num, den, aux), mi.all_axes), None

        tick = tick_interleaved if V > 1 else tick_plain
        x0 = jnp.zeros((B // M, S // mi.tp if mi.tp > 1 else S, cfg.d_model),
                       jnp.dtype(cfg.dtype))
        carry0 = (x0, _F32(0.0), _F32(0.0), transformer._zero_aux())
        carry0 = comms.varying_all(carry0, mi.all_axes)
        # ledger: the tick body is traced once, runs T times; pipeline
        # events carry the schedule's vpp fact for the roofline
        facts = comms.scope_facts(vpp=V) if pp > 1 \
            else contextlib.nullcontext()
        with comms.scope_mult(T), facts:
            (_, num, den, aux), _ = lax.scan(tick, carry0, jnp.arange(T))

        # fold the masked per-stage partials: last stage holds num/den,
        # each stage its own layers' aux (tiny scalars — plain psum)
        if pp > 1:
            num = lax.psum(num, mi.sp_axes)
            den = lax.psum(den, mi.sp_axes)
            aux = jax.tree.map(lambda a: lax.psum(a, mi.sp_axes), aux)
        # cp ranks hold disjoint zigzag sequence chunks, so their partial
        # token sums add like the batch axes
        num, den = comms.varying_all((num, den), mi.all_axes)
        num = lax.psum(num, mi.batch_axes + mi.cp_phys_axes)
        den = lax.psum(den, mi.batch_axes + mi.cp_phys_axes)
        num = lax.pmean(num, mi.mp_axes)
        den = lax.pmean(den, mi.mp_axes)
        loss = num / jnp.maximum(den, 1.0)
        if cfg.n_experts:
            # per-microbatch means sum to M x the full-batch mean
            lb = lax.pmean(aux["lb_loss"],
                           mi.mp_axes + mi.batch_axes + mi.cp_phys_axes) / M
            loss = loss + _LB_COEF * lb
        metrics = {"xent": num / jnp.maximum(den, 1.0), "tokens": den}
        return loss, metrics

    return loss_fn


class PipelineTrainer(Trainer):
    """Drop-in :class:`~repro.train.train_step.Trainer` running the
    microbatched 1F1B schedule (interleaved when the model was built with
    ``vpp > 1``); on a stage-free mesh it degenerates to plain gradient
    accumulation over ``n_micro`` microbatches."""

    def __init__(self, model: Model, mesh, scheme="baseline", opt_cfg=None,
                 n_micro: int = 1, ring_bidir: bool = False,
                 ring_chunks: int = 1, remat_policy=None,
                 tune: bool = False):
        self.n_micro = n_micro
        self.remat_policy = remat_policy
        # fail fast on a bad spec (before the jitted build)
        parse_remat_policy(remat_policy, getattr(model, "vpp", 1))
        super().__init__(model, mesh, scheme=scheme, opt_cfg=opt_cfg,
                         ring_bidir=ring_bidir, ring_chunks=ring_chunks,
                         tune=tune)

    def _check_mesh(self):
        pass  # any mesh: pp > 1 pipelines, pp == 1 just microbatches

    def _loss_fn(self):
        return pipeline_loss_fn(self.model, self.n_micro,
                                remat_policy=self.remat_policy)
