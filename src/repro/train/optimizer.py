"""Adam with ZeRO-1 sharded optimizer states and compressed gradient sync.

Gradient classes, routed by each leaf's sharding spec (Pv metadata):

  A. fsdp ("data" in spec, ZeRO-3 leaves): the all-gather VJP already
     reduce-scattered these over data (ZeRO codec) — update the local shard
     directly; optimizer state lives at the same sharding.
  B. model-sharded (TP/EP/vocab): per-data-shard partial grads -> flat
     reduce-scatter over data under the *DP* codec (the paper's aggressive
     compression target), ZeRO-1 chunk update, all-gather params back under
     the *ZeRO* codec.
  C. replicated (norms, ring-mode attention weights, mamba/xlstm
     projections, routers): first psum over the model axis under the
     *tp_bwd* codec (paper §III-A: MP-backward gradients take the MP codec,
     never the DP one — no double compression, challenge C3), then join
     class B's flat DP path.

Context-parallel mesh (``cp`` axis): every leaf's grad is partial per cp
rank (each rank backpropagated only its sequence chunk), so the whole
grad set folds over the cp axes under the ``cp_bwd`` codec before the
per-class routing above.

Multi-pod: the flat chunk is additionally psum'd over the 'pod' axis with
the DP codec — the cross-pod hop is the slowest-link traffic the paper
compresses hardest.

Pipeline mesh (explicit 'stage' axis): ZeRO stays over 'data' only — each
stage rank's flat vector holds its *own* stage's layer shards, so the
chunks are per-stage-local by construction.  Stage-replicated leaves
(embedding / head / final norm) carry partial grads per stage and fold
over the stage axis under the ``pp_bwd`` codec first (the classic
first/last-stage tied-embedding grad sync, generalized).

Multi-node (hierarchical, ZeRO++-style): on a (node, data, model) mesh the
flat DP sync becomes two-level — reduce-scatter over the intra-node 'data'
sub-axis under the ``dp_inner`` (mild) codec, then all-reduce of the 1/dp
chunk over the inter-node 'node' sub-axis under the ``dp_outer``
(aggressive) codec.  The ZeRO-1 master chunks are replicated per node
(hpZ secondary partition), so the param all-gather stays entirely on fast
intra-node links under ``zero_inner``.

Optional 8-bit optimizer state (paper future-work [42]): m/v stored as
bq8 blocks, decode -> update -> re-encode each step.

Carried-state codecs: the flat ZeRO-1 sync sites below (``zero1_grad``
reduce-scatter + its hier/pod psums, ``zero1_param`` all-gather) are the
sites that support stateful codecs (``ef:*`` error feedback, ``plr*``
low-rank) — the trainer wraps this ``apply`` in
``comms.codec_state_io(codec_state)`` and each site reads/writes its slot
keyed by the site's ledger tag.  ``Trainer.codec_sites`` enumerates these
sites with their payload shapes; keep the two in lockstep when adding a
sync site here.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import comms
from repro.kernels import ops as kops
from repro.kernels.ref import BLOCK
from repro.models.params import MeshInfo, Pv

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class AdamConfig:
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    state_bits: int = 32            # 8 -> bq8-quantized m/sqrt(v) (ZeRO-1)
    warmup: int = 10
    # > 1 splits the flat ZeRO-1 DP sync into that many contiguous bucket
    # slices, each with its own reduce-scatter (+ hier/pod psum) chain, and
    # moves the grad-clip scale AFTER the sync.  The wire ops then no
    # longer depend on the global grad norm (a whole-backward barrier), so
    # the XLA latency-hiding scheduler can launch bucket k's ring hops as
    # soon as backward has produced its slice — DP sync overlaps the rest
    # of backward instead of serializing after it.  Opt-in: clipping after
    # the (lossy) encode is not bit-exact with the bucket-free path.
    grad_buckets: int = 1


def _is_pv(x):
    return isinstance(x, Pv)


def _leaf_class(spec: tuple) -> str:
    if "data" in spec:
        return "A"
    if "model" in spec:
        return "B"
    return "C"


def _split_classes(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_is_pv)
    classes = [_leaf_class(l.spec) for l in leaves]
    return leaves, treedef, classes


def _flat_concat(arrs):
    return jnp.concatenate([a.reshape(-1).astype(_F32) for a in arrs]) \
        if arrs else jnp.zeros((0,), _F32)


def _lr_at(cfg: AdamConfig, step):
    warm = jnp.minimum(step.astype(_F32) / max(cfg.warmup, 1), 1.0)
    return cfg.lr * warm


class Adam:
    """Functional optimizer; init/apply run INSIDE shard_map."""

    def __init__(self, cfg: AdamConfig, mi: MeshInfo):
        if cfg.state_bits == 8 and not cfg.b1 ** 2 < cfg.b2:
            raise ValueError("8-bit optimizer state needs b1**2 < b2 "
                             f"(got b1={cfg.b1}, b2={cfg.b2})")
        self.cfg = cfg
        self.mi = mi

    # ------------------------------------------------------------------
    def init(self, params):
        leaves, _, classes = _split_classes(params)
        mi = self.mi
        fsdp_state = [
            {"master": l.v.astype(_F32), "m": jnp.zeros_like(l.v, _F32),
             "v": jnp.zeros_like(l.v, _F32)}
            if c == "A" else None
            for l, c in zip(leaves, classes)]
        flat = _flat_concat([l.v for l, c in zip(leaves, classes)
                             if c != "A"])
        n = flat.shape[0]
        # master chunk holds this data-shard's slice of the flat params —
        # per grad-sync bucket, so the layout matches what apply's bucketed
        # reduce-scatters produce (concat of per-bucket 1/dp chunks)
        idx = lax.axis_index(mi.data_axis)
        segs = []
        for lo, hi in self._bucket_bounds(n):
            cl = self._chunk_len(hi - lo)
            pad = jnp.pad(flat[lo:hi], (0, cl * mi.dp - (hi - lo)))
            segs.append(lax.dynamic_slice_in_dim(pad, idx * cl, cl, 0))
        master = jnp.concatenate(segs)
        chunk_len = master.shape[0]
        zc = jnp.zeros((chunk_len,), _F32)
        if self.cfg.state_bits == 8:
            m = kops.bq_encode_blocks(zc.reshape(-1, BLOCK), 8)
            v = kops.bq_encode_blocks(zc.reshape(-1, BLOCK), 8)
        else:
            m, v = zc, zc
        return {"fsdp": fsdp_state, "master": master, "m": m, "v": v,
                "step": jnp.zeros((), jnp.int32)}

    def _chunk_len(self, n: int) -> int:
        """Length of this shard's ZeRO-1 flat chunk (matches
        comms.reduce_scatter_flat's padding)."""
        per = -(-n // self.mi.dp)
        return kops.padded_rows(per) * BLOCK

    def _bucket_bounds(self, n: int) -> list:
        """Contiguous (lo, hi) slices of the flat B/C vector, one per
        grad-sync bucket (a single whole-vector bucket by default)."""
        k = max(1, min(self.cfg.grad_buckets, n or 1))
        base, rem = divmod(n, k)
        bounds, at = [], 0
        for i in range(k):
            ln = base + (1 if i < rem else 0)
            bounds.append((at, at + ln))
            at += ln
        return bounds

    @staticmethod
    def flat_size(params) -> int:
        leaves, _, classes = _split_classes(params)
        return sum(l.v.size for l, c in zip(leaves, classes) if c != "A")

    # ------------------------------------------------------------------
    def _adam_update(self, g, m, v, master, step):
        c = self.cfg
        m = c.b1 * m + (1 - c.b1) * g
        v = c.b2 * v + (1 - c.b2) * g * g
        t = step.astype(_F32) + 1.0
        mh = m / (1 - c.b1 ** t)
        vh = v / (1 - c.b2 ** t)
        upd = mh / (jnp.sqrt(vh) + c.eps)
        if c.weight_decay:
            upd = upd + c.weight_decay * master
        return master - _lr_at(c, step) * upd, m, v

    def _state_decode(self, s):
        if self.cfg.state_bits == 8:
            return kops.bq_decode_blocks(s, 8).reshape(-1)
        return s

    def _state_encode(self, x):
        if self.cfg.state_bits == 8:
            return kops.bq_encode_blocks(x.reshape(-1, BLOCK), 8)
        return x

    # At 8 bits v is kept as sqrt(v), which has m's dynamic range: bq8 of v
    # itself rounds every entry below 1/254 of its block's largest to 0.
    # Entries of sqrt(v) below that bound still round to 0, so the decode
    # floors sqrt(v) at the least an exact Adam state with this m can hold:
    # by Cauchy-Schwarz on the two moving averages,
    #   |m| <= (1 - b1) / sqrt((1 - b2) (1 - b1**2 / b2)) * sqrt(v),
    # which keeps the lane's update bounded.  32-bit state is v as is.
    @property
    def v_layout(self) -> str:
        """What the ZeRO-1 ``v`` state holds; saved with checkpoints."""
        return "sqrt_v" if self.cfg.state_bits == 8 else "v"

    @property
    def v_floor(self) -> float:
        """Least sqrt(v) / |m| of an exact Adam state."""
        c = self.cfg
        return math.sqrt((1 - c.b2) * (1 - c.b1 ** 2 / c.b2)) / (1 - c.b1)

    def _v_decode(self, s, m):
        if self.cfg.state_bits != 8:
            return s
        r = self._state_decode(s)
        return jnp.square(jnp.maximum(r, self.v_floor * jnp.abs(m)))

    def _v_encode(self, v):
        if self.cfg.state_bits != 8:
            return v
        return self._state_encode(jnp.sqrt(v))

    # ------------------------------------------------------------------
    def apply(self, params, grads, state):
        """Returns (new_params, new_state, stats).  Inside shard_map."""
        mi, cfg = self.mi, self.cfg
        leaves, treedef, classes = _split_classes(params)
        gleaves, _, _ = _split_classes(grads)
        step = state["step"]

        # -- cp (context-parallel) fold: EVERY leaf's grad is partial per
        # cp rank (each rank backpropagated only its zigzag sequence
        # chunk; params are replicated over cp), so fold the whole grad
        # set over the cp axes under the cp backward codec before any
        # per-class routing.  On a cp-node-factored mesh this rides the
        # hierarchical two-level all-reduce (cp_bwd_inner / cp_bwd_outer).
        if mi.cp > 1:
            aflat = _flat_concat([g.v for g in gleaves])
            aflat = comms.psum(aflat, mi.cp_axes,
                               comms.Site("cp", "grad_seq_rep", "bwd"))
            out, off = [], 0
            for g in gleaves:
                n = g.v.size
                out.append(Pv(aflat[off:off + n].reshape(g.v.shape), g.spec))
                off += n
            gleaves = out

        # -- class C: fold model-axis partial grads (MP codec, paper C3).
        # On a tp-node-factored mesh this rides the hierarchical two-level
        # all-reduce (tp_bwd_inner / tp_bwd_outer codecs).
        c_vals = [g.v for g, c in zip(gleaves, classes) if c == "C"]
        if c_vals and mi.tp > 1:
            cflat = _flat_concat(c_vals)
            cflat = comms.psum(cflat, mi.tp_axes,
                               comms.Site("tp", "grad_rep", "bwd"))
            out, off = [], 0
            for g, c in zip(gleaves, classes):
                if c == "C":
                    n = g.v.size
                    out.append(cflat[off:off + n].reshape(g.v.shape))
                    off += n
            it = iter(out)
            gleaves = [Pv(next(it), g.spec) if c == "C" else g
                       for g, c in zip(gleaves, classes)]

        # -- stage-replicated leaves on a pipeline mesh (embedding / head /
        # final norm — "stage" not in spec): each stage rank holds a
        # *partial* grad (the embedding is consumed on the first stage, the
        # head on the last), folded over the stage axis under the PP
        # backward codec (pp_bwd_inner / pp_bwd_outer when the stage axis
        # is node-factored) before joining the DP sync.  Stage-sharded
        # leaves (each rank's own layers) need no fold.
        if mi.pp > 1:
            srep = [(i, g) for i, (g, c) in enumerate(zip(gleaves, classes))
                    if c != "A" and "stage" not in g.spec]
            if srep:
                sflat = _flat_concat([g.v for _, g in srep])
                sflat = comms.psum(sflat, mi.stage_axes,
                                   comms.Site("pp", "grad_stage_rep",
                                              "bwd"))
                off = 0
                for i, g in srep:
                    n = g.v.size
                    gleaves[i] = Pv(sflat[off:off + n].reshape(g.v.shape),
                                    g.spec)
                    off += n

        # -- global grad-norm clip.  Each class's squared sum is divided by
        # its replication factor so the psum over all axes counts every
        # parameter exactly once.  (Cross-pod partials are approximated by
        # the sum-of-squares of per-pod partial grads; exact to within the
        # usual sqrt(pods) factor and deterministic.)
        pod = mi.pod if mi.pod_axis else 1
        node = mi.node if mi.node_axis else 1
        # after the cp fold every leaf is additionally replicated over cp
        cpr = mi.cp if mi.cp_axis else 1
        rep = {"A": pod * node * cpr,
               "B": mi.dp * pod * node * cpr,
               "C": mi.dp * mi.tp * pod * node * cpr}
        sq = jnp.float32(0.0)
        for g, c in zip(gleaves, classes):
            # stage-sharded leaves are distinct per stage rank (counted
            # once by the psum over all axes); stage-replicated leaves were
            # just folded over the stage axis, so divide their square out
            r = rep[c] * (mi.pp if mi.pp > 1 and "stage" not in g.spec else 1)
            sq = sq + jnp.sum(g.v.astype(_F32) ** 2) / r
        sq = comms.varying_all(sq, mi.all_axes)
        sq = lax.psum(sq, mi.all_axes)
        gnorm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12))

        # -- class A (fsdp): local update
        new_fsdp, new_leaves = [], [None] * len(leaves)
        for i, (l, g, c) in enumerate(zip(leaves, gleaves, classes)):
            if c != "A":
                new_fsdp.append(None)
                continue
            gv = g.v.astype(_F32)
            if "model" not in g.spec:
                gv = comms.psum(gv, mi.tp_axes,
                                comms.Site("tp", "grad_fsdp", "bwd"))
            # (no stage fold here: fsdp only annotates layer-group plans,
            # which are always stage-stacked on a pipeline mesh)
            # per-leaf site names: each class-A leaf is its own payload,
            # so each gets its own codec-state slot under stateful dp
            # codecs (Trainer.codec_sites enumerates the same indices)
            if mi.node_axis:
                gv = comms.psum(gv, mi.node_axis,
                                comms.Site("dp", f"grad_fsdp{i}",
                                           level="outer"))
            if mi.pod_axis:
                gv = comms.psum(gv, mi.pod_axis,
                                comms.Site("dp", f"grad_fsdp{i}_pod"))
            st = state["fsdp"][i]
            master, m, v = self._adam_update(gv * scale, st["m"], st["v"],
                                             st["master"], step)
            new_fsdp.append({"master": master, "m": m, "v": v})
            new_leaves[i] = Pv(master.astype(l.v.dtype), l.spec)

        # -- classes B + C: flat compressed DP reduce-scatter (ZeRO-1).
        # Bucketed mode (grad_buckets > 1) defers the clip scale until
        # after the sync: the reduce-scatters then consume raw backward
        # outputs (no data dependency on the global grad norm), so each
        # bucket's ring hops dispatch as soon as its slice of backward is
        # done — the async overlap the fused ring path is built for.
        bucketed = cfg.grad_buckets > 1
        bc = [g.v if bucketed else g.v * jnp.asarray(scale, g.v.dtype)
              for g, c in zip(gleaves, classes) if c != "A"]
        gflat = _flat_concat(bc)
        # two-level DP sync on a (node, data) factored mesh: intra-node RS
        # (mild codec) -> inter-node AR of the 1/dp chunk (aggressive codec);
        # the dp_inner/dp_outer tags fall back to the flat dp codec under
        # non-level-aware schemes.
        hier = mi.node_axis is not None
        chunks = []
        for b, (lo, hi) in enumerate(self._bucket_bounds(gflat.shape[0])):
            sfx = str(b) if bucketed else ""
            gc = comms.reduce_scatter_flat(
                gflat[lo:hi], mi.data_axis,
                comms.Site("dp", f"zero1_grad{sfx}",
                           level="inner" if hier else None))
            if hier:
                gc = comms.psum(gc, mi.node_axis,
                                comms.Site("dp", f"zero1_grad{sfx}",
                                           level="outer"))
            if mi.pod_axis:
                gc = comms.psum(gc, mi.pod_axis,
                                comms.Site("dp", f"zero1_grad{sfx}_pod"))
            chunks.append(gc)
        gchunk = chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks)
        if bucketed:
            gchunk = gchunk * scale     # post-sync clip (see above)
        m = self._state_decode(state["m"])
        v = self._v_decode(state["v"], m)
        master, m, v = self._adam_update(gchunk, m, v, state["master"], step)
        # hpZ: master chunks are replicated per node, so this all-gather
        # rides only fast intra-node links
        if not bucketed:
            flat_new = comms.all_gather_flat(
                master, mi.data_axis, self.flat_size(params),
                comms.Site("zero", "zero1_param",
                           level="inner" if hier else None))
        else:
            segs, at = [], 0
            for b, (lo, hi) in enumerate(
                    self._bucket_bounds(gflat.shape[0])):
                cl = self._chunk_len(hi - lo)
                segs.append(comms.all_gather_flat(
                    master[at:at + cl], mi.data_axis, hi - lo,
                    comms.Site("zero", f"zero1_param{b}",
                               level="inner" if hier else None)))
                at += cl
            flat_new = jnp.concatenate(segs)
        off = 0
        for i, (l, c) in enumerate(zip(leaves, classes)):
            if c == "A":
                continue
            n = l.v.size
            new_leaves[i] = Pv(
                flat_new[off:off + n].reshape(l.v.shape).astype(l.v.dtype),
                l.spec)
            off += n

        new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        new_state = {"fsdp": new_fsdp, "master": master,
                     "m": self._state_encode(m), "v": self._v_encode(v),
                     "step": step + 1}
        return new_params, new_state, {"grad_norm": gnorm,
                                       "lr": _lr_at(cfg, step)}
