"""Attention: head-sharded TP mode and ring/SP mode, plus decode paths.

Mode selection (``cfg.attn_mode_for(tp)``):
  * ``head`` — Megatron-SP: AG(seq) over tp -> local-head attention ->
    RS(seq).  Needs q_heads % tp == 0 and kv_heads % tp == 0.
  * ``ring`` — sequence stays sharded; with tp > 1 the GQA-small KV chunk
    is all-gathered over tp once so weights can stay replicated for any
    head count; the sub-quadratic-memory path.

Context parallelism (``cp`` mesh axis) composes with BOTH modes: each cp
rank holds one zigzag (causal load-balanced) slice of the sequence, and
:func:`ring_attention` rotates KV blocks around ``mi.cp_axes`` via
compressed ppermute hops (``cp`` ledger dimension, ``cp_fwd``/``cp_bwd``
codecs, hier-aware when the ring crosses nodes) with an online-softmax
log-sum-exp merge.  Masking is position-based throughout, so the
non-contiguous zigzag shards need no special cases.

Decode:
  * ``head``  — KV cache [B, S_max, KV_loc, hd] (heads sharded), local attn.
  * ``ring``  — KV cache seq-sharded over one or two mesh axes
    (flash-decoding style): per-shard partial softmax, pmax/psum combine.

All softmax statistics are f32; GQA is grouped natively (no KV duplication).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core import comms, compat
from repro.models import layers
from repro.models.params import D as Dd, MeshInfo
from repro.models.layers import use, apply_rope, apply_mrope, rms_norm

_F32 = jnp.float32
_NEG = -1e30


# --------------------------------------------------------------------------
# plan
# --------------------------------------------------------------------------

def attn_plan(cfg, mode: str, cross: bool = False):
    hd, H, KV, Dm = cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    if mode == "head":
        q_spec, o_spec = (None, "model"), ("model", None)
    else:  # ring: weights replicated over model (seq carries the parallelism)
        q_spec, o_spec = (None, None), (None, None)
    p = {
        "wq": Dd((Dm, H * hd), spec=q_spec, dtype=cfg.dtype),
        "wk": Dd((Dm, KV * hd), spec=q_spec, dtype=cfg.dtype),
        "wv": Dd((Dm, KV * hd), spec=q_spec, dtype=cfg.dtype),
        "wo": Dd((H * hd, Dm), spec=o_spec, dtype=cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = Dd((H * hd,), spec=q_spec[1:], init="zeros", dtype=cfg.dtype)
        p["bk"] = Dd((KV * hd,), spec=q_spec[1:], init="zeros", dtype=cfg.dtype)
        p["bv"] = Dd((KV * hd,), spec=q_spec[1:], init="zeros", dtype=cfg.dtype)
    if cfg.qk_norm:
        p["qn"] = Dd((hd,), init="zeros", dtype="float32", fsdp_ok=False)
        p["kn"] = Dd((hd,), init="zeros", dtype="float32", fsdp_ok=False)
    return p


# --------------------------------------------------------------------------
# online-softmax core
# --------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, causal, window, k_valid=None):
    """Additive bias [B, 1, 1, Sq, Sk] from position predicates."""
    qp = q_pos[:, :, None]              # [B,Sq,1]
    kp = k_pos[:, None, :]              # [B,1,Sk]
    ok = jnp.ones(qp.shape[:1] + (qp.shape[1], kp.shape[2]), bool)
    if causal:
        ok &= kp <= qp
    if window:
        ok &= kp > qp - window
    if k_valid is not None:
        ok &= k_valid[:, None, :]
    return jnp.where(ok, 0.0, _NEG)[:, None, None, :, :].astype(_F32)


def _attn_part(q, k, v, bias, scale):
    """One KV block of attention, unnormalized.

    q [B,Sq,H,hd], k/v [B,Sk,KV,hd], bias [B,1,1,Sq,Sk]
    -> (o [B,Sq,H,hd] f32, m [B,Sq,H] f32, l [B,Sq,H] f32)
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd).astype(_F32)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(_F32)) * scale
    s = s + bias                                             # [B,KV,G,Sq,Sk]
    m = jnp.max(s, axis=-1)                                  # [B,KV,G,Sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", p, v.astype(_F32))
    o = jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, hd)
    m = jnp.moveaxis(m, 3, 1).reshape(B, Sq, H)
    l = jnp.moveaxis(l, 3, 1).reshape(B, Sq, H)
    return o, m, l


def _combine(a, b):
    o1, m1, l1 = a
    o2, m2, l2 = b
    m = jnp.maximum(m1, m2)
    w1 = jnp.exp(m1 - m)
    w2 = jnp.exp(m2 - m)
    return (o1 * w1[..., None] + o2 * w2[..., None], m, l1 * w1 + l2 * w2)


def _finish(o, m, l, dtype):
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def _empty_acc(q):
    B, Sq, H, hd = q.shape
    return (jnp.zeros((B, Sq, H, hd), _F32),
            jnp.full((B, Sq, H), _NEG, _F32),
            jnp.zeros((B, Sq, H), _F32))


def full_attention(q, k, v, q_pos, k_pos, causal, window, k_valid=None,
                   kv_chunk: int = 2048):
    """Local (no-collective) attention, scanning KV in chunks for memory."""
    scale = q.shape[-1] ** -0.5
    Sk = k.shape[1]
    if Sk <= kv_chunk:
        bias = _mask_bias(q_pos, k_pos, causal, window, k_valid)
        o, m, l = _attn_part(q, k, v, bias, scale)
        return _finish(o, m, l, q.dtype)
    n = -(-Sk // kv_chunk)
    pad = n * kv_chunk - Sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pos_p = jnp.pad(k_pos, ((0, 0), (0, pad)))
    valid = jnp.ones(k_pos.shape, bool) if k_valid is None else k_valid
    valid = jnp.pad(valid, ((0, 0), (0, pad)))
    B = q.shape[0]
    ks = jnp.moveaxis(kp.reshape(B, n, kv_chunk, *k.shape[2:]), 1, 0)
    vs = jnp.moveaxis(vp.reshape(B, n, kv_chunk, *v.shape[2:]), 1, 0)
    ps = jnp.moveaxis(pos_p.reshape(B, n, kv_chunk), 1, 0)
    vls = jnp.moveaxis(valid.reshape(B, n, kv_chunk), 1, 0)

    def step(acc, blk):
        kb, vb, pb, vlb = blk
        bias = _mask_bias(q_pos, pb, causal, window, vlb)
        return _combine(acc, _attn_part(q, kb, vb, bias, scale)), None

    acc0 = comms.match_vma(_empty_acc(q), (q, k, v, q_pos, k_pos))
    (o, m, l), _ = lax.scan(step, acc0, (ks, vs, ps, vls))
    return _finish(o, m, l, q.dtype)


def ring_attention(q, k, v, q_pos, k_pos, mi: MeshInfo, causal, window,
                   k_valid=None):
    """KV blocks rotate around the context-parallel ring; compressed hops.

    q [B, Sq_loc, H, hd] attends to its local KV block first, then to the
    cp-1 blocks arriving around ``mi.cp_axes`` — the (GQA-small) KV moves,
    queries stay put, and the online-softmax log-sum-exp merge makes the
    result independent of block arrival order up to fp rounding.  The hops
    ride ``comms.ppermute`` under the ``cp`` ledger dimension (``cp_fwd``
    codec forward, inverse-permuted ``cp_bwd`` gradients via its
    custom_vjp); ``q_pos``/``k_pos`` carry GLOBAL positions, so the zigzag
    load-balanced sharding needs no mask special cases.
    """
    cp = mi.cp
    scale = q.shape[-1] ** -0.5
    if cp == 1:
        bias = _mask_bias(q_pos, k_pos, causal, window, k_valid)
        o, m, l = _attn_part(q, k, v, bias, scale)
        return _finish(o, m, l, q.dtype)
    perm = [(j, (j + 1) % cp) for j in range(cp)]
    acc = _empty_acc(q)
    kb, vb, pb = k, v, k_pos
    vlb = k_valid
    for t in range(cp):
        bias = _mask_bias(q_pos, pb, causal, window, vlb)
        acc = _combine(acc, _attn_part(q, kb, vb, bias, scale))
        if t < cp - 1:
            # ring hops over the (possibly node-factored) joint cp axis: an
            # AxisPair routes intra-node hops under cp_*_inner and the
            # node-crossing hop under cp_*_outer
            kb = comms.ppermute(kb, mi.cp_axes, perm,
                                comms.site("cp", "ring_kv"))
            vb = comms.ppermute(vb, mi.cp_axes, perm,
                                comms.site("cp", "ring_kv"))
            # positions/validity are tiny int/bool payloads: rotate uncompressed
            pb = lax.ppermute(pb, mi.cp_axes, perm)
            if vlb is not None:
                vlb = lax.ppermute(vlb, mi.cp_axes, perm)
    return _finish(*acc, q.dtype)


# --------------------------------------------------------------------------
# projections (+ rope/qk-norm), shared by the entry points
# --------------------------------------------------------------------------

def _project_qkv(p, xq, xkv, pos_q, pos_kv, cfg, mi, theta, pos3_q=None):
    hd = cfg.head_dim_
    wq, wk, wv = use(p["wq"], mi), use(p["wk"], mi), use(p["wv"], mi)
    q = jnp.einsum("bsd,dh->bsh", xq, wq)
    k = jnp.einsum("bsd,dh->bsh", xkv, wk)
    v = jnp.einsum("bsd,dh->bsh", xkv, wv)
    if cfg.qkv_bias:
        q = q + use(p["bq"], mi)
        k = k + use(p["bk"], mi)
        v = v + use(p["bv"], mi)
    q = q.reshape(*q.shape[:2], -1, hd)
    k = k.reshape(*k.shape[:2], -1, hd)
    v = v.reshape(*v.shape[:2], -1, hd)
    if cfg.qk_norm:
        q = rms_norm(q, use(p["qn"], mi), cfg.norm_eps)
        k = rms_norm(k, use(p["kn"], mi), cfg.norm_eps)
    if cfg.mrope and pos3_q is not None:
        q = apply_mrope(q, pos3_q, theta)
        k = apply_mrope(k, pos3_q, theta)
    elif theta:
        q = apply_rope(q, pos_q, theta)
        k = apply_rope(k, pos_kv, theta)
    return q, k, v


def _theta(cfg, window):
    """gemma3: global (window=0) layers use the long-context rope base."""
    if cfg.rope_theta_global and window == 0:
        return cfg.rope_theta_global
    return cfg.rope_theta


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------

@jax.named_scope(obs.ATTENTION)
def attn_train(p, x, pos, cfg, mi: MeshInfo, mode: str, causal=True, window=0,
               cross=None, cross_pos=None, pos3=None, want_cache=False):
    """Training/prefill attention sublayer.

    x [B, S_loc, D] seq-sharded; pos [B, S_loc] global positions.
    cross: encoder output [B, Se_loc, D] for cross-attention (whisper dec).
    Returns out [B, S_loc, D] (and (k, v, k_pos) cache when want_cache).
    """
    theta = _theta(cfg, window)
    xkv = cross if cross is not None else x
    pos_kv = cross_pos if cross is not None else pos
    if mode == "head":
        xg = comms.all_gather(x, mi.tp_axes, 1, comms.site("tp", "attn_in"))
        pos_q_g = _gather_pos(pos, mi)
        if cross is not None:
            kvg = comms.all_gather(cross, mi.tp_axes, 1,
                                   comms.site("tp", "attn_cross_kv"))
            pos_kv_g = _gather_pos(cross_pos, mi)
        else:
            kvg, pos_kv_g = xg, pos_q_g
        q, k, v = _project_qkv(p, xg, kvg, pos_q_g, pos_kv_g, cfg, mi, theta,
                               pos3)
        if mi.cp > 1:   # q/k/v cover this rank's cp chunk; ring over cp
            o = ring_attention(q, k, v, pos_q_g, pos_kv_g, mi, causal,
                               window)
        else:
            o = full_attention(q, k, v, pos_q_g, pos_kv_g, causal, window)
        y = jnp.einsum("bsh,hd->bsd", o.reshape(*o.shape[:2], -1),
                       use(p["wo"], mi))
        out = comms.reduce_scatter(y, mi.tp_axes, 1,
                                   comms.site("tp", "attn_out"))
        cache = (k, v, pos_kv_g)      # full cp-local seq, local heads
    else:  # ring: sequence stays sharded, weights replicated over model
        q, k, v = _project_qkv(p, x, xkv, pos, pos_kv, cfg, mi, theta, pos3)
        cache = (k, v, pos_kv)        # local seq slice, all heads
        kb, vb, pkv = k, v, pos_kv
        if mi.tp > 1:
            # KV is GQA-small: gather the tp sub-slices of this rank's cp
            # chunk once (tp-dimension traffic), so the cp ring below
            # rotates whole chunks and queries never move
            kb = comms.all_gather(kb, mi.tp_axes, 1,
                                  comms.site("tp", "attn_kv"))
            vb = comms.all_gather(vb, mi.tp_axes, 1,
                                  comms.site("tp", "attn_kv"))
            pkv = _gather_pos(pos_kv, mi)
        o = ring_attention(q, kb, vb, pos, pkv, mi, causal, window)
        out = jnp.einsum("bsh,hd->bsd", o.reshape(*o.shape[:2], -1),
                         use(p["wo"], mi))
    if want_cache:
        return out, cache
    return out


def _gather_pos(pos, mi):
    return comms.all_gather(pos, mi.tp_axes, 1,
                            comms.site("tp", "attn_pos")) \
        if mi.tp > 1 else pos


@jax.named_scope(obs.ATTENTION)
def attn_decode(p, x, cache, index, cfg, mi: MeshInfo, mode: str, window=0,
                seq_axes=("model",), pos3=None, cross: bool = False):
    """Single-token decode.

    x [B, 1, D] (replicated over model); cache dict with k/v [B, S_chunk, ...]
    and (ring mode) the global seq offset of this shard's chunk.
    index: int32 scalar — current position (== tokens already in cache).
    Returns (out [B,1,D], new_cache).
    """
    theta = _theta(cfg, window)
    B = x.shape[0]
    pos_q = jnp.full((B, 1), index, jnp.int32)
    # head mode: weights are head-sharded, so q/k/v below already hold only
    # this shard's heads.  ring mode: weights replicated -> all heads local.
    q, k_new, v_new = _project_qkv(p, x, x, pos_q, pos_q, cfg, mi, theta, pos3)

    if mode == "head":
        # cache [B, S_max, KV_loc, hd]: full seq local, heads sharded
        k = cache["k"].at[:, index].set(k_new[:, 0])
        v = cache["v"].at[:, index].set(v_new[:, 0])
        S_max = k.shape[1]
        k_pos = jnp.broadcast_to(jnp.arange(S_max, dtype=jnp.int32)[None],
                                 (B, S_max))
        valid = k_pos < index + 1
        o = full_attention(q, k, v, pos_q, k_pos,
                           causal=False, window=window, k_valid=valid)
        y = jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, -1), use(p["wo"], mi))
        out = comms.psum(y, mi.tp_axes, comms.site("tp", "attn_out"))
        return out, {**cache, "k": k, "v": v}

    # ring mode: cache seq-sharded over seq_axes; all heads local
    chunk = cache["k"].shape[1]
    off = _shard_index(mi, seq_axes) * chunk
    if not cross:
        idx_local = index - off
        k = cache["k"].at[:, idx_local].set(k_new[:, 0], mode="drop")
        v = cache["v"].at[:, idx_local].set(v_new[:, 0], mode="drop")
    else:  # cross-attention cache was filled at prefill; never written here
        k, v = cache["k"], cache["v"]
    k_pos = off + jnp.broadcast_to(
        jnp.arange(chunk, dtype=jnp.int32)[None], (B, chunk))
    valid = k_pos < (cache["len"] if cross else index + 1)
    o, m, l = _attn_part(q, k, v,
                         _mask_bias(pos_q, k_pos, False, window, valid),
                         cfg.head_dim_ ** -0.5)
    # flash-decoding combine across the seq shards
    for ax in seq_axes:
        mg = comms.pmax(m, ax)
        w = jnp.exp(m - mg)
        o, m, l = comms.psum(o * w[..., None], ax,
                             comms.site("tp", "attn_combine")), mg, \
            comms.psum(l * w, ax, comms.site("tp", "attn_combine"))
    o = (o / jnp.maximum(l, 1e-30)[..., None]).astype(x.dtype)
    y = jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, -1), use(p["wo"], mi))
    return y, ({**cache, "k": k, "v": v} if not cross else cache)


@jax.named_scope(obs.ATTENTION)
def attn_decode_paged(p, x, pool, tables, pos, active, cfg, mi: MeshInfo,
                      *, bits, block_tokens, window=0, pos3=None,
                      backend=None):
    """Single-token decode against one layer's paged KV pool (head mode).

    x [N, 1, D] — one row per decode SLOT (replicated over model); pool is
    this layer's LOCAL paged pool (:mod:`repro.serve.paged_kv`); tables
    [N, max_blocks] local block ids; pos [N] int32 per-slot positions;
    active [N] bool slot mask.  Inactive slots write nowhere (their block
    id is forced out of range -> dropped scatter) and attend over a fully
    masked sequence, so stale pool contents never reach a live slot.
    Returns (out [N, 1, D], new_pool).
    """
    from repro.serve import paged_kv

    theta = _theta(cfg, window)
    N = x.shape[0]
    pos_q = pos[:, None].astype(jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, x, pos_q, pos_q, cfg, mi, theta,
                                   pos3)
    kv_loc, hd = k_new.shape[2], cfg.head_dim_

    nb_loc = (pool["k"] if bits is None else pool["k"]["q_hi"]).shape[0]
    blk = jnp.take_along_axis(tables, (pos // block_tokens)[:, None],
                              axis=1)[:, 0]
    blk = jnp.where(active, blk, nb_loc)          # inactive -> dropped write
    pool = paged_kv.write_token(pool, blk, pos % block_tokens,
                                k_new[:, 0], v_new[:, 0], bits, backend)

    k, v = paged_kv.read_tables(pool, tables, bits, kv_loc, hd, x.dtype,
                                backend)
    s_pad = k.shape[1]
    k_pos = jnp.broadcast_to(jnp.arange(s_pad, dtype=jnp.int32)[None],
                             (N, s_pad))
    valid = (k_pos <= pos[:, None]) & active[:, None]
    o = full_attention(q, k, v, pos_q, k_pos,
                       causal=False, window=window, k_valid=valid)
    y = jnp.einsum("bsh,hd->bsd", o.reshape(N, 1, -1), use(p["wo"], mi))
    out = comms.psum(y, mi.tp_axes, comms.site("tp", "attn_out"))
    return out, pool


def _shard_index(mi, seq_axes):
    """Linear shard index over the (possibly multi-axis) seq sharding.

    Entries may themselves be AxisPairs (node-factored model axis);
    compat.axis_index linearizes those outer-major."""
    idx = jnp.int32(0)
    for ax in seq_axes:
        idx = idx * compat.axis_size(ax) + compat.axis_index(ax)
    return idx
