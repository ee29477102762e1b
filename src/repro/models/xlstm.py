"""xLSTM blocks: mLSTM (matrix memory, chunkwise-parallel) and sLSTM
(scalar memory with recurrent mixing, inherently sequential).

mLSTM reuses the chunked linear-recurrence engine from ``ssm.py`` (same
S_t = a_t S + u (x) r shape) with the cross-shard prefix over compressed
ppermute.  Simplification vs the xLSTM paper: the exponential input gate is
replaced by a sigmoid gate so no max-stabilizer scan is needed — the
compute/communication profile (what this systems repro measures) is
unchanged; noted in DESIGN.md.

sLSTM cannot be parallelized over sequence (nonlinear recurrence through the
hidden state — the xLSTM paper says as much), so under sequence sharding we
either
  * all-to-all "batch<->seq transpose": trade the seq sharding for batch
    sharding over the model axis (zero redundancy; needs B_loc % tp == 0), or
  * all-gather the sequence and compute redundantly (fallback).
The a2a path is the default and is compressed under the ``ep`` tag.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core import comms, compat
from repro.models.params import D as Dd, MeshInfo
from repro.models.layers import use
from repro.models.ssm import chunked_outer_scan, cross_shard_prefix, _bexp

_F32 = jnp.float32


# --------------------------------------------------------------------------
# mLSTM
# --------------------------------------------------------------------------

def mlstm_plan(cfg):
    Dm = cfg.d_model
    di = int(cfg.proj_factor * Dm)          # value width
    H = cfg.n_heads
    hd = cfg.head_dim_                      # q/k width per head
    return {
        "w_q": Dd((Dm, H * hd), dtype=cfg.dtype),
        "w_k": Dd((Dm, H * hd), dtype=cfg.dtype),
        "w_v": Dd((Dm, di), dtype=cfg.dtype),
        "w_i": Dd((Dm, H), dtype=cfg.dtype),
        "w_f": Dd((Dm, H), dtype=cfg.dtype),
        "b_f": Dd((H,), init="ones", dtype="float32", fsdp_ok=False),
        "w_o": Dd((Dm, di), dtype=cfg.dtype),
        "w_out": Dd((di, Dm), dtype=cfg.dtype),
    }


@jax.named_scope(obs.XLSTM)
def mlstm_block(p, x, cfg, mi: MeshInfo, sp: bool = True,
                want_cache: bool = False):
    """x [B, S_loc, D] -> [B, S_loc, D] (+ decode-layout state cache)."""
    B, S, Dm = x.shape
    H, hd = cfg.n_heads, cfg.head_dim_
    di = int(cfg.proj_factor * Dm)
    Pv = di // H

    q = jnp.einsum("bsd,dh->bsh", x, use(p["w_q"], mi)).reshape(B, S, H, hd)
    k = jnp.einsum("bsd,dh->bsh", x, use(p["w_k"], mi)).reshape(B, S, H, hd)
    v = jnp.einsum("bsd,de->bse", x, use(p["w_v"], mi)).reshape(B, S, H, Pv)
    f = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", x, use(p["w_f"], mi))
                       .astype(_F32) + use(p["b_f"], mi))
    ig = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", x, use(p["w_i"], mi))
                        .astype(_F32))
    kq_scale = hd ** -0.5
    u_num = ig[..., None] * v.astype(_F32)                     # [B,S,H,Pv]
    r = k.astype(_F32) * kq_scale
    qf = q.astype(_F32)

    num, Sn_fin, d_tot = chunked_outer_scan(f, u_num, r, qf)
    u_den = ig[..., None]                                      # [B,S,H,1]
    den, Sd_fin, _ = chunked_outer_scan(f, u_den, r, qf)

    sn_in = sd_in = None
    if sp and mi.tp > 1:
        ax = mi.tp_axes
        sn_in = cross_shard_prefix(d_tot, Sn_fin, mi, ax)
        sd_in = cross_shard_prefix(d_tot, Sd_fin, mi, ax)
        la = jnp.log(jnp.maximum(f, 1e-38))
        d0 = jnp.exp(jnp.cumsum(la, axis=1))                   # [B,S,H]
        num = num + jnp.einsum("bhpn,bshn->bshp", sn_in, qf) * d0[..., None]
        den = den + jnp.einsum("bhpn,bshn->bshp", sd_in, qf) * d0[..., None]

    y = num / jnp.maximum(jnp.abs(den), 1.0)                   # [B,S,H,Pv]
    o = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, use(p["w_o"], mi))
                       .astype(_F32))
    y = (y.reshape(B, S, di) * o).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", y, use(p["w_out"], mi))
    if not want_cache:
        return out

    # prefill -> decode handoff (decode shards C on the value dim)
    from repro.models.ssm import _broadcast_final
    inc_n = Sn_fin if sn_in is None else sn_in * _bexp(d_tot) + Sn_fin
    inc_d = Sd_fin if sd_in is None else sd_in * _bexp(d_tot) + Sd_fin
    C_tot, _ = _broadcast_final(inc_n, jnp.zeros((B, 1, 1), _F32), mi, sp)
    n_tot, _ = _broadcast_final(inc_d, jnp.zeros((B, 1, 1), _F32), mi, sp)
    tp = mi.tp
    if Pv % tp == 0 and tp > 1:
        i = compat.axis_index(mi.tp_axes)
        C_tot = jax.lax.dynamic_slice_in_dim(C_tot, i * (Pv // tp),
                                             Pv // tp, axis=2)
    return out, {"C": C_tot, "n": n_tot[:, :, 0, :]}


@jax.named_scope(obs.XLSTM)
def mlstm_decode(p, x, cache, cfg, mi: MeshInfo):
    """Single token; matrix state sharded over model on the value dim.

    cache {"C": [B,H,Pv_loc,hd], "n": [B,H,hd]}  (n replicated: small).
    """
    B = x.shape[0]
    H, hd = cfg.n_heads, cfg.head_dim_
    di = int(cfg.proj_factor * cfg.d_model)
    Pv = di // H
    tp = mi.tp
    Pv_loc = Pv // tp if Pv % tp == 0 else Pv
    sharded = Pv % tp == 0 and tp > 1
    i = compat.axis_index(mi.tp_axes)
    xt = x[:, 0]

    q = (xt @ use(p["w_q"], mi)).reshape(B, H, hd).astype(_F32)
    k = (xt @ use(p["w_k"], mi)).reshape(B, H, hd).astype(_F32) * hd ** -0.5
    v_full = (xt @ use(p["w_v"], mi)).reshape(B, H, Pv).astype(_F32)
    if sharded:
        # value columns for this shard: slice per head
        v = lax.dynamic_slice_in_dim(v_full, i * Pv_loc, Pv_loc, axis=2)
    else:
        v = v_full
    f = jax.nn.sigmoid((xt @ use(p["w_f"], mi)).astype(_F32)
                       + use(p["b_f"], mi))
    ig = jax.nn.sigmoid((xt @ use(p["w_i"], mi)).astype(_F32))

    C = cache["C"] * f[:, :, None, None] \
        + (ig[..., None] * v)[..., None] * k[:, :, None, :]
    n = cache["n"] * f[..., None] + ig[..., None] * k
    num = jnp.einsum("bhpn,bhn->bhp", C, q)                    # [B,H,Pv(_loc)]
    den = jnp.einsum("bhn,bhn->bh", n, q)[..., None]
    y = num / jnp.maximum(jnp.abs(den), 1.0)

    o = jax.nn.sigmoid((xt @ use(p["w_o"], mi)).astype(_F32))
    if sharded:
        # o-gate slice + row-sliced out-proj, then psum over model
        og = o.reshape(B, H, Pv)
        og = lax.dynamic_slice_in_dim(og, i * Pv_loc, Pv_loc, axis=2)
        y = (y * og).reshape(B, H * Pv_loc).astype(x.dtype)
        w_out = use(p["w_out"], mi).reshape(H, Pv, cfg.d_model)
        w_loc = lax.dynamic_slice_in_dim(w_out, i * Pv_loc, Pv_loc, axis=1)
        out = y @ w_loc.reshape(H * Pv_loc, cfg.d_model)
        out = comms.psum(out[:, None], mi.tp_axes,
                         comms.site("tp", "xlstm_out"))
    else:
        y = (y.reshape(B, di) * o).astype(x.dtype)
        out = (y @ use(p["w_out"], mi))[:, None]
    return out, {"C": C, "n": n}


# --------------------------------------------------------------------------
# sLSTM
# --------------------------------------------------------------------------

def slstm_plan(cfg):
    Dm = cfg.d_model
    H = cfg.n_heads
    hd = Dm // H
    p = {"w_out": Dd((Dm, Dm), dtype=cfg.dtype)}
    for g in ("i", "f", "z", "o"):
        p[f"w_{g}"] = Dd((Dm, Dm), dtype=cfg.dtype)
        p[f"r_{g}"] = Dd((H, hd, hd), scale=0.05, dtype=cfg.dtype)
        p[f"b_{g}"] = Dd((Dm,), init="zeros", dtype="float32", fsdp_ok=False)
    return p


def _slstm_scan(p, x, cfg, mi, h0=None, c0=None, n0=None, m0=None):
    """Sequential sLSTM over the local sequence. x [B, S, D] (full channels).

    Exponential gates with the xLSTM max-stabilizer (easy here: the scan is
    sequential anyway).  Returns (y [B,S,D], final (h,c,n,m))."""
    B, S, Dm = x.shape
    H = cfg.n_heads
    hd = Dm // H

    W = {g: use(p[f"w_{g}"], mi) for g in "ifzo"}
    R = {g: use(p[f"r_{g}"], mi).astype(_F32) for g in "ifzo"}
    bias = {g: use(p[f"b_{g}"], mi) for g in "ifzo"}
    pre = {g: (jnp.einsum("bsd,de->bse", x, W[g]).astype(_F32)
               + bias[g]).reshape(B, S, H, hd) for g in "ifzo"}

    if h0 is None:
        h0 = jnp.zeros((B, H, hd), _F32)
        c0 = jnp.zeros((B, H, hd), _F32)
        n0 = jnp.ones((B, H, hd), _F32)
        m0 = jnp.zeros((B, H, hd), _F32)
    h0, c0, n0, m0 = comms.match_vma((h0, c0, n0, m0), (x, pre))

    def step(carry, t):
        h, c, n, m = carry
        g = {k: t[j] + jnp.einsum("bhe,heo->bho", h, R[k])
             for j, k in enumerate("ifzo")}
        m_new = jnp.maximum(g["f"] + m, g["i"])
        iq = jnp.exp(g["i"] - m_new)
        fq = jnp.exp(g["f"] + m - m_new)
        c = fq * c + iq * jnp.tanh(g["z"])
        n = fq * n + iq
        h = jax.nn.sigmoid(g["o"]) * c / jnp.maximum(n, 1e-6)
        return (h, c, n, m_new), h

    xs = tuple(jnp.moveaxis(pre[g], 1, 0) for g in "ifzo")
    (h, c, n, m), ys = lax.scan(step, (h0, c0, n0, m0), xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, Dm)
    return y, (h, c, n, m)


@jax.named_scope(obs.XLSTM)
def slstm_block(p, x, cfg, mi: MeshInfo, sp: bool = True,
                want_cache: bool = False):
    """x [B, S_loc, D] -> [B, S_loc, D] under sequence sharding.

    Default: all-to-all batch<->seq transpose (compressed 'ep' tag) so every
    model shard owns complete sequences for a batch slice; fallback:
    all-gather seq + redundant compute when B_loc doesn't divide tp.
    """
    B, S, Dm = x.shape
    tp = mi.tp
    ax = mi.tp_axes
    if not sp or tp == 1:
        y, fin = _slstm_scan(p, x, cfg, mi)
    elif B % tp == 0:
        xt = comms.all_to_all(x, ax, 0, 1,
                              comms.site("ep", "slstm_transpose"))  # [B/tp, S*tp, D]
        y, fin = _slstm_scan(p, xt, cfg, mi)
        y = comms.all_to_all(y, ax, 1, 0,
                             comms.site("ep", "slstm_transpose"))  # -> [B, S_loc, D]
        if want_cache:                                 # regather batch slices
            fin = tuple(comms.all_gather(t, ax, 0,
                                         comms.site("tp", "slstm_state"))
                        for t in fin)
    else:
        xg = comms.all_gather(x, ax, 1,
                              comms.site("tp", "slstm_seq"))  # [B, S_full, D]
        yg, fin = _slstm_scan(p, xg, cfg, mi)
        i = compat.axis_index(ax)
        y = lax.dynamic_slice_in_dim(yg, i * S, S, axis=1)
    out = jnp.einsum("bsd,de->bse", y, use(p["w_out"], mi))
    if not want_cache:
        return out
    h, c, n, m = fin
    return out, {"h": h, "c": c, "n": n, "m": m}


@jax.named_scope(obs.XLSTM)
def slstm_decode(p, x, cache, cfg, mi: MeshInfo):
    """Single step; state replicated (sLSTM state is small)."""
    y, (h, c, n, m) = _slstm_scan(p, x, cfg, mi, cache["h"], cache["c"],
                                  cache["n"], cache["m"])
    out = jnp.einsum("bsd,de->bse", y, use(p["w_out"], mi))
    return out, {"h": h, "c": c, "n": n, "m": m}
