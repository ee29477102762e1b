"""Linear-recurrence engine + Mamba2 block (zamba2's SSM half).

Core recurrence (shared by Mamba2 SSD and mLSTM):

    S_t = a_t * S_{t-1} + u_t (x) r_t          S in R^{P x N}, a_t in (0,1]
    y_t = S_t . q_t                            contraction over N

computed chunkwise: intra-chunk via a masked quadratic form (never
materializing per-step states), inter-chunk via lax.scan over chunk states,
and *cross-shard* (sequence sharded over the model axis) via a Hillis-Steele
exclusive prefix over (compressed) ppermute — the recurrent-state analogue of
the paper's PP point-to-point compression (DESIGN.md §5).

All decays stay in log-space within a chunk so every exp() argument is <= 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core import comms, compat
from repro.models import layers
from repro.models.params import D as Dd, MeshInfo
from repro.models.layers import use, rms_norm

_F32 = jnp.float32


# --------------------------------------------------------------------------
# chunked linear-recurrence engine
# --------------------------------------------------------------------------

def chunked_outer_scan(a, u, r, q, chunk: int = 128, s0=None):
    """See module docstring.

    a [B,L,H], u [B,L,H,P], r [B,L,H,N], q [B,L,H,N]
    -> y [B,L,H,P], state_out [B,H,P,N], decay_total [B,H]
    s0: optional initial state [B,H,P,N] (from the previous seq shard).
    """
    B, L, H = a.shape
    P, N = u.shape[-1], r.shape[-1]
    nc = -(-L // chunk)
    pad = nc * chunk - L
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r = jnp.pad(r, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

    def to_chunks(t):
        return jnp.moveaxis(t.reshape(B, nc, chunk, *t.shape[2:]), 1, 0)

    ac, uc, rc, qc = map(to_chunks, (a, u, r, q))           # [nc,B,Q,H,...]
    la = jnp.log(jnp.maximum(ac.astype(_F32), 1e-38))
    cum = jnp.cumsum(la, axis=2)                            # [nc,B,Q,H]

    if s0 is None:
        s0 = jnp.zeros((B, H, P, N), _F32)
    s0 = comms.match_vma(s0, (a, u, r, q))

    Q = chunk
    tri = jnp.tril(jnp.ones((Q, Q), bool))                  # s <= t

    def step(S, blk):
        ab_cum, ub, rb, qb = blk                            # [B,Q,H(,*)]
        # intra-chunk quadratic form
        G = jnp.einsum("bthn,bshn->bhts", qb.astype(_F32), rb.astype(_F32))
        ct = ab_cum.transpose(0, 2, 1)                      # [B,H,Q]
        wlog = ct[:, :, :, None] - ct[:, :, None, :]        # cum_t - cum_s
        W = jnp.exp(jnp.where(tri, wlog, -jnp.inf))         # mask pre-exp
        y = jnp.einsum("bhts,bshp->bthp", G * W, ub.astype(_F32))
        # carry-in contribution: q_t . (S * decay(start->t])
        d0 = jnp.exp(ab_cum)                                # [B,Q,H]
        y = y + jnp.einsum("bhpn,bthn->bthp", S, qb.astype(_F32)) \
            * d0[..., None]
        # chunk state update
        d_end = jnp.exp(ab_cum[:, -1:, :] - ab_cum)         # decay s->end
        S_new = S * jnp.exp(ab_cum[:, -1, :])[:, :, None, None] \
            + jnp.einsum("bshp,bshn->bhpn",
                         ub.astype(_F32) * d_end[..., None], rb.astype(_F32))
        return S_new, y

    S_fin, ys = lax.scan(step, s0, (cum, uc, rc, qc))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, nc * chunk, H, P)[:, :L]
    # sum of per-chunk final log-decays = total log decay over the shard
    decay_total = jnp.exp(jnp.sum(cum[:, :, -1, :], axis=0))
    return y, S_fin, decay_total


def cross_shard_prefix(decay, state, mi: MeshInfo, axis: str):
    """Exclusive prefix of the linear recurrence across seq shards.

    decay [B,H] (f32), state [B,H,P,N] (f32) — per-shard totals.
    Returns s_in [B,H,P,N]: the state entering this shard.
    Hillis-Steele over (compressed tag 'pp') ppermute: O(log tp) hops.
    """
    tp = compat.axis_size(axis)
    if tp == 1:
        return jnp.zeros_like(state)
    i = compat.axis_index(axis)
    d, s = decay.astype(_F32), state.astype(_F32)
    step = 1
    while step < tp:
        perm = [(j, j + step) for j in range(tp - step)]
        d_in = comms.ppermute(d, axis, perm, comms.site("pp", "ssm_scan"))
        s_in = comms.ppermute(s, axis, perm, comms.site("pp", "ssm_scan"))
        has = (i >= step)
        # incoming left prefix decays through the local segment
        s = jnp.where(has, s_in * _bexp(d) + s, s)
        d = jnp.where(has, d_in * d, d)
        step *= 2
    # shift right by one for the exclusive prefix
    perm = [(j, j + 1) for j in range(tp - 1)]
    s_prev = comms.ppermute(s, axis, perm, comms.site("pp", "ssm_scan"))
    return jnp.where(i > 0, s_prev, jnp.zeros_like(s_prev))


def _bexp(d):
    """broadcast decay [B,H] onto state [B,H,P,N]."""
    return d[:, :, None, None]


# --------------------------------------------------------------------------
# Mamba2 block
# --------------------------------------------------------------------------

def mamba_plan(cfg):
    Dm, di = cfg.d_model, cfg.d_inner
    H = di // cfg.ssm_head_dim
    N, K = cfg.ssm_state, cfg.conv_kernel
    return {
        "w_x": Dd((Dm, di), dtype=cfg.dtype),
        "w_z": Dd((Dm, di), dtype=cfg.dtype),
        "w_bc": Dd((Dm, 2 * N), dtype=cfg.dtype),
        "w_dt": Dd((Dm, H), dtype=cfg.dtype),
        "dt_bias": Dd((H,), init="zeros", dtype="float32", fsdp_ok=False),
        "A_log": Dd((H,), init="zeros", dtype="float32", fsdp_ok=False),
        "D_skip": Dd((H,), init="ones", dtype="float32", fsdp_ok=False),
        "conv_w": Dd((K, di), scale=0.1, dtype=cfg.dtype, fsdp_ok=False),
        "conv_b": Dd((di,), init="zeros", dtype=cfg.dtype, fsdp_ok=False),
        "gn": Dd((di,), init="zeros", dtype="float32", fsdp_ok=False),
        "w_out": Dd((di, Dm), dtype=cfg.dtype),
    }


def _causal_conv(xi, w, b, prev):
    """Depthwise causal conv, kernel K, with halo `prev` [B, K-1, di]."""
    K = w.shape[0]
    xp = jnp.concatenate([prev, xi], axis=1)
    y = sum(xp[:, j:j + xi.shape[1]] * w[j] for j in range(K))
    return y + b


@jax.named_scope(obs.SSM)
def mamba_block(p, x, cfg, mi: MeshInfo, sp: bool = True,
                want_cache: bool = False):
    """x [B, S_loc, D] -> [B, S_loc, D].  Seq sharded over model when sp.

    want_cache: also return the decode-layout cache (channel/head-sharded
    final state + conv tail) for prefill -> decode handoff."""
    B, S, Dm = x.shape
    di = cfg.d_inner
    H = di // cfg.ssm_head_dim
    P = cfg.ssm_head_dim
    N = cfg.ssm_state
    ax = mi.tp_axes

    xi_raw = jnp.einsum("bsd,de->bse", x, use(p["w_x"], mi))
    z = jnp.einsum("bsd,de->bse", x, use(p["w_z"], mi))

    # conv halo from the previous seq shard (zero for shard 0)
    K = cfg.conv_kernel
    tail = xi_raw[:, -(K - 1):]
    if sp and mi.tp > 1:
        perm = [(j, j + 1) for j in range(mi.tp - 1)]
        halo = comms.ppermute(tail, ax, perm,
                              comms.site("pp", "conv_halo"))
        halo = jnp.where(compat.axis_index(ax) > 0, halo,
                         jnp.zeros_like(halo))
    else:
        halo = jnp.zeros_like(tail)
    xi = jax.nn.silu(_causal_conv(xi_raw, use(p["conv_w"], mi),
                                  use(p["conv_b"], mi), halo))

    dt = jax.nn.softplus(jnp.einsum("bsd,dh->bsh", x, use(p["w_dt"], mi))
                         .astype(_F32) + use(p["dt_bias"], mi))
    a = jnp.exp(-dt * jnp.exp(use(p["A_log"], mi)))         # [B,S,H]
    bc = jnp.einsum("bsd,dn->bsn", x, use(p["w_bc"], mi)).astype(_F32)
    B_, C_ = jnp.split(bc, 2, axis=-1)                      # [B,S,N]
    Bh = jnp.broadcast_to(B_[:, :, None, :], (B, S, H, N))
    Ch = jnp.broadcast_to(C_[:, :, None, :], (B, S, H, N))
    u = dt[..., None] * xi.reshape(B, S, H, P).astype(_F32)

    y, S_fin, d_tot = chunked_outer_scan(a, u, Bh, Ch)
    s_in = None
    if sp and mi.tp > 1:
        s_in = cross_shard_prefix(d_tot, S_fin, mi, ax)
        # add carried-state contribution: q_t . (s_in * decay(start->t])
        la = jnp.log(jnp.maximum(a, 1e-38))
        d0 = jnp.exp(jnp.cumsum(la, axis=1))                # [B,S,H]
        y = y + jnp.einsum("bhpn,bshn->bshp", s_in, Ch) * d0[..., None]

    y = y + use(p["D_skip"], mi)[None, None, :, None] \
        * xi.reshape(B, S, H, P).astype(_F32)
    y = y.reshape(B, S, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, use(p["gn"], mi), cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, use(p["w_out"], mi))
    if not want_cache:
        return out

    # ---- prefill -> decode state handoff (decode layout: sharded on H/di)
    incl = S_fin if s_in is None else s_in * _bexp(d_tot) + S_fin
    state, conv_tail = _broadcast_final(incl, tail, mi, sp)
    tp = mi.tp
    i = compat.axis_index(ax)
    H_loc, di_loc = H // tp, di // tp
    state = lax.dynamic_slice_in_dim(state, i * H_loc, H_loc, axis=1)
    conv_tail = lax.dynamic_slice_in_dim(conv_tail, i * di_loc, di_loc,
                                         axis=2)
    return out, {"conv": conv_tail.astype(x.dtype), "state": state}


def _broadcast_final(incl, tail, mi: MeshInfo, sp: bool):
    """The global-final recurrent state / conv tail live on the LAST seq
    shard; broadcast them to every shard (masked psum over model)."""
    ax = mi.tp_axes
    if not (sp and mi.tp > 1):
        return incl, tail
    last = compat.axis_index(ax) == mi.tp - 1
    state = comms.psum(jnp.where(last, incl, jnp.zeros_like(incl)), ax,
                       comms.site("tp", "ssm_state"))
    ct = comms.psum(jnp.where(last, tail.astype(_F32),
                              jnp.zeros_like(tail, _F32)), ax,
                    comms.site("tp", "ssm_state"))
    return state, ct


# --------------------------------------------------------------------------
# decode (single token): channel-sharded over model via weight slicing
# --------------------------------------------------------------------------

@jax.named_scope(obs.SSM)
def mamba_decode(p, x, cache, cfg, mi: MeshInfo):
    """x [B, 1, D]; cache {conv: [B,K-1,di_loc], state: [B,H_loc,P,N]}.

    Channels/heads sliced per model shard; out-proj partial + psum(tp).
    """
    B = x.shape[0]
    di, H, P, N = cfg.d_inner, cfg.d_inner // cfg.ssm_head_dim, \
        cfg.ssm_head_dim, cfg.ssm_state
    tp = mi.tp
    di_loc, H_loc = di // tp, H // tp
    i = compat.axis_index(mi.tp_axes)

    def col(w, width):
        return lax.dynamic_slice_in_dim(w, i * width, width, axis=1)

    def vec(w, width):
        return lax.dynamic_slice_in_dim(w, i * width, width, axis=0)

    xt = x[:, 0]
    xi = xt @ col(use(p["w_x"], mi), di_loc)
    z = xt @ col(use(p["w_z"], mi), di_loc)
    conv_w = col(use(p["conv_w"], mi), di_loc)
    conv_b = vec(use(p["conv_b"], mi), di_loc)
    win = jnp.concatenate([cache["conv"], xi[:, None, :]], axis=1)
    K = cfg.conv_kernel
    xc = jax.nn.silu(sum(win[:, j] * conv_w[j] for j in range(K)) + conv_b)

    dt = jax.nn.softplus(
        (xt @ col(use(p["w_dt"], mi), H_loc)).astype(_F32)
        + lax.dynamic_slice_in_dim(use(p["dt_bias"], mi), i * H_loc, H_loc, 0))
    A = lax.dynamic_slice_in_dim(use(p["A_log"], mi), i * H_loc, H_loc, 0)
    a = jnp.exp(-dt * jnp.exp(A))                           # [B,H_loc]
    bc = (xt @ use(p["w_bc"], mi)).astype(_F32)
    B_, C_ = jnp.split(bc, 2, axis=-1)                      # [B,N]
    u = dt[..., None] * xc.reshape(B, H_loc, P).astype(_F32)
    S_new = cache["state"] * a[:, :, None, None] \
        + u[..., None] * B_[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", S_new, C_)
    Dk = lax.dynamic_slice_in_dim(use(p["D_skip"], mi), i * H_loc, H_loc, 0)
    y = y + Dk[None, :, None] * xc.reshape(B, H_loc, P).astype(_F32)
    y = y.reshape(B, di_loc).astype(x.dtype) * jax.nn.silu(z)
    gn = lax.dynamic_slice_in_dim(use(p["gn"], mi), i * di_loc, di_loc, 0)
    y = rms_norm(y, gn, cfg.norm_eps)
    out = y @ lax.dynamic_slice_in_dim(use(p["w_out"], mi), i * di_loc,
                                       di_loc, axis=0)
    out = comms.psum(out[:, None, :], mi.tp_axes,
                     comms.site("tp", "ssm_out"))
    new_cache = {"conv": win[:, 1:], "state": S_new}
    return out, new_cache
