"""Capacity-routed top-k Mixture-of-Experts with expert parallelism.

Experts are sharded over the ``model`` axis (EP); token routing crosses the
mesh via (compressed) all-to-all — the paper's related work [29] applies the
same online-compression co-design to MPI all-to-all, so the ``ep`` tag rides
the MP-class codec of the active scheme.

Flow (per shard, tokens T = B_loc * S_loc):
  router -> top-k -> capacity-bounded scatter into [E, C, D] send buffer
  -> all-to-all over model -> per-expert FFN (einsum over the E_loc local
  experts) -> all-to-all back -> weighted combine (+ optional shared expert).

Static shapes throughout: capacity C = ceil(cf * T * k / E); overflow tokens
are dropped (standard Switch/GShard semantics) and reported via aux stats.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core import comms
from repro.models import layers
from repro.models.params import D as Dd, MeshInfo
from repro.models.layers import use

_F32 = jnp.float32


def moe_plan(cfg):
    E, Dm, F = cfg.n_experts, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    if cfg.moe_ws:
        # weight-stationary: pin the ZeRO-3 shard to the expert hidden dim
        # so decode can move (small) tokens instead of (huge) weights
        in_spec, out_spec = ("model", None, "data"), ("model", "data", None)
        ok = False
    else:
        in_spec, out_spec = ("model", None, None), ("model", None, None)
        ok = True
    p = {
        "router": Dd((Dm, E), dtype="float32", fsdp_ok=False),
        "w_in": Dd((E, Dm, F), spec=in_spec, dtype=cfg.dtype, fsdp_ok=ok),
        "w_gate": Dd((E, Dm, F), spec=in_spec, dtype=cfg.dtype, fsdp_ok=ok),
        "w_out": Dd((E, F, Dm), spec=out_spec, dtype=cfg.dtype, fsdp_ok=ok),
    }
    if cfg.shared_expert:
        p["shared"] = layers.mlp_plan(cfg, d_ff=cfg.moe_d_ff or cfg.d_ff)
    return p


def capacity(cfg, tokens: int) -> int:
    c = int(cfg.capacity_factor * tokens * cfg.top_k / cfg.n_experts)
    return max(4, -(-c // 4) * 4)


@jax.named_scope(obs.MOE)
def moe_block(p, x, cfg, mi: MeshInfo, sp: bool = True):
    """x [B, S_loc, D] -> (y [B, S_loc, D], aux dict)."""
    if cfg.moe_ws and not sp and mi.dp > 1:
        # weight-stationary decode (§Perf hillclimb #2): expert weights stay
        # F-sharded over 'data'; the (tiny) token batch is all-gathered,
        # routed redundantly on every data shard (router is replicated, so
        # routing is identical), each shard computes its F slice, and the
        # partial outputs reduce-scatter(+sum) back to the owner shard.
        # Moves ~MB of activations instead of ~GB of expert weights/step.
        xg = comms.all_gather(x, mi.data_axis, 0,
                              comms.site("ep", "moe_decode_batch"))
        y, aux = _moe_ffn(p, xg, cfg, mi, f_sliced=True)
        y = comms.reduce_scatter(y, mi.data_axis, 0,
                                 comms.site("ep", "moe_decode_batch"))
        if cfg.shared_expert:
            y = y + layers.mlp(p["shared"], x, cfg.replace(mlp_kind="swiglu"),
                               mi, sp=False)
        return y, aux
    y, aux = _moe_ffn(p, x, cfg, mi, f_sliced=False, sp=sp)
    if cfg.shared_expert:
        y = y + layers.mlp(p["shared"], x, cfg.replace(mlp_kind="swiglu"),
                           mi, sp=sp)
    return y, aux


def _moe_ffn(p, x, cfg, mi: MeshInfo, f_sliced: bool, sp: bool = False):
    """Router -> dispatch -> all-to-all(model) -> expert FFN -> return route.

    f_sliced: use the raw local F-shard of the expert weights (outputs are
    then partial over the data axis); else ZeRO-3-gather full weights."""
    B, S, Dm = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.top_k
    ep = mi.tp
    E_loc = E // ep
    C = capacity(cfg, T)

    xt = x.reshape(T, Dm)
    logits = (xt.astype(_F32) @ use(p["router"], mi)).astype(_F32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = lax.top_k(probs, k)                              # [T, k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert queue
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)             # [T,k,E]
    flat_oh = onehot.reshape(T * k, E)
    pos_in_e = (jnp.cumsum(flat_oh, axis=0) - flat_oh)              # exclusive
    pos = (pos_in_e * flat_oh).sum(-1).reshape(T, k)                # [T,k]
    keep = (pos < C)
    slot = expert * C + jnp.minimum(pos, C - 1)                     # [T,k]

    # dispatch: scatter tokens into the [E*C, D] send buffer
    buf = jnp.zeros((E * C, Dm), x.dtype)
    src = jnp.repeat(xt[:, None, :], k, axis=1).reshape(T * k, Dm)
    w = keep.reshape(T * k, 1).astype(x.dtype)
    buf = buf.at[slot.reshape(T * k)].add(src * w)

    # all-to-all: [E, C, D] -> experts receive their tokens from every shard.
    # On a tp-node-factored mesh this is the two-stage hierarchical
    # all-to-all (intra-node exchange under ep_*_inner, inter-node under
    # ep_*_outer); chunk order matches the joint outer-major rank order.
    buf = buf.reshape(ep, E_loc * C, Dm)
    recv = comms.all_to_all(buf, mi.tp_axes, 0, 0,
                            comms.site("ep", "moe_dispatch"))  # [ep, E_loc*C, D]
    recv = recv.reshape(ep, E_loc, C, Dm)
    recv = jnp.moveaxis(recv, 1, 0).reshape(E_loc, ep * C, Dm)

    # expert FFN (always gated — SwiGLU-family experts)
    if f_sliced:
        w_in, w_gate, w_out = p["w_in"].v, p["w_gate"].v, p["w_out"].v
    else:
        w_in, w_gate, w_out = use(p["w_in"], mi), use(p["w_gate"], mi), \
            use(p["w_out"], mi)
    h = jax.nn.silu(jnp.einsum("end,edf->enf", recv, w_in))
    h = h * jnp.einsum("end,edf->enf", recv, w_gate)
    out = jnp.einsum("enf,efd->end", h.astype(x.dtype), w_out)      # [E_loc, ep*C, D]

    # return route: inverse rearrangement + all-to-all back
    out = out.reshape(E_loc, ep, C, Dm)
    out = jnp.moveaxis(out, 0, 1).reshape(ep, E_loc * C, Dm)
    back = comms.all_to_all(out, mi.tp_axes, 0, 0,
                            comms.site("ep", "moe_combine"))
    back = back.reshape(E * C, Dm)

    # combine: gather each (token, choice) result, weight by gate
    got = jnp.take(back, slot.reshape(T * k), axis=0).reshape(T, k, Dm)
    y = jnp.sum(got * (gate * keep).astype(x.dtype)[..., None], axis=1)
    y = y.reshape(B, S, Dm)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(0)                                              # [E]
    ce = (onehot.sum(1).astype(_F32)).mean(0) / k                   # frac per e
    aux = {"lb_loss": E * jnp.sum(me * ce),
           "drop_frac": 1.0 - keep.mean()}
    return y, aux
