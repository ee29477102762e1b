"""Shared layer primitives (all written against *local* shard shapes, to be
called inside ``shard_map``; every collective goes through ``repro.core.comms``
so the active compression scheme governs the wire).

Training/prefill layout ("SP", DESIGN.md §4):
    activations [B_loc, S_loc, D] — batch over data(+pod), seq over model.
Decode layout: [B_loc, 1, D] — batch over data, replicated over model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core import comms, compat
from repro.models.params import Pv, fsdp_dim, MeshInfo

_F32 = jnp.float32


def use(p: Pv, mi: MeshInfo, name: str | None = None):
    """Unwrap a param leaf, re-gathering its ZeRO-3 shard if needed.

    The all-gather rides the ``zero`` site (compressed per policy); its
    custom-vjp backward is a reduce-scatter over data — i.e. the DP
    gradient reduction for fsdp leaves happens here, once, with the ZeRO
    codec (paper §III C3: no double compression of gradients).  ``name``
    labels the site so per-tensor rules can target individual leaves
    (e.g. keep embedding gathers mild: ``Rule("bq16", dim="zero",
    name="embed*")``)."""
    d = fsdp_dim(p.spec)
    if d is None:
        return p.v
    return comms.all_gather(p.v, mi.data_axis, d, comms.site("zero", name))


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rms_norm(x, gain, eps):
    xf = x.astype(_F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * (1.0 + gain.astype(_F32))).astype(x.dtype)


def layer_norm(x, gain, bias, eps):
    xf = x.astype(_F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * gain.astype(_F32) + bias.astype(_F32)).astype(x.dtype)


@jax.named_scope(obs.NORM)
def norm(p, x, cfg, mi):
    if cfg.norm == "ln":
        return layer_norm(x, use(p["g"], mi), use(p["b"], mi), cfg.norm_eps)
    return rms_norm(x, use(p["g"], mi), cfg.norm_eps)


def norm_plan(cfg, D_):
    from repro.models.params import D as Dd
    if cfg.norm == "ln":
        return {"g": Dd((D_,), init="ones", dtype="float32", fsdp_ok=False),
                "b": Dd((D_,), init="zeros", dtype="float32", fsdp_ok=False)}
    return {"g": Dd((D_,), init="zeros", dtype="float32", fsdp_ok=False)}


# --------------------------------------------------------------------------
# rotary position embeddings (incl. qwen2-vl M-RoPE)
# --------------------------------------------------------------------------

def _rope_freqs(hd: int, theta: float):
    return theta ** (-jnp.arange(0, hd // 2, dtype=_F32) / (hd // 2))


def apply_rope(x, pos, theta: float):
    """x: [B, S, H, hd]; pos: [B, S] int32 (global positions)."""
    hd = x.shape[-1]
    ang = pos[..., None].astype(_F32) * _rope_freqs(hd, theta)   # [B,S,hd/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(_F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], -1).astype(x.dtype)


def mrope_sections(hd: int):
    """qwen2-vl: split the hd/2 rotary freqs into (t, h, w) sections."""
    half = hd // 2
    t = half // 4
    h = (half - t) // 2
    return (t, h, half - t - h)


def apply_mrope(x, pos3, theta: float):
    """x: [B, S, H, hd]; pos3: [B, S, 3] (t/h/w position ids)."""
    hd = x.shape[-1]
    freqs = _rope_freqs(hd, theta)                                # [hd/2]
    secs = mrope_sections(hd)
    parts, off = [], 0
    for i, n in enumerate(secs):
        parts.append(pos3[..., i:i + 1].astype(_F32) * freqs[off:off + n])
        off += n
    ang = jnp.concatenate(parts, -1)                              # [B,S,hd/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(_F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], -1).astype(x.dtype)


# --------------------------------------------------------------------------
# vocab-parallel embedding & cross-entropy (Megatron-style)
# --------------------------------------------------------------------------

def embed_plan(cfg):
    from repro.models.params import D as Dd
    return {"table": Dd((cfg.padded_vocab, cfg.d_model), spec=("model", None),
                        dtype=cfg.dtype)}


@jax.named_scope(obs.EMBED)
def embed(p, tokens, cfg, mi, sp: bool = True):
    """Vocab-parallel embedding (Megatron-SP form).

    sp=True: tokens are the FULL sequence [B, S] (replicated over model);
    each vocab shard contributes its rows and the partial embeddings are
    reduce-scattered over the sequence -> [B, S_loc, D].  (Megatron fuses
    the embedding all-reduce into this RS under sequence parallelism.)
    sp=False (decode): [B, 1] -> psum(model) -> [B, 1, D] replicated.
    """
    table = use(p["table"], mi, "embed_table")     # [V_loc, D]
    v_loc = table.shape[0]
    lo = compat.axis_index(mi.tp_axes) * v_loc
    local = tokens - lo
    ok = (local >= 0) & (local < v_loc)
    e = jnp.take(table, jnp.clip(local, 0, v_loc - 1), axis=0)
    e = e * ok[..., None].astype(e.dtype)
    if sp and mi.tp > 1:
        e = comms.reduce_scatter(e, mi.tp_axes, 1, comms.site("tp", "embed"))
    else:
        e = comms.psum(e, mi.tp_axes, comms.site("tp", "embed"))
    if cfg.scale_embed:
        e = e * jnp.asarray(cfg.d_model ** 0.5, e.dtype)
    return e


@jax.named_scope(obs.HEAD)
def lm_head_logits(params, x, cfg, mi, sp: bool = True):
    """x [B, S_loc, D] -> vocab-sharded logits [B, S, V_loc] (f32).

    sp=True gathers the sequence over model first, so every model shard
    scores the full sequence against its vocab slice (required for the
    vocab-parallel cross-entropy psums to be token-consistent)."""
    if sp and mi.tp > 1:
        x = comms.all_gather(x, mi.tp_axes, 1, comms.site("tp", "lm_head"))
    if cfg.tie_embeddings:
        w = use(params["embed"]["table"], mi, "embed_table")  # [V_loc, D]
        return jnp.einsum("bsd,vd->bsv", x.astype(_F32), w.astype(_F32))
    w = use(params["lm_head"]["w"], mi, "lm_head_w")  # [D, V_loc]
    return jnp.einsum("bsd,dv->bsv", x.astype(_F32), w.astype(_F32))


def lm_head_plan(cfg):
    from repro.models.params import D as Dd
    if cfg.tie_embeddings:
        return {}
    return {"lm_head": {"w": Dd((cfg.d_model, cfg.padded_vocab),
                                spec=(None, "model"), dtype=cfg.dtype)}}


@jax.named_scope(obs.HEAD)
def vocab_parallel_xent(logits, labels, cfg, mi):
    """Vocab-sharded cross-entropy.

    logits [B, S, V_loc] f32, labels [B, S] int32 (-1 = pad).
    Returns per-token loss [B, S] and weight mask [B, S].
    """
    v_loc = logits.shape[-1]
    lo = compat.axis_index(mi.tp_axes) * v_loc
    # guard padded vocab tail: tokens >= vocab_size never occur as labels,
    # but padded logit columns exist — mask them out of the lse.
    col = lo + jnp.arange(v_loc)
    col_ok = (col < cfg.vocab_size)
    logits = jnp.where(col_ok, logits, -1e30)

    # stabilizer is gradient-free (lse is shift-invariant); comms.pmax
    # carries a zero VJP
    m = comms.pmax(jnp.max(lax.stop_gradient(logits), axis=-1),
                   mi.tp_axes)                                     # [B,S]
    z = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
    z = comms.psum(z, mi.tp_axes, comms.site("tp", "xent"))
    lse = m + jnp.log(z)

    local = labels - lo
    ok = (local >= 0) & (local < v_loc)
    tl = jnp.take_along_axis(
        logits, jnp.clip(local, 0, v_loc - 1)[..., None], axis=-1)[..., 0]
    tl = comms.psum(jnp.where(ok, tl, 0.0), mi.tp_axes,
                    comms.site("tp", "xent"))
    w = (labels >= 0).astype(_F32)
    return (lse - tl) * w, w


# --------------------------------------------------------------------------
# Megatron(-SP) MLP
# --------------------------------------------------------------------------

_GATED = {"swiglu", "geglu"}


def mlp_plan(cfg, d_ff=None):
    from repro.models.params import D as Dd
    f = d_ff or cfg.d_ff
    p = {"w1": Dd((cfg.d_model, f), spec=(None, "model"), dtype=cfg.dtype),
         "w2": Dd((f, cfg.d_model), spec=("model", None), dtype=cfg.dtype)}
    if cfg.mlp_kind in _GATED:
        p["w3"] = Dd((cfg.d_model, f), spec=(None, "model"), dtype=cfg.dtype)
    return p


def _act(h, kind):
    if kind in ("swiglu",):
        return jax.nn.silu(h)
    if kind in ("geglu", "gelu"):
        return jax.nn.gelu(h)
    if kind == "relu2":
        r = jax.nn.relu(h)
        return r * r
    raise ValueError(kind)


@jax.named_scope(obs.MLP)
def mlp(p, x, cfg, mi, sp: bool = True):
    """Column->row parallel MLP.

    sp=True  (train/prefill): AG(seq over model) -> matmuls -> RS(seq).
    sp=False (decode):        f/g conjugate psum pair, x replicated over model.
    """
    if sp:
        xg = comms.all_gather(x, mi.tp_axes, 1, comms.site("tp", "mlp_in"))
    else:
        xg = comms.copy_fwd_psum_bwd(x, mi.tp_axes, comms.site("tp", "mlp_in"))
    w1 = use(p["w1"], mi, "mlp_w1")
    h = jnp.einsum("bsd,df->bsf", xg, w1)
    h = _act(h, cfg.mlp_kind)
    if cfg.mlp_kind in _GATED:
        h = h * jnp.einsum("bsd,df->bsf", xg, use(p["w3"], mi, "mlp_w3"))
    y = jnp.einsum("bsf,fd->bsd", h.astype(x.dtype), use(p["w2"], mi, "mlp_w2"))
    if sp:
        return comms.reduce_scatter(y, mi.tp_axes, 1, comms.site("tp", "mlp_out"))
    return comms.psum_fwd_copy_bwd(y, mi.tp_axes, comms.site("tp", "mlp_out"))
