"""Plain float32 layers for the configurations' references.

Straightforward ``jax.numpy``: no kernels, no caches, no sharding.  Every
matrix product goes through :func:`mm`, at ``HIGHEST`` precision (a TPU
otherwise multiplies float32 in one bfloat16 pass).

``prec="fp8"`` is the control, the reference computed one precision below
the configuration's bfloat16: every operand of a matrix product, and
every activation the program keeps in bfloat16 (the residual stream, norm
outputs, projections, attention and MLP outputs), is rounded to float8
(e4m3, one scale per tensor, taken from its largest magnitude), while
products accumulate, and softmax, norms and the loss compute, in float32
as the program does.  The gradient passes each rounding straight through.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
_F8_MAX = 448.0


def fp8_round(x):
    """x rounded to float8 e4m3 under a per-tensor scale; identity gradient."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / _F8_MAX, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + lax.stop_gradient(q - x)


def operand(x, prec: str):
    x = x.astype(F32)
    return fp8_round(x) if prec == "fp8" else x


def store(x, prec: str):
    """An activation as the configuration stores it (float32 here; float8
    in the control)."""
    return fp8_round(x) if prec == "fp8" else x


def mm(eq: str, a, b, prec: str, keep: bool = False):
    """A matrix product; its output is stored as an activation unless
    ``keep`` (the program keeps scores and logits in float32)."""
    out = jnp.einsum(eq, operand(a, prec), operand(b, prec),
                     precision=HIGHEST)
    return out if keep else store(out, prec)


def rms_norm(x, g, eps):
    """RMSNorm with a zero-centred gain: x / rms(x) * (1 + g)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + g)


def rope(x, theta):
    """Rotary embedding on [S, heads, hd], halves rotated as pairs
    (i, i + hd/2), positions 0..S-1."""
    s, _, hd = x.shape
    freqs = theta ** (-jnp.arange(hd // 2, dtype=F32) / (hd // 2))
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs           # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(x, w, c, prec):
    """Grouped-query causal self-attention of one sequence x [S, D]."""
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    s = x.shape[0]
    q = mm("sd,de->se", x, w["wq"], prec).reshape(s, h, hd)
    k = mm("sd,de->se", x, w["wk"], prec).reshape(s, kv, hd)
    v = mm("sd,de->se", x, w["wv"], prec).reshape(s, kv, hd)
    q = store(rope(q, c["rope_theta"]), prec)
    k = store(rope(k, c["rope_theta"]), prec)
    q = q.reshape(s, kv, h // kv, hd)          # query head j*G+g reads kv j
    sc = mm("qkgh,skh->kgqs", q, k, prec, keep=True) * hd ** -0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = mm("kgqs,skh->qkgh", p, v, prec).reshape(s, h * hd)
    return mm("se,ed->sd", o, w["wo"], prec)


def mlp(x, w, c, prec):
    act = c["hidden_act"]
    hid = mm("sd,df->sf", x, w["w1"], prec)
    if act == "relu2":
        hid = jnp.square(jax.nn.relu(hid))
    elif act == "swiglu":
        hid = jax.nn.silu(hid) * mm("sd,df->sf", x, w["w3"], prec)
    else:
        raise ValueError(f"no reference for activation {act!r}")
    return mm("sf,fd->sd", store(hid, prec), w["w2"], prec)


def attn_block(x, w, c, prec):
    """Pre-norm attention and MLP sublayers with residuals."""
    eps = c["norm_eps"]
    h = store(rms_norm(x, w["ln1"], eps), prec)
    x = store(x + causal_attention(h, w["attn"], c, prec), prec)
    h = store(rms_norm(x, w["ln2"], eps), prec)
    return store(x + mlp(h, w["mlp"], c, prec), prec)


def token_xent_sum(x, head, labels, vocab, prec):
    """Sum over the sequence of -log softmax(x @ head)[label]."""
    logits = mm("sd,dv->sv", x, head, prec, keep=True)[:, :vocab]
    lse = jax.nn.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - tl)
