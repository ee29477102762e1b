"""Plain float32 reference of zamba2-1.2b as the benchmark runs it.

Token embedding; groups of Mamba2 layers, each followed by one shared
attention block (the same weights at every application); a final RMSNorm
and an output head tied to the embedding; mean token cross-entropy.

A Mamba2 layer (pre-norm, residual): x_in = x W_x and the gate z = x W_z;
a depthwise causal convolution of width 4 over x_in, then SiLU; per head
a step dt = softplus(x W_dt + dt_bias) and a decay a = exp(-dt exp(A_log));
B and C (one group) from x W_bc; the recurrence per head

    S_t = a_t S_{t-1} + (dt_t x_t) B_t^T        (P x N state)
    y_t = S_t C_t + D x_t

then RMSNorm(y * silu(z)) and the output projection.  The recurrence is
computed exactly in blocks of 64 steps: inside a block from the closed
form of the sum (decays as differences of a cumulative log), across
blocks by carrying the state.  The shared block is attention and SwiGLU
MLP, each pre-norm with a residual.

Departures from the published model that the program makes, and this
reference follows, are listed under ``assumed`` in the configuration
file.  Weights are laid out as the program keeps them (its parameter
tree, by path).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.lib import nn

F32 = jnp.float32
_BLOCK = 64


def _rows(c: dict) -> int:
    return -(-c["vocab_size"] // 128) * 128


def _mamba_groups(c: dict) -> list:
    """(index in the program's group list, layers) of the Mamba2 groups."""
    out, i = [], 0
    for g in c["plan"]:
        if g["kind"] == "mamba":
            out.append((i, g["n"]))
        i += 1
    return out


def layout(c: dict) -> list:
    d, ff = c["hidden_size"], c["intermediate_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    di = c["mamba_expand"] * d
    n_st, p, k = c["mamba_d_state"], c["mamba_headdim"], c["mamba_d_conv"]
    heads = di // p
    bf, f32 = "bfloat16", "float32"
    out = [("embed/table", (_rows(c), d), bf, "normal", 0.02),
           ("final_norm/g", (d,), f32, "zeros", 0.0)]
    for gi, n in _mamba_groups(c):
        g = f"groups/{gi}/"
        out += [(g + "ln1/g", (n, d), f32, "zeros", 0.0),
                (g + "mamba/A_log", (n, heads), f32, "zeros", 0.0),
                (g + "mamba/D_skip", (n, heads), f32, "ones", 0.0),
                (g + "mamba/conv_b", (n, di), bf, "zeros", 0.0),
                (g + "mamba/conv_w", (n, k, di), bf, "normal", 0.1),
                (g + "mamba/dt_bias", (n, heads), f32, "zeros", 0.0),
                (g + "mamba/gn", (n, di), f32, "zeros", 0.0),
                (g + "mamba/w_bc", (n, d, 2 * n_st), bf, "normal", 0.02),
                (g + "mamba/w_dt", (n, d, heads), bf, "normal", 0.02),
                (g + "mamba/w_out", (n, di, d), bf, "normal", 0.02),
                (g + "mamba/w_x", (n, d, di), bf, "normal", 0.02),
                (g + "mamba/w_z", (n, d, di), bf, "normal", 0.02)]
    s = "shared/"
    out += [(s + "attn/wq", (d, h * hd), bf, "normal", 0.02),
            (s + "attn/wk", (d, kv * hd), bf, "normal", 0.02),
            (s + "attn/wv", (d, kv * hd), bf, "normal", 0.02),
            (s + "attn/wo", (h * hd, d), bf, "normal", 0.02),
            (s + "ln1/g", (d,), f32, "zeros", 0.0),
            (s + "ln2/g", (d,), f32, "zeros", 0.0),
            (s + "mlp/w1", (d, ff), bf, "normal", 0.02),
            (s + "mlp/w2", (ff, d), bf, "normal", 0.02),
            (s + "mlp/w3", (d, ff), bf, "normal", 0.02)]
    return out


def ssm_scan(log_a, u, b, cmat, prec):
    """y_t = sum_{s<=t} exp(sum_{s<r<=t} log a_r) (C_t . B_s) u_s, exactly.

    log_a [L, H], u [L, H, P], b / cmat [L, N] -> y [L, H, P]."""
    n_len, heads, p = u.shape
    q = _BLOCK
    nb = n_len // q
    la = log_a.reshape(nb, q, heads)
    ub = u.reshape(nb, q, heads, p)
    bb = b.reshape(nb, q, -1)
    cb = cmat.reshape(nb, q, -1)
    tri = jnp.tril(jnp.ones((q, q), bool))

    def block(state, xs):
        la_k, u_k, b_k, c_k = xs
        cum = jnp.cumsum(la_k, axis=0)                     # [Q, H]
        diff = cum[:, None, :] - cum[None, :, :]            # [t, s, H]
        dec = jnp.exp(jnp.where(tri[:, :, None], diff, -jnp.inf))
        cbt = nn.mm("tn,sn->ts", c_k, b_k, prec, keep=True)
        y = nn.mm("tsh,shp->thp", dec * cbt[:, :, None], u_k, prec,
                  keep=True)
        y = y + nn.mm("hpn,tn->thp", state, c_k, prec, keep=True) \
            * jnp.exp(cum)[:, :, None]
        tail = jnp.exp(cum[-1][None, :] - cum)               # [s, H]
        state = state * jnp.exp(cum[-1])[:, None, None] + nn.mm(
            "shp,sn->hpn", u_k * tail[:, :, None], b_k, prec, keep=True)
        return state, y

    s0 = jnp.zeros((heads, p, b.shape[-1]), F32)
    _, ys = lax.scan(jax.checkpoint(block), s0, (la, ub, bb, cb))
    return ys.reshape(n_len, heads, p)


def mamba(x, w, c, prec):
    """One Mamba2 mixer on a sequence x [S, D] (input already normed)."""
    n_len = x.shape[0]
    di = c["mamba_expand"] * c["hidden_size"]
    p, n_st, k = c["mamba_headdim"], c["mamba_d_state"], c["mamba_d_conv"]
    heads = di // p
    xi = nn.mm("sd,de->se", x, w["w_x"], prec)
    z = nn.mm("sd,de->se", x, w["w_z"], prec)
    xp = jnp.concatenate([jnp.zeros((k - 1, di), F32), xi], axis=0)
    conv = sum(xp[j:j + n_len] * w["conv_w"][j] for j in range(k))
    xi = nn.store(jax.nn.silu(conv + w["conv_b"]), prec)
    dt = jax.nn.softplus(nn.mm("sd,dh->sh", x, w["w_dt"], prec, keep=True)
                         + w["dt_bias"])
    log_a = -dt * jnp.exp(w["A_log"])
    bc = nn.mm("sd,dn->sn", x, w["w_bc"], prec, keep=True)
    b, cmat = bc[:, :n_st], bc[:, n_st:]
    xh = xi.reshape(n_len, heads, p)
    y = ssm_scan(log_a, dt[:, :, None] * xh, b, cmat, prec)
    y = y + w["D_skip"][None, :, None] * xh
    y = nn.store(y.reshape(n_len, di), prec) * jax.nn.silu(z)
    y = nn.store(nn.rms_norm(y, w["gn"], c["norm_eps"]), prec)
    return nn.mm("se,ed->sd", y, w["w_out"], prec)


def row_loss(w: dict, tokens, labels, c: dict, prec: str):
    """Sum of the token losses of one sequence."""
    x = nn.store(w["embed/table"][tokens], prec)
    eps = c["norm_eps"]
    keys = ("A_log", "D_skip", "conv_b", "conv_w", "dt_bias", "gn", "w_bc",
            "w_dt", "w_out", "w_x", "w_z")
    layer = jax.checkpoint(lambda x, lw: nn.store(x + mamba(
        nn.store(nn.rms_norm(x, lw["ln1"], eps), prec), lw, c, prec), prec))
    shared = {"ln1": w["shared/ln1/g"], "ln2": w["shared/ln2/g"],
              "attn": {k: w["shared/attn/" + k]
                       for k in ("wq", "wk", "wv", "wo")},
              "mlp": {k: w["shared/mlp/" + k] for k in ("w1", "w2", "w3")}}
    attn = jax.checkpoint(lambda x, sw: nn.attn_block(x, sw, c, prec))
    gi = 0
    for g in c["plan"]:
        if g["kind"] == "mamba":
            pre = f"groups/{gi}/"
            for i in range(g["n"]):
                lw = {k: w[pre + "mamba/" + k][i] for k in keys}
                lw["ln1"] = w[pre + "ln1/g"][i]
                x = layer(x, lw)
        else:
            for _ in range(g["n"]):
                x = attn(x, shared)
        gi += 1
    x = nn.store(nn.rms_norm(x, w["final_norm/g"], eps), prec)
    return nn.token_xent_sum(x, w["embed/table"].T, labels,
                             c["vocab_size"], prec)
