"""Model FLOPs per token, forward and backward, with no recomputation.

Counted from the configuration file's sizes (not from the program): every
matrix product of the forward pass at 2 operations per multiply-add,
causal attention over the average (S + 1) / 2 keys a token attends to,
and the backward pass at twice the forward.  Rematerialised forward work
is not counted, nor are elementwise operations, norms and softmax.
"""

from __future__ import annotations


def _attn_fwd(c: dict, seq: int) -> float:
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    proj = 2 * d * h * hd + 2 * 2 * d * kv * hd + 2 * h * hd * d
    core = 2 * 2 * h * hd * (seq + 1) / 2
    mats = 3 if c["hidden_act"] in ("swiglu", "geglu") else 2
    mlp = mats * 2 * d * c["intermediate_size"]
    return proj + core + mlp


def _mamba_fwd(c: dict) -> float:
    d = c["hidden_size"]
    di = c["mamba_expand"] * d
    p, n, k = c["mamba_headdim"], c["mamba_d_state"], c["mamba_d_conv"]
    heads = di // p
    proj = 2 * d * di * 2 + 2 * d * 2 * n + 2 * d * heads + 2 * di * d
    conv = 2 * k * di
    # the recurrence: state update (outer product u B^T) and read-out (S C)
    scan = 2 * 2 * heads * p * n
    return proj + conv + scan


def forward_per_token(c: dict, seq: int) -> float:
    total = 2 * c["hidden_size"] * c["vocab_size"]          # output head
    for g in c["plan"]:
        one = _attn_fwd(c, seq) if g["kind"] == "attn" else _mamba_fwd(c)
        total += g["n"] * one
    return total


def train_per_token(c: dict, seq: int) -> float:
    """Forward + backward (twice the forward), no recomputation."""
    return 3 * forward_per_token(c, seq)
