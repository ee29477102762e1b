"""Model FLOPs per token, forward and backward, with no recomputation.

Counted from the configuration file's sizes (not from the program): every
matrix product of the forward pass at 2 operations per multiply-add, and
the backward pass at twice the forward.  Rematerialised forward work is
not counted, nor are elementwise operations, norms and softmax.

Each group of the file's ``plan`` is a stack of ``n`` layers of one kind:

    attn     attention, then the dense feed-forward (``intermediate_size``)
    moe      attention, then a mixture of experts
    mamba    a Mamba2 mixer

Attention is grouped-query (``num_key_value_heads``, ``head_dim``), or
multi-head latent attention where the file has ``kv_lora_rank``.  A causal
query attends to the mean number of keys over its positions: (S + 1) / 2,
or fewer where the group has a ``window``.  The expert layer counts the
router over all experts, the routed experts' share that this chip holds
for its own tokens (``num_experts_per_tok`` x held / routed), with no
exchange, and the shared experts; key names follow the model's
``config.json``, with the router's width in ``published``.
"""

from __future__ import annotations


def keys_per_query(seq: int, window: int = 0) -> float:
    """Mean over positions i < seq of the keys a causal query sees,
    min(i + 1, window), with window 0 for all of them."""
    w = min(window or seq, seq)
    return (w * (w + 1) // 2 + (seq - w) * w) / seq


def _gqa(c: dict, keys: float) -> float:
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    proj = 2 * d * h * hd + 2 * 2 * d * kv * hd + 2 * h * hd * d
    return proj + 2 * 2 * h * hd * keys


def _mla(c: dict, keys: float) -> float:
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    v, lora, qr = c["v_head_dim"], c["kv_lora_rank"], c.get("q_lora_rank")
    q = 2 * d * qr + 2 * qr * h * (nope + rope) if qr \
        else 2 * d * h * (nope + rope)
    proj = q + 2 * d * (lora + rope) + 2 * lora * h * (nope + v) \
        + 2 * h * v * d
    return proj + 2 * h * (nope + rope) * keys + 2 * h * v * keys


def _dense_ffn(c: dict) -> float:
    mats = 3 if c["hidden_act"] in ("swiglu", "geglu") else 2
    return mats * 2 * c["hidden_size"] * c["intermediate_size"]


def _moe_ffn(c: dict) -> float:
    d, ff = c["hidden_size"], c["moe_intermediate_size"]
    held = c["n_routed_experts"]
    routed = c.get("published", {}).get("n_routed_experts", held)
    expert = 3 * 2 * d * ff
    return (2 * d * routed
            + expert * c["num_experts_per_tok"] * held / routed
            + expert * c.get("n_shared_experts", 0))


def _mamba(c: dict) -> float:
    d = c["hidden_size"]
    di = c["mamba_expand"] * d
    p, n, k = c["mamba_headdim"], c["mamba_d_state"], c["mamba_d_conv"]
    heads = di // p
    proj = 2 * d * di * 2 + 2 * d * 2 * n + 2 * d * heads + 2 * di * d
    conv = 2 * k * di
    # the recurrence: state update (outer product u B^T) and read-out (S C)
    scan = 2 * 2 * heads * p * n
    return proj + conv + scan


def _layer(c: dict, g: dict, seq: int) -> float:
    kind = g["kind"]
    if kind == "mamba":
        return _mamba(c)
    if kind not in ("attn", "moe"):
        raise ValueError(f"configuration {c.get('name', '?')!r}: no FLOPs "
                         f"count for plan kind {kind!r}")
    keys = keys_per_query(seq, g.get("window", 0))
    attn = _mla(c, keys) if "kv_lora_rank" in c else _gqa(c, keys)
    return attn + (_dense_ffn(c) if kind == "attn" else _moe_ffn(c))


def forward_per_token(c: dict, seq: int) -> float:
    total = 2 * c["hidden_size"] * c["vocab_size"]          # output head
    for g in c["plan"]:
        total += g["n"] * _layer(c, g, seq)
    return total


def train_per_token(c: dict, seq: int) -> float:
    """Forward + backward (twice the forward), no recomputation."""
    return 3 * forward_per_token(c, seq)
