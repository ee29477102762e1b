"""Plain float32 reference of minitron-4b as the benchmark runs it.

A decoder of identical layers: token embedding, then per layer RMSNorm ->
grouped-query causal attention with rotary positions -> residual,
RMSNorm -> squared-ReLU MLP -> residual; a final RMSNorm and an untied
output head; mean token cross-entropy over the vocabulary rows held.

Departures from the published model (Minitron, arXiv:2407.14679), which
the program makes and this reference follows: RMSNorm with a zero-centred
gain in place of LayerNorm1p, rotary embedding over the whole head, no
biases.  The configuration file lists them under ``assumed``.

Weights are laid out as the program keeps them (its parameter tree, by
path): the layers' weights are stacked on a leading layer axis.
"""

from __future__ import annotations

import jax

from bench.lib import nn


def _rows(c: dict) -> int:
    return -(-c["vocab_size"] // 128) * 128     # vocabulary padded to 128


def layout(c: dict) -> list:
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    ff, n = c["intermediate_size"], c["num_hidden_layers"]
    bf, f32 = "bfloat16", "float32"
    out = [("embed/table", (_rows(c), d), bf, "normal", 0.02),
           ("final_norm/g", (d,), f32, "zeros", 0.0),
           ("lm_head/w", (d, _rows(c)), bf, "normal", 0.02)]
    g = "groups/0/"
    out += [(g + "attn/wq", (n, d, h * hd), bf, "normal", 0.02),
            (g + "attn/wk", (n, d, kv * hd), bf, "normal", 0.02),
            (g + "attn/wv", (n, d, kv * hd), bf, "normal", 0.02),
            (g + "attn/wo", (n, h * hd, d), bf, "normal", 0.02),
            (g + "ln1/g", (n, d), f32, "zeros", 0.0),
            (g + "ln2/g", (n, d), f32, "zeros", 0.0),
            (g + "mlp/w1", (n, d, ff), bf, "normal", 0.02),
            (g + "mlp/w2", (n, ff, d), bf, "normal", 0.02)]
    return out


def row_loss(w: dict, tokens, labels, c: dict, prec: str):
    """Sum of the token losses of one sequence."""
    x = nn.store(w["embed/table"][tokens], prec)
    g = "groups/0/"
    block = jax.checkpoint(lambda x, lw: nn.attn_block(x, lw, c, prec))
    for i in range(c["num_hidden_layers"]):
        lw = {"ln1": w[g + "ln1/g"][i], "ln2": w[g + "ln2/g"][i],
              "attn": {k: w[g + "attn/" + k][i]
                       for k in ("wq", "wk", "wv", "wo")},
              "mlp": {k: w[g + "mlp/" + k][i] for k in ("w1", "w2")}}
        x = block(x, lw)
    x = nn.store(nn.rms_norm(x, w["final_norm/g"], c["norm_eps"]), prec)
    return nn.token_xent_sum(x, w["lm_head/w"], labels, c["vocab_size"],
                             prec)
