"""The benchmark's own counts, against hand counts at a tiny size: model
FLOPs per token, codec bytes, and the table of peaks."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.lib import codec_bytes, flops, peaks  # noqa: E402

DENSE = {"hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
         "hidden_act": "relu2", "vocab_size": 10,
         "plan": [{"kind": "attn", "n": 3}]}


def test_dense_flops_by_hand():
    seq = 5
    # per layer: q 2*8*8, k and v 2*8*4 each, o 2*8*8 = 384; causal
    # attention 2 * 2 * 2 heads * 4 * (5+1)/2 = 96; relu2 MLP 2 * 2*8*16
    # = 512; three layers and a head of 2*8*10
    fwd = 3 * (384 + 96 + 512) + 160
    assert flops.forward_per_token(DENSE, seq) == fwd
    assert flops.train_per_token(DENSE, seq) == 3 * fwd


def test_hybrid_flops_by_hand():
    c = dict(DENSE, hidden_act="swiglu", mamba_expand=2, mamba_headdim=4,
             mamba_d_state=2, mamba_d_conv=4,
             plan=[{"kind": "mamba", "n": 2}, {"kind": "attn", "n": 1}])
    # mamba: d=8, di=16, 4 heads of 4, state 2: x and z 2*2*8*16 = 512,
    # B and C 2*8*4 = 64, dt 2*8*4 = 64, out 2*16*8 = 256, conv 2*4*16 =
    # 128, scan 4 * 4 heads * 4 * 2 = 128 -> 1152
    mamba = 512 + 64 + 64 + 256 + 128 + 128
    attn = 384 + 2 * 2 * 2 * 4 * 3 + 3 * 2 * 8 * 16      # SwiGLU: 3 mats
    assert flops.forward_per_token(c, 5) == 2 * mamba + attn + 160


def test_codec_bytes_by_hand():
    # bq8: 1 byte a value + 4 bytes per 128; f32 payload of 256 values
    w = 1 + 4 / 128
    assert codec_bytes.wire_per_value(8) == w
    # all-gather over 2 of a 256-value shard: encode it, decode the other
    assert codec_bytes.collective("all_gather", 8, 256, 2, 4) == \
        256 * (4 + w) + 256 * (w + 4)
    # reduce-scatter over 2 of 512 values: encode a 256 chunk, then the
    # last hop reads wire and local and writes the f32 sum
    assert codec_bytes.collective("reduce_scatter", 8, 512, 2, 4) == \
        256 * (4 + w) + 256 * (w + 4 + 4)
    ev = {"op": "reduce_scatter", "n": 2, "elems": 512, "dtype": "float32",
          "codec_fwd": "bq8", "codec_bwd": "none", "bwd_op": "all_gather",
          "mult": 3}
    assert codec_bytes.event(ev) == 3 * (256 * (4 + w) + 256 * (w + 8))
    ev["codec_fwd"] = "none"
    assert codec_bytes.event(ev) == 0.0
    assert codec_bytes.optimizer_state(8, 1024) == 4 * 1024 * (4 + w)
    assert codec_bytes.optimizer_state(32, 1024) == 0.0


def test_peaks_refuse_an_unknown_device():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")
