"""The benchmark's own counts, against hand counts at a tiny size: model
FLOPs per token for each layer kind, codec bytes, and the table of peaks;
and the FLOPs of minitron-4b's file and of Moonlight-16B-A3B's one-chip
cut at their widths."""

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench.lib import codec_bytes, flops, peaks, spec  # noqa: E402

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


def test_keys_a_query_sees_under_a_window():
    assert flops.keys_per_query(5, 2) == (1 + 2 + 2 + 2 + 2) / 5
    for window in (0, 5, 9):                     # none, or no shorter
        assert flops.keys_per_query(5, window) == (5 + 1) / 2
    c = dict(DENSE, plan=[{"kind": "attn", "n": 1, "window": 2},
                          {"kind": "attn", "n": 1}])
    # each layer 384 + 512 beside attention's 2 * 2 * 2 heads * 4 * keys
    assert flops.forward_per_token(c, 5) == pytest.approx(
        2 * (384 + 512) + 32 * 9 / 5 + 32 * 3 + 160)


MLA = dict(DENSE, kv_lora_rank=4, q_lora_rank=None, qk_nope_head_dim=3,
           qk_rope_head_dim=2, v_head_dim=4, plan=[{"kind": "attn", "n": 1}])


@pytest.mark.parametrize("q_lora,q_proj", [
    (None, 2 * 8 * 2 * 5),                    # d -> 2 heads of 3 + 2
    (6, 2 * 8 * 6 + 2 * 6 * 2 * 5),           # d -> 6 -> 2 heads of 3 + 2
])
def test_latent_attention_flops_by_hand(q_lora, q_proj):
    c = dict(MLA, q_lora_rank=q_lora)
    # kv down 2*8*(4+2) = 96, kv up 2*4*2*(3+4) = 112, out 2*2*4*8 = 128;
    # over (5+1)/2 = 3 keys, scores 2*2*5*3 = 60 and values 2*2*4*3 = 48
    attn = q_proj + 96 + 112 + 128 + 60 + 48
    assert flops.forward_per_token(c, 5) == attn + 512 + 160


def test_expert_layer_flops_by_hand():
    c = dict(DENSE, n_routed_experts=4, num_experts_per_tok=2,
             moe_intermediate_size=6, n_shared_experts=1,
             published={"n_routed_experts": 8},
             plan=[{"kind": "attn", "n": 1}, {"kind": "moe", "n": 2}])
    # router 2*8*8 = 128 over all 8; an expert 3 * 2*8*6 = 288, of which
    # 2 a token, held here 4 of 8: 2 * 4/8 * 288; one shared 288
    moe = 384 + 96 + 128 + 2 * 4 / 8 * 288 + 288
    assert flops.forward_per_token(c, 5) == (384 + 96 + 512) + 2 * moe + 160
    # with no published count the router is as wide as the experts held
    del c["published"], c["n_shared_experts"]
    moe = 384 + 96 + 2 * 8 * 4 + 2 * 288
    assert flops.forward_per_token(c, 5) == (384 + 96 + 512) + 2 * moe + 160


def test_unknown_layer_kind_raises():
    c = dict(DENSE, name="tiny", plan=[{"kind": "mlstm", "n": 1}])
    with pytest.raises(ValueError, match="'tiny'.*'mlstm'"):
        flops.forward_per_token(c, 5)


def test_flops_of_minitron_4b_file():
    c = spec.config("minitron-4b")
    assert flops.forward_per_token(c, 2048) == 901_275_648
    assert flops.train_per_token(c, 2048) == 2_703_826_944


def test_flops_of_moonlight_one_chip_cut():
    """Moonlight-16B-A3B's published widths, cut as eight chips share each
    layer: the dense layer and 5 expert layers, 8 of 64 experts held,
    20,480 vocabulary rows, at its context of 8192."""
    c = json.loads((HERE / "data" / "moonlight-16b-a3b.json").read_text())
    keys = (8192 + 1) / 2
    mla_proj = (2 * 2048 * 16 * 192 + 2 * 2048 * (512 + 64)
                + 2 * 512 * 16 * (128 + 128) + 2 * 16 * 128 * 2048)
    mla_core = 2 * 16 * 192 * keys + 2 * 16 * 128 * keys
    dense = 3 * 2 * 2048 * 11264
    moe = 2 * 2048 * 64 + (6 * 8 / 64 + 2) * 3 * 2 * 2048 * 1408
    head = 2 * 2048 * 20480
    assert (mla_proj, mla_core, dense, moe, head) == (
        27_525_120, 41_948_160, 138_412_032, 47_841_280, 83_886_080)
    fwd = 6 * (mla_proj + mla_core) + dense + 5 * moe + head
    assert flops.forward_per_token(c, 8192) == fwd == 878_344_192
    assert flops.train_per_token(c, 8192) == 2_635_032_576


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
