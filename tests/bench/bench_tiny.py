"""Tiny versions of the benchmark's configurations, for CPU tests: the
same program paths and references at widths a test run can hold."""

MINITRON = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "num_hidden_layers": 2,
    "vocab_size": 512, "plan": [{"kind": "attn", "n": 2}],
    "program": {"arch": "minitron-4b",
                "cut": {"n_layers": 2, "vocab_size": 512, "d_model": 64,
                        "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
                        "d_ff": 128, "groups": []}}}

ZAMBA2 = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 16, "intermediate_size": 128, "num_hidden_layers": 4,
    "vocab_size": 512, "mamba_d_state": 8, "mamba_headdim": 8,
    "plan": [{"kind": "mamba", "n": 2}, {"kind": "attn", "n": 1},
             {"kind": "mamba", "n": 2}, {"kind": "attn", "n": 1}],
    "program": {"arch": "zamba2-1.2b",
                "cut": {"n_layers": 4, "vocab_size": 512, "d_model": 64,
                        "n_heads": 4, "n_kv_heads": 4, "head_dim": 16,
                        "d_ff": 128, "ssm_state": 8, "ssm_head_dim": 8,
                        "groups": [{"kind": "mamba", "n": 2},
                                   {"kind": "shared_attn", "n": 1},
                                   {"kind": "mamba", "n": 2},
                                   {"kind": "shared_attn", "n": 1}]}}}

# cell of BENCHMARK.json -> overrides of its configuration and traffic
CELLS = {
    "minitron4b.1chip.opt8": {"c": MINITRON,
                              "traffic": {"seq": 64, "global_batch": 4}},
}
