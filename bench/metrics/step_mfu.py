"""step_mfu (%): model FLOPs per token (forward + backward, no
recomputation, counted from the configuration's sizes) x the traced
window's tokens per second / (chips x the device's bf16 peak)."""


def read(f):
    t = f["trace"]
    if t["window_s"] <= 0 or not f["steps"]:
        return None
    tok_s = f["steps"] * f["tokens_per_step"] / t["window_s"]
    return 100.0 * f["flops_per_token"] * tok_s \
        / (f["chips"] * f["peak"]["bf16_flops"])
