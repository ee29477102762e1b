"""codec_ms_per_step (ms): device time of the bq codec kernels (encode,
decode, fused decode-add-encode and decode-add, all rates) per traced
step, averaged over devices.  Nothing to read where none ran."""


def read(f):
    s = f["trace"]["codec_s"]
    if s <= 0 or not f["steps"]:
        return None
    return 1e3 * s / f["steps"]
