"""codec_roofline (%): the least time the step's codec work needs (its HBM
bytes, counted from shapes by bench/lib/codec_bytes.py, over the device's
peak HBM bandwidth) over the codec kernels' device time.  HBM-bound: the
codecs do a few operations a byte.  Nothing to read where no codec
kernel ran or no codec work was counted."""


def read(f):
    s = f["trace"]["codec_s"]
    b = f["codec_bytes_per_step"]
    if s <= 0 or b <= 0 or not f["steps"]:
        return None
    least = b / f["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / (s / f["steps"])
