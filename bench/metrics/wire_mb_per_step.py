"""wire_mb_per_step (MB): the program's ledger of wire bytes per device per
step, summed over parallelism dimensions (recorded when the step is
compiled).  An exact count; nothing to read on one chip."""


def read(f):
    if f["chips"] < 2:
        return None
    return f["wire_bytes_per_step"] / 1e6
