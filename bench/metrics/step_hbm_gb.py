"""step_hbm_gb (GB): device memory of the compiled step per device, from
its memory analysis: arguments + outputs - aliased + temporaries."""


def read(f):
    return f["step_bytes"] / 1e9
