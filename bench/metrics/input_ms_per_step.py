"""input_ms_per_step (ms): host time in the benchmark's ``bench.input``
spans (making a batch and placing it on the devices) per traced step."""


def read(f):
    if not f["steps"]:
        return None
    return 1e3 * f["trace"]["input_s"] / f["steps"]
