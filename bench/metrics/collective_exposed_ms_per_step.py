"""collective_exposed_ms_per_step (ms): device time of XLA collective
operations (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all, their start/done halves included) during which no other
operation runs on that device, averaged over devices, per traced step.
Nothing to read where the trace holds no collective."""


def read(f):
    t = f["trace"]
    if not f["steps"] or f["chips"] < 2:
        return None
    return 1e3 * t["collective_exposed_s"] / f["steps"]
