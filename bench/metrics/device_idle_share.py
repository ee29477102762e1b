"""device_idle_share (%): 1 - device busy time / traced window, where busy
is the union of the intervals in which an operation runs on a device,
averaged over the cell's devices (profiler trace)."""


def read(f):
    t = f["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
