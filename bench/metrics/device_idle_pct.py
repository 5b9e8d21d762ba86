"""device_idle_pct: the share of the traced window in which no operation
ran on the device, averaged over the cell's chips."""


def read(record, trace):
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
