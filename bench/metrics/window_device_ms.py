"""window_device_ms: device busy time per dispatched window, averaged
over the cell's chips."""


def read(record, trace):
    if not trace or trace["busy_s"] <= 0 or not record["traced_windows"]:
        return None
    return 1e3 * trace["busy_s"] / record["traced_windows"]
