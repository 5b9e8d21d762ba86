"""window_roofline_pct: the least time of the traced windows' ops (from
their semantics, ``bench/roofline.py``) over the device busy time they
took."""


def read(record, trace):
    if not trace or trace["busy_s"] <= 0 or not record["traced_windows"]:
        return None
    return 100.0 * record["traced_least_s"] / trace["busy_s"]
