"""wire_ms: device time per window of collective operations (the verbs'
wire hop), averaged over the cell's chips.  Nothing to read where the
trace holds no collective, as with all participants on one chip."""


def read(record, trace):
    if not trace or trace["collective_s"] <= 0 \
            or not record["traced_windows"]:
        return None
    return 1e3 * trace["collective_s"] / record["traced_windows"]
