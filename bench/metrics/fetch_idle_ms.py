"""fetch_idle_ms: first-device idle time per window while the host is in
its ``fetch`` span, the copy of the answers to the host."""
from bench import scopes


def read(record, trace):
    return scopes.per_window_ms(record, trace, "gap_s_by_span", ["fetch"])
