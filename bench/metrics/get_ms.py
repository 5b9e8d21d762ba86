"""get_ms: device time per window of the read path (``kv.get``,
``KVStore._get_window``)."""
from bench import scopes


def read(record, trace):
    return scopes.per_window_ms(record, trace, "scope_s", ["kv.get"])
