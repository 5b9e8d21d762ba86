"""probe_ms: device time per window of the index probe of the window
(``kv.probe`` in ``KVStore.op_window``), averaged over the cell's chips."""
from bench import scopes


def read(record, trace):
    return scopes.per_window_ms(record, trace, "scope_s", ["kv.probe"])
