"""verbs_ms: device time per window of the ops under any verb that
moves data between participants (``verb.*``: ``SharedRegion`` reads and
writes, the ``colls`` helpers), each op counted once."""
from bench import scopes


def read(record, trace):
    return scopes.per_window_ms(record, trace, "scope_s", [scopes.VERBS])
