"""lock_ms: device time per window of the ticket locks: the acquire
(``kv.lock_acquire``) and the deferred release (``kv.release``)."""
from bench import scopes


def read(record, trace):
    return scopes.per_window_ms(record, trace, "scope_s", ["kv.lock_acquire", "kv.release"])
