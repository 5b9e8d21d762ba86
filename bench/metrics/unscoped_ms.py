"""unscoped_ms: device time per window of the ops under no phase of the
window and no ``bench.*`` scope: window glue and ops that XLA adds
without metadata.  Nothing to read in a program without the window's
``kv.*`` scopes."""
from bench import scopes


def read(record, trace):
    if not any(n.startswith("kv.") for n in (trace or {}).get("scope_s", {})):
        return None
    return scopes.per_window_ms(record, trace, "scope_s", [scopes.UNSCOPED])
