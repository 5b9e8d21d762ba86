"""service_ms: device time per window of the service rounds
(``kv.service``, the ``_serve_rounds`` loop), its tracker waves included."""
from bench import scopes


def read(record, trace):
    return scopes.per_window_ms(record, trace, "scope_s", ["kv.service"])
