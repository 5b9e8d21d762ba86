"""schedule_ms: device time per window of the service schedule
(``kv.schedule``, ``KVStore._service_schedule``)."""
from bench import scopes


def read(record, trace):
    return scopes.per_window_ms(record, trace, "scope_s", ["kv.schedule"])
