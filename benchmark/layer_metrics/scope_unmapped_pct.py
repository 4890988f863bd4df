"""scope_unmapped_pct (compiled steps): of the whole steps' self time, the
share on events that the program's scope map does not hold, or holds with
no scope after inheritance from their callers: the map's own health.  The
``scopes`` line names the five largest."""

import scope_times


def read(view):
    found = scope_times.read(view)
    if found is None or not found["total_ms"]:
        return None
    return 100.0 * found["unmapped_ms"] / found["total_ms"]
