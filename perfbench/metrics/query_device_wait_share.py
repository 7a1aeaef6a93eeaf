"""Serving tier: the share of the window's lookup time, from admission to
answer, in which lookups waited for their matchers' answers to come back
from the device (``TripleStore.query_stats``: ``device_wait_ms`` over
``wall_ms``), in percent.  A store that does not time its lookups reads
nothing."""


def read(run):
    a, b = run.lookups_after.get("query_stats"), run.before.get("query_stats")
    if not a or "wall_ms" not in a:
        return None
    wall = a["wall_ms"] - b.get("wall_ms", 0.0)
    if wall <= 0:
        return None
    return 100.0 * (a["device_wait_ms"] - b.get("device_wait_ms", 0.0)) / wall
