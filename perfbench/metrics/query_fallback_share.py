"""Query tier: the share of the window's lookups that the batched
executor answered on the host (``fallback``) rather than through a
compiled matcher (``batched``), in percent."""


def read(run):
    a, b = run.lookups_after.get("query_stats"), run.before.get("query_stats")
    if not a:
        return None
    batched = a.get("batched", 0) - b.get("batched", 0)
    fallback = a.get("fallback", 0) - b.get("fallback", 0)
    if batched + fallback == 0:
        return None
    return 100.0 * fallback / (batched + fallback)
