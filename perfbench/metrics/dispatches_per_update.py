"""Maintenance: compiled-program calls the engine's ``DispatchCounter``
counted per update published in the window, leaving out the lookups'."""


def read(run):
    done = run.completed
    if not done:
        return None
    last, first = done[-1].counters, run.before
    n = (last["dispatches"] - last["query_dispatches"]) - (
        first["dispatches"] - first["query_dispatches"])
    return n / len(done)
