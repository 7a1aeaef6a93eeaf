"""Serving tier: milliseconds of ``TripleStore.publish_ms`` (each epoch's
snapshot build with its host mirror) per update published in the window."""


def read(run):
    done = run.completed
    if not done:
        return None
    return (done[-1].counters["publish_ms"] - run.before["publish_ms"]) / len(done)
