"""Milliseconds per update of the closed-loop ingest client, over the
whole window: its length over the updates it held.  Each update published
inside the window counts one; the update in flight when the window closed
counts the share of its own time that fell inside the window, so that
whether the window happens to close early or late in an update, or in an
add or a delete, does not move the number.  Where the stream ran out
before the window closed, the time after its last publication is left out."""


def read(run):
    done = run.completed
    late = [u for u in run.updates if u.status == "done" and u.published > run.window_end]
    if late:
        u = late[0]
        share = (run.window_end - u.submitted) / (u.published - u.submitted)
        return (run.window_end - run.window_start) / (len(done) + share) * 1e3
    if not done:
        return None
    return (done[-1].published - run.window_start) / len(done) * 1e3
