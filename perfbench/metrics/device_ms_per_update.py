"""Device: milliseconds in which an operation ran on the chip inside each
update that was published in the traced window, per such update, whatever
program ran it."""


def read(run):
    t = run.trace
    if not t or t["device_s_per_update"] is None:
        return None
    return t["device_s_per_update"] * 1e3
