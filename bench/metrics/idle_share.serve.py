"""idle_share.serve: the share of the traced window of the service cell in
which no operation ran on the device (device trace)."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
