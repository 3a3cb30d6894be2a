"""a2a_ms.dist: device time of the all-to-all operations per transform,
averaged over the chips (device trace)."""


def read(run):
    t, w = run.trace, run.window
    if t is None or not w.get("transforms"):
        return None
    seconds = sum(t.op_seconds(lambda name: "all-to-all" in name).values())
    if seconds <= 0:
        return None
    return seconds * 1e3 / w["transforms"]
