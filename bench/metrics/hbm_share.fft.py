"""hbm_share.fft: the least bytes of every transform in the window over the
chip's HBM bandwidth, as a share of the device's busy time (device trace).

The least bytes of a transform are its input and its output array, each
moved once (``yardstick.least_bytes``), whichever path runs it; every
transform of the cells that report this holds arrays far larger than the
chip's on-chip memory, so no path can move less."""


def read(run):
    t, w = run.trace, run.window
    if t is None or t.busy_s <= 0 or not w.get("least_bytes"):
        return None
    return 100.0 * w["least_bytes"] / run.peaks()["hbm_bytes_per_s"] / t.busy_s
