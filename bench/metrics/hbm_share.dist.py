"""hbm_share.dist: the least bytes of every transform in the window over
the HBM bandwidth of all the cell's chips, as a share of the device's busy
time averaged over them (device trace): ``hbm_share.fft`` for a cell whose
transforms are spread over several chips, each of which moves its share of
the bytes."""

from harness import load_cell


def read(run):
    t, w = run.trace, run.window
    if t is None or t.busy_s <= 0 or not w.get("least_bytes"):
        return None
    chips = int(load_cell(run.cell)[0]["chips"])
    hbm = chips * run.peaks()["hbm_bytes_per_s"]
    return 100.0 * w["least_bytes"] / hbm / t.busy_s
