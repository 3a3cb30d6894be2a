"""ici_share.dist: the all-to-all's share of its roofline (device trace).

The least time a chip needs for its exchanges: the least bytes it has to
send for every transform in the window
(``yardstick_dist.least_exchange_bytes``) over its interconnect peak
(``peaks_ici.json``); as a share of the seconds a chip spent in the
all-to-all operations in the window (``yardstick_dist.is_all_to_all``, as
``all_to_all_ms.dist`` reads them, averaged over the chips).  A plan that
exchanges more than the least (pencil's second rotation) reads lower."""

from harness import load_cell
from yardstick import peaks
from yardstick_dist import ici_table, is_all_to_all, least_exchange_bytes


def read(run):
    t, w = run.trace, run.window
    if t is None or not w.get("least_bytes"):
        return None
    seconds = sum(t.op_seconds(is_all_to_all).values())
    if seconds <= 0:
        return None
    config, _ = load_cell(run.cell)
    if not all(kind.endswith("Complex") for kind in config["kinds"]):
        raise ValueError(f"{run.cell}: ici_share.dist counts C2C transforms "
                         f"only, not {config['kinds']}")
    # a C2C transform's least bytes are its input and its output: two
    # signals of the same size
    sent = least_exchange_bytes(w["least_bytes"] / 2, int(config["chips"]))
    peak = peaks(run.device_kind, ici_table(run.peaks_path))
    return 100.0 * sent / peak["ici_bytes_per_s"] / seconds
