"""all_to_all_ms.dist: device time of the all-to-all operations per
transform, averaged over the chips (device trace).

The operations are those ``yardstick_dist.is_all_to_all`` names: on the
TPU ``all_to_all``, which ``a2a_ms.dist``'s match (``all-to-all``, the CPU
rehearsal's spelling) does not find."""

from yardstick_dist import is_all_to_all


def read(run):
    t, w = run.trace, run.window
    if t is None or not w.get("transforms"):
        return None
    seconds = sum(t.op_seconds(is_all_to_all).values())
    if seconds <= 0:
        return None
    return seconds * 1e3 / w["transforms"]
