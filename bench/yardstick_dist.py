"""The benchmark's arithmetic for a cell over several chips: the least
bytes a chip has to exchange, which operations exchange them, and where
the interconnect's peak is kept.

Kept with the benchmark, beside ``yardstick.py``, so that every PR
computes these numbers the same way.
"""

from __future__ import annotations

import os


def least_exchange_bytes(signal_bytes: float, chips: int) -> float:
    """Bytes one chip has to send to the others for one transform of a
    signal of ``signal_bytes`` sharded evenly over ``chips``.

    A chip holds ``signal_bytes / chips``.  Each line along a sharded axis
    has the part of it that lies on the other chips, ``(chips - 1) /
    chips`` of it, so the chip has to send that share of its block at
    least once, whatever the decomposition: ``signal_bytes * (chips - 1) /
    chips**2``.  A decomposition that exchanges twice (pencil) moves more,
    not less."""
    return signal_bytes * (chips - 1) / chips ** 2


def is_all_to_all(op_kind: str) -> bool:
    """Whether a device operation of the reduced trace is an all-to-all:
    ``all_to_all f32[1,512,128,512]`` on the TPU (a complex64 exchange runs
    as two float32 ones), ``all-to-all`` in the CPU rehearsal."""
    return "all_to_all" in op_kind.replace("-", "_")


def ici_table(peaks_path: str) -> str:
    """The interconnect's peaks, kept beside a table of HBM peaks under the
    same name with ``_ici``: ``peaks.json`` -> ``peaks_ici.json``."""
    root, ext = os.path.splitext(peaks_path)
    return root + "_ici" + ext
