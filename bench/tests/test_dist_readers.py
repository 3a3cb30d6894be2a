"""The readers of the four-chip cell, on hand-built traces:

    python -m pytest bench/tests/test_dist_readers.py -q

* the least bytes a chip has to exchange, counted by hand;
* ``all_to_all_ms.dist``, ``ici_share.dist`` and ``hbm_share.dist`` on a
  window of two devices with known all-to-all intervals, under the names
  the TPU's trace gives them;
* nothing when the window holds no all-to-all, and an error for a device
  kind that has no peaks.
"""

from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
from tracing import TraceSummary  # noqa: E402
from yardstick import C64_BYTES, peaks  # noqa: E402
from yardstick_dist import (ici_table, is_all_to_all,  # noqa: E402
                            least_exchange_bytes)

CELL = "accfft-c2c-512.planned"
MS = 1_000_000   # ns
SIGNAL = C64_BYTES * 512 ** 3   # one 512^3 complex64 signal, 1 GiB


def test_least_exchange_bytes_by_hand():
    # a chip holds 256 MiB and sends 3/4 of it: 192 MiB
    assert least_exchange_bytes(SIGNAL, 4) == 201_326_592
    assert least_exchange_bytes(SIGNAL, 1) == 0
    assert least_exchange_bytes(SIGNAL, 2) == SIGNAL / 4


def test_ici_table_sits_beside_each_peaks_table():
    assert ici_table(harness.PEAKS) == os.path.join(BENCH, "peaks_ici.json")
    assert ici_table(harness.REHEARSAL_PEAKS) == os.path.join(
        BENCH, "testdata", "rehearsal_peaks_ici.json")
    assert peaks("TPU v5 lite", ici_table(harness.PEAKS)) == {
        "ici_bytes_per_s": 200e9}
    assert "ici_bytes_per_s" in peaks("cpu",
                                      ici_table(harness.REHEARSAL_PEAKS))


def _summary(ops) -> TraceSummary:
    """A one-second window, ``ops`` per device as (start ms, end ms, name)."""
    return TraceSummary((0, 1000 * MS), {
        d: [(s * MS, e * MS, name) for s, e, name in evs]
        for d, evs in ops.items()}, [])


# the TPU's names: a complex64 exchange runs as two float32 all_to_alls
TWO_DEVICES = {
    0: [(0, 50, "all_to_all f32[1,512,128,512]"),
        (50, 100, "all_to_all f32[1,512,128,512]"),
        (200, 900, "fusion f32[1,128,512,512]")],
    1: [(0, 300, "all_to_all f32[1,128,512,512]"),
        (300, 800, "fusion f32[1,128,512,512]")],
}
# ten transforms, each reading and writing one signal
WINDOW = {"transforms": 10, "least_bytes": 10 * 2 * SIGNAL}


def _run(summary, kind="TPU v5 lite", window=WINDOW):
    return harness.Run(CELL, dict(window), summary, kind, harness.PEAKS)


def test_readers_on_a_known_window():
    run = _run(_summary(TWO_DEVICES))
    # all-to-all: 100 and 300 ms on the two devices, 0.2 s a chip
    assert harness.load_reader("all_to_all_ms.dist")(run) == \
        pytest.approx(20.0)
    sent = 10 * 201_326_592
    assert harness.load_reader("ici_share.dist")(run) == pytest.approx(
        100.0 * sent / 200e9 / 0.2)
    # busy 0.8 s on each device; the cell's four chips share the bytes
    assert run.trace.busy_s == pytest.approx(0.8)
    assert harness.load_reader("hbm_share.dist")(run) == pytest.approx(
        100.0 * 10 * 2 * SIGNAL / (4 * 819e9) / 0.8)


def test_both_spellings_are_all_to_all():
    assert is_all_to_all("all_to_all f32[1,512,128,512]")      # TPU
    assert is_all_to_all("all-to-all")                         # CPU
    assert not is_all_to_all("fft4step:tpu_custom_call f32[65536,16,32]")
    assert not is_all_to_all("all-reduce f32[8]")


def test_no_all_to_all_reads_nothing():
    ops = {d: [(s, e, n) for s, e, n in evs if not is_all_to_all(n)]
           for d, evs in TWO_DEVICES.items()}
    for name in ("all_to_all_ms.dist", "ici_share.dist"):
        assert harness.load_reader(name)(_run(_summary(ops))) is None
    # an all-to-all outside the window does not count
    late = {0: [(1100, 1200, "all_to_all f32[1,512,128,512]"),
                (0, 500, "fusion f32[1,128,512,512]")]}
    assert harness.load_reader("ici_share.dist")(_run(_summary(late))) \
        is None
    for name in ("all_to_all_ms.dist", "ici_share.dist", "hbm_share.dist"):
        assert harness.load_reader(name)(_run(None)) is None


def test_unknown_device_kind_raises():
    run = _run(_summary(TWO_DEVICES), kind="TPU v9 imaginary")
    for name in ("ici_share.dist", "hbm_share.dist"):
        with pytest.raises(KeyError):
            harness.load_reader(name)(run)
