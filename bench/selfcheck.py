#!/usr/bin/env python3
"""Self-check of the benchmark's own arithmetic, on the CPU:

1. the least-bytes count, against sums done by hand for one C2C and one
   R2C problem;
2. the interval arithmetic of the trace reduction, on hand-made
   intervals;
3. the reduction of a recorded trace of a TPU v5e window
   (``testdata/v5e_window.xplane.pb``: three passes of forward and inverse
   over three small planned problems), against a plain recount of the
   same events made here without the reduction's code.

    python3 bench/selfcheck.py      # exits nonzero on the first mismatch
"""

import os
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "testdata", "v5e_window.xplane.pb")


def check(cond, what):
    if not cond:
        raise SystemExit(f"selfcheck FAIL: {what}")
    print(f"selfcheck ok: {what}")


def least_bytes_by_hand():
    from yardstick import least_bytes

    # 4096 x 16384 C2C: 2^26 complex64 read + 2^26 complex64 written
    check(least_bytes((4096,), 16384, real=False) == 2 * 2**26 * 8,
          "least bytes of 4096 x 16384 C2C = 1073741824")
    # 1048576 x 64 R2C: 2^26 float32 read, 64 * 524289 complex64 written
    check(least_bytes((1048576,), 64, real=True)
          == 2**26 * 4 + 64 * 524289 * 8,
          "least bytes of 1048576 x 64 R2C = 536871424")
    # 361 x 361 x 384 R2C: 361*361*384 float32, 361*181*384 complex64
    check(least_bytes((361, 361), 384, real=True)
          == 361 * 361 * 384 * 4 + 361 * 181 * 384 * 8,
          "least bytes of 361x361 x 384 R2C")


def intervals_by_hand():
    from tracing import TraceSummary, gaps, union

    check(union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)],
          "union merges touching and overlapping intervals")
    check(gaps([(0, 3), (5, 9)], 0, 12) == [(3, 5), (9, 12)],
          "gaps are the window minus the busy union")
    s = TraceSummary((0, 100),
                     {0: [(10, 30, "a"), (20, 40, "b"), (90, 120, "a")],
                      1: [(0, 50, "c")]},
                     [(40, 60, "submit"), (60, 80, "result_wait")])
    check(s.busy[0] == [(10, 40), (90, 100)] and abs(s.busy_s - 45e-9) < 1e-18,
          "busy is clipped to the window and averaged over the devices")
    check(abs(s.op_seconds()["a"] - 15e-9) < 1e-18,
          "op seconds clip to the window and average over devices")
    idle = s.idle_by_span()
    check(abs(idle["submit"] - 20e-9) < 1e-18
          and abs(idle["result_wait"] - 20e-9) < 1e-18
          and abs(idle["unattributed"] - 20e-9) < 1e-18,
          "idle gaps split among the spans that overlap them")


def recorded_trace():
    from jax.profiler import ProfileData

    from tracing import op_kind, summarize

    profile = ProfileData.from_file(TRACE)
    spans = {"execute_forward", "execute_inverse"}
    s = summarize(profile, spans)
    # the plain recount: every XLA Ops event of TPU 0 inside the window
    lo, hi = s.window
    events = []
    for plane in profile.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                events += [(int(e.start_ns), int(e.start_ns)
                            + int(e.duration_ns), e.name)
                           for e in line.events]
    check(len(events) > 0 and s.devices == [0],
          f"one TPU with {len(events)} operations in the recorded trace")
    skew = 2_000_000   # ns; see tracing.py on the device's clock
    check(all(lo - skew <= a and b <= hi + skew for a, b, _ in events),
          "every device operation lies within 2 ms of the window span")
    inside = [(max(a, lo), min(b, hi), op_kind(n)) for a, b, n in events
              if b > lo and a < hi]
    # busy time by a sweep over sorted start/end points
    points = sorted([(a, 1) for a, _, _ in inside]
                    + [(b, -1) for _, b, _ in inside])
    depth, busy, since = 0, 0, None
    for t, d in points:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    check(abs(s.busy_s - busy / 1e9) < 1e-12,
          f"busy {s.busy_s!r} s equals the sweep's {busy / 1e9!r} s")
    per_op = defaultdict(int)
    for a, b, n in inside:
        per_op[n] += b - a
    ops = s.op_seconds()
    check(set(ops) == set(per_op) and all(
        abs(ops[n] - per_op[n] / 1e9) < 1e-12 for n in per_op),
        f"per-operation seconds of {len(per_op)} names match the recount")
    idle = s.idle_by_span()
    check(abs(sum(idle.values()) + s.busy_s - s.window_s) < 1e-9,
          "idle gaps and busy time add up to the window")
    n_spans = sum(1 for _, _, n in s.spans if n in spans)
    check(n_spans == 18, f"18 benchmark spans (3 passes x 3 problems x "
          f"2 directions), found {n_spans}")


def main() -> int:
    sys.path.insert(0, HERE)
    least_bytes_by_hand()
    intervals_by_hand()
    recorded_trace()
    print("selfcheck: all ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
