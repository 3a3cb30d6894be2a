"""plan_s.setup: seconds the program spent inside its ``fft.plan`` spans
(the planner choosing each problem's candidate), from the program's own
span table (``repro.core.trace.counters``).

Read after the run: every ``fft.plan`` span falls in set-up, since the
window and the check reuse the plans set-up made.  A program without the
table gives nothing."""

import sys


def read(run):
    trace = sys.modules.get("repro.core.trace")
    counters = getattr(trace, "counters", None)
    if counters is None:
        return None
    count, seconds = counters().get("fft.plan", (0, 0.0))
    return seconds if count else None
