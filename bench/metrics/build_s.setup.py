"""build_s.setup: seconds the program spent inside its ``fft.build`` spans
(an executable built, or loaded from the compile caches), from the
program's own span table (``repro.core.trace.counters``).

Read after the run: every ``fft.build`` span falls in set-up, since the
window and the check reuse the executables set-up built.  A program
without the table gives nothing."""

import sys


def read(run):
    trace = sys.modules.get("repro.core.trace")
    counters = getattr(trace, "counters", None)
    if counters is None:
        return None
    count, seconds = counters().get("fft.build", (0, 0.0))
    return seconds if count else None
