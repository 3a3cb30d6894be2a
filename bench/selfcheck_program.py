#!/usr/bin/env python3
"""Self-check of the reduction of the program's own trace events
(``program_trace.py``), on the CPU, against plain recounts made here
without the reduction's code:

1. on both recorded TPU v5e windows: the clock offset's bracket is not
   empty, and after the shift every device program lies between its
   enqueue and its completion callback; on the window of unnamed
   executables (``testdata/v5e_window.xplane.pb``) the offset lies in the
   1.3-1.7 ms read off that trace by hand;
2. on the recorded TPU v5e window of named executables
   (``testdata/v5e_named_window.xplane.pb``: one pass of the pow2 cell,
   forward and inverse of 12 problems): no module is ``jit__lambda``,
   every dispatch span in the window pairs with the module of its own
   executable, the device seconds per executable family match a recount of
   the ``XLA Modules`` events and cover at least 99 % of the benchmark's
   ``busy_s`` (``tracing.summarize``), and launch and sync times match a
   recount;
3. the least bytes read from an executable's name equal
   ``yardstick.least_bytes``, and a name of double precision, an in-place
   transform or a distributed plan gives none.

    python3 bench/selfcheck_program.py      # exits nonzero on a mismatch
"""

import os
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
OLD = os.path.join(HERE, "testdata", "v5e_window.xplane.pb")
NAMED = os.path.join(HERE, "testdata", "v5e_named_window.xplane.pb")


def check(cond, what):
    if not cond:
        raise SystemExit(f"selfcheck FAIL: {what}")
    print(f"selfcheck ok: {what}")


def _events(profile):
    """(plane, line, name, start, end, stats) of every event."""
    for plane in profile.planes:
        for line in plane.lines:
            for e in line.events:
                s = int(e.start_ns)
                yield (plane.name, line.name, e.name, s,
                       s + int(e.duration_ns), dict(e.stats))


def clock_offset(path, programs, lo_ms=None, hi_ms=None):
    from jax.profiler import ProfileData

    import program_trace

    profile = ProfileData.from_file(path)
    t = program_trace.summarize(profile, set())
    off = t.clock_offset()
    check(off is not None, "the clock offset's bracket is not empty")
    delta, upper = off
    if lo_ms is not None:
        check(lo_ms * 1e6 <= delta <= upper <= hi_ms * 1e6,
              f"offset {delta / 1e6!r} ms, bracket up to {upper / 1e6!r} "
              f"ms, inside {lo_ms}-{hi_ms} ms")
    mods, enq, done = {}, {}, {}
    for plane, line, name, s, e, st in _events(profile):
        if line == "XLA Modules":
            mods[st["run_id"]] = (s, e)
        elif name == "DoEnqueueProgram":
            enq[st["run_id"]] = s
        elif name == "CompleteCallbacks":
            done[st["run_id"]] = s
    check(len(mods) == programs and set(mods) == set(enq) == set(done),
          f"{programs} device programs, each with an enqueue and a callback")
    check(all(enq[r] <= s + delta and e + delta <= done[r]
              for r, (s, e) in mods.items()),
          f"after a shift of {delta / 1e6!r} ms every program starts after "
          f"its enqueue and ends before its completion callback")


def named_window():
    from jax.profiler import ProfileData

    import program_trace
    from repro.core.trace import SPANS

    profile = ProfileData.from_file(NAMED)
    t = program_trace.summarize(profile, set(SPANS))
    events = list(_events(profile))
    modules = [(s, e, name, st["run_id"]) for _, line, name, s, e, st
               in events if line == "XLA Modules"]
    check(modules and not any(n.startswith("jit__lambda")
                              for _, _, n, _ in modules)
          and all(n.startswith("jit_fft_") for _, _, n, _ in modules),
          f"{len(modules)} modules, every one named jit_fft_..., "
          f"none jit__lambda")
    (lo, hi), = [(s, e) for _, _, n, s, e, _ in events if n == "window"]
    dispatch = sorted((s, e, st["exe"], st["seq"]) for _, _, n, s, e, st
                      in events if n == "fft.dispatch" and lo <= s and e <= hi)
    sync = {(st["exe"], st["seq"]): (s, e) for _, _, n, s, e, st in events
            if n == "fft.sync"}
    enqueue = [(s, st["run_id"]) for _, _, n, s, _, st in events
               if n == "DoEnqueueProgram"]
    by_run = {r: (s, e, n) for s, e, n, r in modules}
    # plain pairing: the one enqueue inside each dispatch span
    pairs, inside = [], []
    for s, e, exe, seq in dispatch:
        runs = [r for ts, r in enqueue if s <= ts <= e]
        inside.append(len(runs))
        if len(runs) == 1:
            pairs.append(((s, e), sync[(exe, seq)], by_run[runs[0]], exe))
    check(inside and set(inside) == {1},
          f"one enqueue inside each of {len(inside)} dispatch spans")
    check(all(m[2].startswith(f"jit_{exe}(") for _, _, m, exe in pairs),
          f"each of {len(pairs)} dispatch spans pairs with its own "
          f"executable's module")
    got = t.transforms()
    check(len(got) == len(pairs) and all(
        d[3] == exe and program_trace.module_name(m[2]) == exe
        for (d, _, m), (_, _, _, exe) in zip(got, pairs)),
        "the reduction pairs the same transforms")
    per_family = defaultdict(float)
    for _, _, (s, e, n), _ in pairs:
        per_family[n.split("_")[2]] += (e - s) / 1e9
    exe = defaultdict(float)
    for k, v in t.exe_seconds().items():
        exe[k.split("_")[1]] += v
    check(set(exe) == set(per_family) and all(
        abs(exe[f] - per_family[f]) < 1e-12 for f in exe),
        "device seconds per family match the recount: " + ", ".join(
            f"{f} {per_family[f]!r} s" for f in sorted(per_family)))
    from tracing import summarize as reduce_benchmark

    busy = reduce_benchmark(profile, {"execute_forward",
                                      "execute_inverse"}).busy_s
    total = sum(t.exe_seconds().values())
    check(total >= 0.99 * busy,
          f"the executables' device seconds are {100 * total / busy!r} % "
          f"of busy_s")
    delta = t.clock_offset()[0]
    launch = sum(m[0] + delta - d[0] for d, _, m, _ in pairs) / len(pairs)
    wake = sum(y[1] - (m[1] + delta) for _, y, m, _ in pairs) / len(pairs)
    check(abs(t.launch_ms() - launch / 1e6) < 1e-9
          and abs(t.sync_ms() - wake / 1e6) < 1e-9,
          f"launch {launch / 1e6!r} ms and sync {wake / 1e6!r} ms match "
          f"the recount")


def bytes_from_names():
    from program_trace import least_bytes_of
    from yardstick import least_bytes

    check(least_bytes_of("fft_xla_xla_1048576_b64_r2c_f32_op_fwd")
          == least_bytes((1048576,), 64, True)
          and least_bytes_of("fft_pallas_nd_a_b__361x361_b384_c2c_f32_op_inv")
          == least_bytes((361, 361), 384, False)
          and least_bytes_of("fft_xla_xla_4096_b16_c2c_f64_op_fwd") is None
          and least_bytes_of("fft_xla_xla_4096_b16_c2c_f32_ip_fwd") is None
          and least_bytes_of("fft_slab4_512x512x512_tr_fwd") is None,
          "least bytes read from executable names")


def main() -> int:
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    bytes_from_names()
    clock_offset(OLD, 18, 1.3, 1.7)
    clock_offset(NAMED, 24)
    named_window()
    print("selfcheck_program: all ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
