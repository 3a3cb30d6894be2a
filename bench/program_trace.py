"""Reduction of the program's own trace events: its named executables on the
device, its spans on the host, and the host events that bracket each device
program, on one clock.

* Device modules: on a TPU, the ``XLA Modules`` events of each
  ``/device:TPU:<i>`` plane (``jit_<name>(<hash>)``, with a ``run_id``);
  in the CPU rehearsal, the operation events grouped by their
  ``hlo_module`` and ``run_id`` stats.
* Program spans: host events whose name is in ``repro.core.trace.SPANS``,
  with their ``exe`` and ``seq`` stats.
* Host anchors by ``run_id``: the enqueue of a program
  (``DoEnqueueProgram`` on a TPU, ``PjRtCpuExecutable::ExecuteHelper`` on
  the CPU), on the dispatching thread inside its ``fft.dispatch`` span, and
  the completion callback (``CompleteCallbacks``, TPU only).

The device's clock in a TPU trace runs behind the host's.  One offset
``delta`` per window puts each device program after its enqueue and before
its completion callback: ``delta >= enqueue - device start`` and
``delta <= callback - device end`` over every ``run_id``; the largest lower
bound is taken, and the smallest upper bound is kept beside it.  An empty
bracket gives no offset, and the readers that need one give nothing.  The
CPU rehearsal has one clock: ``delta`` is 0.

A transform is a dispatch span, the sync span of the same ``seq`` and
``exe``, and the device module whose enqueue falls inside the dispatch.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")
_SUFFIX = re.compile(
    r"_(\d+(?:x\d+)*)_b(\d+)_(c2c|r2c)_(f32|f64)_(op|ip)_(fwd|inv)$")
ENQUEUE = ("DoEnqueueProgram", "PjRtCpuExecutable::ExecuteHelper")
CALLBACK = "CompleteCallbacks"


def module_name(event_name: str) -> str:
    """A module's executable name: ``jit_<name>(<hash>)`` -> ``<name>``."""
    name = _MODULE.match(event_name).group(1)
    return name[4:] if name.startswith("jit_") else name


def least_bytes_of(name: str):
    """The least bytes one run of executable ``name`` moves
    (``yardstick.least_bytes``, float arrays out of place), from the
    extents, batch and kind its name holds; ``None`` for a name that holds
    none, or names double precision or an in-place transform."""
    from yardstick import least_bytes

    m = _SUFFIX.search(name)
    if m is None or m.group(4) != "f32" or m.group(5) != "op":
        return None
    extents = tuple(int(v) for v in m.group(1).split("x"))
    return least_bytes(extents, int(m.group(2)), m.group(3) == "r2c")


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


class ProgramTrace:
    """All times in ns on the trace's host clock, except ``modules``, on
    the device's."""

    def __init__(self, window, modules, spans, enqueue, callback, tpu):
        self.window = window        # (start, end) of the 'window' span
        self.modules = modules      # [(start, end, name, run_id, device)]
        self.spans = spans          # [(start, end, span, exe, seq)]
        self.enqueue = enqueue      # (run_id, device) -> start
        self.callback = callback    # (run_id, device) -> start
        self.tpu = tpu

    def clock_offset(self):
        """``(delta, upper)`` in ns, or ``None`` for an empty bracket."""
        if not self.tpu:
            return 0, 0
        lower = upper = None
        for s, e, _, run, dev in self.modules:
            if (run, dev) in self.enqueue:
                b = self.enqueue[(run, dev)] - s
                lower = b if lower is None else max(lower, b)
            if (run, dev) in self.callback:
                b = self.callback[(run, dev)] - e
                upper = b if upper is None else min(upper, b)
        if lower is None or upper is None or lower > upper:
            return None
        return lower, upper

    def transforms(self) -> list[tuple]:
        """``(dispatch, sync, module)`` of every transform dispatched inside
        the window, each a tuple as in ``spans`` and ``modules``."""
        lo, hi = self.window
        by_run = {(m[3], m[4]): m for m in self.modules}
        enq = sorted((t, key) for key, t in self.enqueue.items())
        times = [t for t, _ in enq]
        sync = {(sp[3], sp[4]): sp for sp in self.spans if sp[2] == "fft.sync"}
        out = []
        for sp in self.spans:
            if sp[2] != "fft.dispatch" or sp[0] < lo or sp[1] > hi:
                continue
            i, j = bisect.bisect_left(times, sp[0]), bisect.bisect_right(
                times, sp[1])
            runs = [enq[k][1] for k in range(i, j) if enq[k][1] in by_run]
            done = sync.get((sp[3], sp[4]))
            if len(runs) == 1 and done is not None:
                out.append((sp, done, by_run[runs[0]]))
        return out

    def launch_ms(self, upper: bool = False):
        """Mean of (device start + delta) - dispatch start, at the
        bracket's lower end (its upper one with ``upper``)."""
        off, ts = self.clock_offset(), self.transforms()
        if off is None or not ts:
            return None
        delta = off[1] if upper else off[0]
        return sum(m[0] + delta - d[0] for d, _, m in ts) / len(ts) / 1e6

    def sync_ms(self, upper: bool = False):
        """Mean of sync end - (device end + delta), at the bracket's lower
        end (its upper one with ``upper``).  ``launch_ms + sync_ms`` is the
        same at either end."""
        off, ts = self.clock_offset(), self.transforms()
        if off is None or not ts:
            return None
        delta = off[1] if upper else off[0]
        return sum(s[1] - (m[1] + delta) for _, s, m in ts) / len(ts) / 1e6

    def idle_split(self):
        """Seconds of the device's gaps between consecutive transforms,
        split into launch (next dispatch start to its device start), wake-up
        (device end to the end of its sync) and between calls (sync end to
        the next dispatch)."""
        off, ts = self.clock_offset(), self.transforms()
        if off is None or not ts:
            return None
        out = {"launch": 0.0, "wake": 0.0, "between": 0.0}
        for (_, s0, m0), (d1, _, m1) in zip(ts, ts[1:]):
            out["wake"] += (s0[1] - (m0[1] + off[0])) / 1e9
            out["between"] += (d1[0] - s0[1]) / 1e9
            out["launch"] += (m1[0] + off[0] - d1[0]) / 1e9
        return out

    def exe_seconds(self) -> dict[str, float]:
        """Device seconds per executable name of the modules the window's
        transforms ran (one device each)."""
        out: dict[str, float] = defaultdict(float)
        for _, _, m in self.transforms():
            out[m[2]] += (m[1] - m[0]) / 1e9
        return dict(out)

    def exe_runs(self) -> dict[str, int]:
        out: dict[str, int] = defaultdict(int)
        for d, _, _ in self.transforms():
            out[d[3]] += 1
        return dict(out)

    def hbm_share(self, prefix: str, hbm_bytes_per_s: float):
        """Least bytes of the window's transforms run by executables named
        ``<prefix>*``, over the HBM bandwidth, as a share of the device
        seconds inside those executables (%)."""
        seconds = sum(v for k, v in self.exe_seconds().items()
                      if k.startswith(prefix))
        runs = {k: n for k, n in self.exe_runs().items()
                if k.startswith(prefix)}
        sizes = {k: least_bytes_of(k) for k in runs}
        if seconds <= 0 or None in sizes.values():
            return None
        moved = sum(n * sizes[k] for k, n in runs.items())
        return 100.0 * moved / hbm_bytes_per_s / seconds


def summarize(profile, span_names) -> ProgramTrace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`ProgramTrace`;
    ``span_names`` is the program's ``SPANS``."""
    planes = list(profile.planes)
    tpu = any(_TPU_PLANE.match(p.name) for p in planes)
    modules, spans, window = [], [], None
    enqueue, callback = {}, {}
    cpu_ops: dict[tuple, list] = {}
    for plane in planes:
        m = _TPU_PLANE.match(plane.name)
        if m is None and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if m is not None:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        s = int(ev.start_ns)
                        modules.append((s, s + int(ev.duration_ns),
                                        module_name(ev.name),
                                        _stats(ev).get("run_id"),
                                        int(m.group(1))))
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if ev.name == "window":
                    window = (s, e)
                    continue
                if ev.name in span_names:
                    st = _stats(ev)
                    spans.append((s, e, ev.name, st.get("exe"),
                                  st.get("seq")))
                    continue
                if ev.name in ENQUEUE or ev.name == CALLBACK:
                    st = _stats(ev)
                    if "run_id" in st:
                        key = (st["run_id"], int(st.get("device_ordinal", 0)))
                        table = callback if ev.name == CALLBACK else enqueue
                        table.setdefault(key, s)
                    continue
                if not tpu and not ev.name.startswith("end:"):
                    st = _stats(ev)
                    if "hlo_module" in st and "run_id" in st:
                        key = (st["run_id"], int(st.get("device_ordinal", 0)),
                               st["hlo_module"])
                        cpu_ops.setdefault(key, []).append((s, e))
    for (run, dev, name), iv in cpu_ops.items():
        modules.append((min(s for s, _ in iv), max(e for _, e in iv),
                        module_name(name), run, dev))
    if window is None:
        raise ValueError("the trace holds no 'window' span")
    return ProgramTrace(window, sorted(modules), sorted(spans), enqueue,
                        callback, tpu)


def log_lines(t: ProgramTrace) -> list[str]:
    """What a traced window prints about the program: the clock offset and
    its bracket, launch and sync at both ends of the bracket with their sum
    (which the offset does not move), device seconds per executable, and
    the idle gaps split by program span."""
    off = t.clock_offset()
    if off is None:
        lines = ["clock_offset_ms=none (empty bracket)"]
    else:
        lines = [f"clock_offset_ms={off[0] / 1e6!r} "
                 f"bracket_ms=[{off[0] / 1e6!r}, {off[1] / 1e6!r}]"]
        launch = (t.launch_ms(), t.launch_ms(upper=True))
        sync = (t.sync_ms(), t.sync_ms(upper=True))
        if launch[0] is not None:
            lines.append(
                f"launch_ms_range=[{launch[0]!r}, {launch[1]!r}] "
                f"sync_ms_range=[{sync[1]!r}, {sync[0]!r}] "
                f"launch_plus_sync_ms={launch[0] + sync[0]!r}")
    secs = sorted(t.exe_seconds().items(), key=lambda kv: -kv[1])
    lines.append("exe_seconds " + " ".join(f"{k}={v!r}" for k, v in secs))
    split = t.idle_split()
    if split is not None:
        lines.append("idle_split " + " ".join(
            f"{k}_s={v!r}" for k, v in split.items()))
    return lines
