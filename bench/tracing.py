"""Reduction of a profiler trace of the window to the numbers the per-layer
readers take.

* Device operations: on a TPU, the events of the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane; in the CPU rehearsal, the host events that
  carry an ``hlo_op`` stat, by their ``device_ordinal``.  Each is named by
  :func:`op_kind`.
* The device's clock in the trace runs 1.3-1.7 ms behind the host's (the
  recorded trace under ``testdata/``: each device program starts that much
  before the host span that dispatched it).  Nothing corrects it: over a
  window of seconds it moves the busy share by well under 0.1 %, and it
  blurs only the attribution of gaps shorter than that.
* The window: the benchmark's own ``window`` span on the host.
* Busy time of a device: the union of its operations' intervals inside the
  window.  Idle time is the rest of the window; each idle gap of the first
  device is split among the benchmark spans (``execute_forward``,
  ``submit``, ...) that overlap it, the rest ``unattributed``.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
from collections import defaultdict

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r"^%?([\w.\-]+?)(?:\.\d+)? = \(?([a-z0-9]+\[[\d,]*\])?")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_kind(name: str) -> str:
    """A device event's operation without its number, and its (first)
    result shape: ``fft4step:tpu_custom_call f32[16384,64,64]``,
    ``fusion c64[4,4096,4096]``.  TPU events carry the whole HLO
    instruction as their name; the rehearsal's carry the bare name."""
    m = _OP_NAME.match(name)
    if m is None:
        return re.sub(r"\.\d+$", "", name)
    t = _TARGET.search(name)
    kind = f"{m.group(1)}:{t.group(1)}" if t else m.group(1)
    return f"{kind} {m.group(2)}" if m.group(2) else kind


class Spans:
    """The benchmark's host spans: ``TraceAnnotation`` while tracing, and
    nothing at all otherwise."""

    def __init__(self, on: bool):
        self.on = bool(on)
        self.names: set[str] = set()

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        self.names.add(name)
        return jax.profiler.TraceAnnotation(name)


def union(intervals) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """The parts of ``[lo, hi)`` that ``busy`` (disjoint, sorted) leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


class TraceSummary:
    """Per-device operations and host spans of one traced window (all
    times in ns on the trace's clock, reported in seconds)."""

    def __init__(self, window: tuple[int, int], ops: dict, spans: list):
        self.window = window
        self.ops = ops            # device index -> [(start, end, name)]
        self.spans = spans        # [(start, end, name)] benchmark spans
        lo, hi = window
        self.busy = {d: union((max(s, lo), min(e, hi)) for s, e, _ in evs
                              if e > lo and s < hi)
                     for d, evs in ops.items()}

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def devices(self) -> list[int]:
        return sorted(d for d, b in self.busy.items() if b)

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices that ran anything."""
        ds = self.devices
        if not ds:
            return 0.0
        return sum(e - s for d in ds for s, e in self.busy[d]) / len(ds) / 1e9

    def op_seconds(self, match=lambda name: True) -> dict[str, float]:
        """Seconds per operation name inside the window, averaged over the
        devices that ran anything; ``match`` selects names."""
        lo, hi = self.window
        out: dict[str, float] = defaultdict(float)
        ds = self.devices
        for d in ds:
            for s, e, name in self.ops[d]:
                if e > lo and s < hi and match(name):
                    out[name] += (min(e, hi) - max(s, lo)) / 1e9 / len(ds)
        return dict(out)

    def idle_by_span(self) -> dict[str, float]:
        """Idle seconds of the first device, split among the benchmark
        spans that overlap each gap, the rest ``unattributed``."""
        ds = self.devices
        if not ds:
            return {}
        out: dict[str, float] = defaultdict(float)
        spans = sorted(self.spans)   # the benchmark's spans do not nest
        first = 0
        for gs, ge in gaps(self.busy[ds[0]], *self.window):
            while first < len(spans) and spans[first][1] <= gs:
                first += 1
            covered = 0
            for k in range(first, len(spans)):
                s, e, n = spans[k]
                if s >= ge:
                    break
                ov = min(e, ge) - max(s, gs)
                if ov > 0:
                    out[n] += ov / 1e9
                    covered += ov
            out["unattributed"] += max(0, ge - gs - covered) / 1e9
        return dict(out)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        idle = sorted(self.idle_by_span().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in ops[:top]],
                "idle_gaps": [[n, s] for n, s in idle[:top]]}


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def summarize(profile, span_names) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`TraceSummary`."""
    ops: dict[int, list] = defaultdict(list)
    spans, window = [], None
    planes = list(profile.planes)
    tpu = any(_TPU_PLANE.match(p.name) for p in planes)
    for plane in planes:
        m = _TPU_PLANE.match(plane.name)
        if m is None and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if m is not None:
                if line.name == "XLA Ops":
                    d = int(m.group(1))
                    ops[d].extend((int(ev.start_ns),
                                   int(ev.start_ns) + int(ev.duration_ns),
                                   op_kind(ev.name)) for ev in line.events)
                continue
            xla_cpu = not tpu and line.name.startswith("tf_XLA")
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if ev.name == "window":
                    window = (s, e)
                elif ev.name in span_names:
                    spans.append((s, e, ev.name))
                elif xla_cpu and not ev.name.startswith("end:"):
                    st = _stats(ev)
                    if "hlo_op" in st:       # XLA:CPU, the rehearsal
                        ops[int(st.get("device_ordinal", 0))].append(
                            (s, e, op_kind(ev.name)))
    if window is None:
        raise ValueError("the trace holds no 'window' span")
    return TraceSummary(window, dict(ops), spans)


def reduce_trace(trace_dir: str, chips: int, span_names) -> TraceSummary:
    """Read the one ``.xplane.pb`` a traced window left under
    ``trace_dir`` and reduce it; only the first ``chips`` devices count."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}: {files}")
    summary = summarize(ProfileData.from_file(files[0]), set(span_names))
    summary.ops = {d: v for d, v in summary.ops.items() if d < chips}
    summary.busy = {d: v for d, v in summary.busy.items() if d < chips}
    return summary
