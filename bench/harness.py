"""One benchmark cell, one run: set-up, the measured window, the check.

A cell's name is ``<config>.<traffic>``.  Everything that belongs to one
configuration, one mix or one metric sits in a file of its own, found by
name:

* ``configs/<config>.json`` — the deployment: source, driver, client and
  service settings, chips, limits of the check, ``reduced``, ``assumed``;
* ``traffic/<traffic>.json`` — the mix: problems, or arrivals and shapes;
* ``drivers/<driver>.py`` — one per path kind; a ``Cell`` class with
  ``window(seconds, span)``, ``check()`` and ``close()``;
* ``metrics/<metric>.py`` — one reader per per-layer metric, a
  ``read(run)`` that returns a number or ``None`` when it finds nothing.

Which metrics a cell reports comes from ``BENCHMARK.json`` at the root of
the checkout: with ``--trace 0`` its end-to-end metrics (taken from the
driver's window), with ``--trace 1`` its per-layer ones (from the readers,
over the profiler trace of the window and the driver's counts).

The run fails, printing no result, unless JAX's first device is of the
expected platform (``tpu``; the CPU rehearsal expects ``cpu``) and there
are as many devices as the cell's ``chips``.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
PEAKS = os.path.join(BENCH, "peaks.json")
#: A nominal table for the CPU rehearsal, so that its readers run; no
#: number computed from it is a measurement of anything.
REHEARSAL_PEAKS = os.path.join(BENCH, "testdata", "rehearsal_peaks.json")


class Refused(Exception):
    """The run cannot measure here; exit nonzero with no result."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def overlay(base: dict, rehearsal: bool) -> dict:
    """A config or mix as run: its ``rehearsal`` entries replace the real
    sizes in the CPU rehearsal."""
    out = {k: v for k, v in base.items() if k != "rehearsal"}
    if rehearsal:
        out.update(base.get("rehearsal", {}))
    return out


def load_cell(name: str, rehearsal: bool = False) -> tuple[dict, dict]:
    config_name, sep, traffic_name = name.partition(".")
    if not sep:
        raise Refused(f"workload {name!r} is not <config>.<traffic>")
    config = load_json(BENCH, "configs", config_name + ".json")
    traffic = load_json(BENCH, "traffic", traffic_name + ".json")
    return overlay(config, rehearsal), overlay(traffic, rehearsal)


def applies(entry: dict, cell: str) -> bool:
    return cell in entry.get("workloads", [cell])


def load_reader(metric: str):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class Run:
    """What a per-layer reader sees: the cell, the driver's window, the
    reduced trace (``None`` without one) and the chip's peaks."""

    def __init__(self, cell, window, trace, device_kind, peaks_path):
        self.cell, self.window, self.trace = cell, window, trace
        self.device_kind, self.peaks_path = device_kind, peaks_path

    def peaks(self) -> dict:
        from yardstick import peaks

        return peaks(self.device_kind, self.peaks_path)


def import_program():
    """The system under test, from this checkout's ``src``."""
    sys.path.insert(0, SRC)
    try:
        from repro.core import device
    except ImportError as e:
        raise Refused(f"the repro package is not in {SRC} ({e})")
    if not os.path.abspath(device.__file__).startswith(SRC + os.sep):
        raise Refused(f"repro imported from {device.__file__}, not {SRC}")
    return device


class CompileCounter:
    """Counts compilations and persistent-cache loads while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, *args, **kwargs):
        if self.active and event in self.counts:
            self.counts[event] += 1

    def line(self) -> str:
        c = self.counts
        return (f"window_compiles={c[self.EVENTS[0]]} "
                f"window_cache_loads={c[self.EVENTS[1]]} "
                f"window_traces={c[self.EVENTS[2]]}")


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def open_devices(platform: str, chips: int) -> list:
    """Import the program, turn on the persistent compile cache at its fixed
    path, and return JAX's devices; refuses a wrong platform or too few
    chips."""
    device = import_program()
    device.setup_compile_cache()
    import jax

    # every program a cell builds goes to the persistent cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no usable device ({e})")
    if devices[0].platform != platform:
        raise Refused(f"needs platform {platform!r}, JAX found "
                      f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise Refused(f"needs {chips} chips, JAX sees {len(devices)}")
    return devices


def main(argv=None, t_start=None, platform: str = "tpu",
         rehearsal: bool = False) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    p = argparse.ArgumentParser(description="one benchmark cell, one run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    tag = [f"[{platform}]"]

    def log(msg: str) -> None:
        print(f"bench {tag[0]} +{time.perf_counter() - t_start:.1f}s {msg}",
              file=sys.stderr, flush=True)

    try:
        bench = load_json(ROOT, "BENCHMARK.json")
        config, traffic = load_cell(args.workload, rehearsal)
        devices = open_devices(platform, int(config["chips"]))
    except Refused as e:
        log(f"refused: {e}")
        return 2
    import jax

    chips = int(config["chips"])
    kind = devices[0].device_kind
    dev = {"platform": devices[0].platform, "kind": kind,
           "count": len(devices)}
    tag[0] = f"[{dev['platform']} {kind} x{len(devices)}]"
    log(f"device platform={dev['platform']} kind={kind} "
        f"count={len(devices)} jax={jax.__version__} cell={args.workload} "
        f"seed={args.seed} seconds={args.seconds} trace={args.trace}"
        + (" REHEARSAL: no number here is a device measurement"
           if rehearsal else ""))

    sys.path.insert(0, BENCH)
    from tracing import reduce_trace, Spans

    driver = importlib.import_module(f"drivers.{config['driver']}")
    counter = CompileCounter()
    cell = driver.Cell(config, traffic, args.seed, devices, log)
    try:
        spans = Spans(args.trace)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") \
            if args.trace else None
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        setup_s = time.perf_counter() - t_start
        counter.active = True
        with spans("window"):
            window = cell.window(args.seconds, spans)
        counter.active = False
        summary = None
        if trace_dir:
            jax.profiler.stop_trace()
            summary = reduce_trace(trace_dir, chips, spans.names)
            shutil.rmtree(trace_dir, ignore_errors=True)
        log(counter.line())
        dev["memory_peak_bytes"] = memory_peak(devices[:chips])
        log(f"memory peak_bytes_in_use={dev['memory_peak_bytes']} "
            f"window_s={window['window_s']!r} "
            f"attempted={window['attempted']} failed={window['failed']}")
        checks = cell.check()
        log("check done")
    finally:
        cell.close()

    metrics = {}
    if args.trace:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        run = Run(args.workload, window, summary, kind,
                  REHEARSAL_PEAKS if rehearsal else PEAKS)
        for m in bench["per_layer"]:
            if not applies(m, args.workload):
                continue
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        for m in bench["end_to_end"]:
            if applies(m, args.workload) and m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    correct = all(v <= limit for _, v, limit in checks)
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics, "device": dev}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {name: {"value": v, "limit": limit}
                        for name, v, limit in checks}
    for name, v, limit in checks:
        log(f"check {name}={v!r} limit={limit!r} "
            f"{'ok' if v <= limit else 'FAIL'}")
    print(json.dumps(finite(result)), flush=True)
    return 0


def finite(obj):
    """``obj`` with every infinite or NaN float as ``null``, so that the
    result line stays strict JSON (a latency of a request that never came
    is infinite; such a run is not correct anyway)."""
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj
