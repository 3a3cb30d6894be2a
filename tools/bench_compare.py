"""Per-backend FFT throughput over a fixed extent grid — the PR-over-PR
perf trajectory record.

Times the *forward transform only* (the hot path the tentpole kernels
optimize), via the same ``build_forward`` the planner's MEASURE sweep uses,
and writes one JSON document:

    PYTHONPATH=src python tools/bench_compare.py --out BENCH_PR4.json
    PYTHONPATH=src python tools/bench_compare.py --smoke --out /tmp/b.json

``--smoke`` shrinks the grid/reps to seconds for the CI interpret-mode run.
The grid spans 1D, 2D, and 3D extents (``--extents 4096 64x64 16x16x16``
syntax) so the ND planning work — fused rank-2 kernel vs separable per-axis
application with its swapaxes traffic — shows up in the trajectory, and all
three paper extent classes (powerof2, radix357 rows like 3072, oddshape
rows like 6859 = 19^3) so the mixed-radix kernel and the fused chirp-Z
path are measured against the xla / jnp-bluestein fallbacks they replace.
Throughput is complex-signal GiB/s moved at the *algorithmic minimum* of
one HBM read + one write — so a fused one-pass kernel scores its real
bandwidth while a log-N staged backend is penalized for its extra passes,
which is exactly the trajectory worth recording (paper Fig. 8).

With ``--devices 1 2 4 8`` the tool becomes the scaling driver for the
mesh-parallel backends: one subprocess per device count (a process's XLA
device count is fixed at first jax init, so the axis NEEDS processes) with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``, benching the
distributed decompositions (dist1d / slab / pencil, TRANSPOSED layout)
against the single-device ``xla`` reference over one extent per paper
class, merged into one document whose records carry a ``devices`` field:

    PYTHONPATH=src python tools/bench_compare.py --devices 1 2 4 8 \\
        --out BENCH_PR6.json

Documents carry the schema-2 provenance header (``repro.core.compare``:
schema version, git sha, device kind, jax version, reps) and every grid
row records ``mean_ms``/``sd_ms``/``n`` alongside the min — the spread
columns ``tools/bench_diff.py``'s pooled-noise regression gate consumes —
plus the bytes-based FFT roofline: ``model_flops`` (5·N·log2 N),
``model_bytes`` (the planner's ``estimate_bytes_moved``), and
``roofline_frac``, the achieved fraction of whichever device wall binds.
``--report fig7.md`` renders the gearshifft-style Fig. 7 table (backend ×
extent class × achieved fraction) from the written document.

With ``--serve`` the tool benches the FFT serving layer instead: a seeded
Zipf mixed-shape replay per backend (p50/p95/p99 enqueue→complete latency,
sustained GiB/s, coalesce + plan-cache counters) plus the coalesced-vs-
serial same-shape burst whose ``speedup`` field is the coalescer's
dispatch-amortization win:

    PYTHONPATH=src python tools/bench_compare.py --serve --out BENCH_PR7.json
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import statistics
import subprocess
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.compare import fig7_report, load_bench, make_meta  # noqa: E402

DEFAULT_EXTENTS = ("1024", "4096", "16384", "65536",        # 1D powerof2
                   "3072", "18432",                         # 1D radix357
                   "6859",                                  # 1D oddshape 19^3
                   "64x64", "256x256",                      # 2D (fft2 range)
                   "32x32x32")                              # 3D
SMOKE_EXTENTS = ("256", "1024", "12", "19", "16x16", "8x8x8")

DEFAULT_BACKENDS = ("xla", "stockham", "fourstep", "fourstep_pallas",
                    "stockham_pallas", "sixstep", "fft2_pallas",
                    "chirpz_pallas", "bluestein")

#: One extent per paper class for the --devices scaling grid (all shardable
#: over 8 devices): 1D/3D powerof2, 3D radix357, 1D oddshape
#: (438976 = 2^6 * 19^3 factors as 152 x 2888, both divisible by 8).
SCALING_EXTENTS = ("4096", "64x64x64", "48x48x48", "438976")
SMOKE_SCALING_EXTENTS = ("1024", "8x8x8", "12x12x12", "304")

DIST_BACKENDS = ("dist1d", "slab", "pencil")


def _record_times(rec: dict, times: list[float]) -> float:
    """min/mean/sd/n columns from per-rep wall times (seconds); returns the
    best time.  The sd/n columns are what bench_diff's pooled-noise gate
    reads — a 1-rep smoke run records sd=0, n=1 (no spread information)."""
    best = min(times)
    rec["time_ms"] = best * 1e3
    rec["mean_ms"] = statistics.fmean(times) * 1e3
    rec["sd_ms"] = statistics.stdev(times) * 1e3 if len(times) > 1 else 0.0
    rec["n"] = len(times)
    return best


#: Rows whose roofline had to fall back to the algorithmic-minimum bytes
#: because the cost model judged the (problem, candidate) infeasible —
#: reported after the grid so a model/feasibility drift is visible in the
#: run log instead of silently flattering roofline_frac.
ROOFLINE_FALLBACKS: list[tuple[str, str]] = []


def _annotate_roofline(rec: dict, problem, cand, best_s: float) -> None:
    """Attach the bytes-based FFT roofline: modeled 5·N·log2(N) flops,
    modeled HBM bytes from the *active* cost model (so a fitted per-device
    table flows into roofline_frac too), and the achieved fraction of
    whichever wall binds (always finite for an ok row — an
    :class:`~repro.core.backends.Infeasible` verdict degrades to the
    one-read+one-write algorithmic minimum, and the row is tagged and
    logged: a row that actually ran but models as infeasible means the
    model's feasibility rules have drifted from the kernels')."""
    import jax
    from repro.core.costmodel import get_active_model
    from repro.roofline.analysis import fft_model_flops, fft_roofline_frac

    flops = fft_model_flops(problem.extents, problem.batch)
    verdict = get_active_model().estimate(problem, cand)
    bytes_ = float(verdict)
    if not (0.0 < bytes_ < float("inf")):
        bytes_ = 2.0 * problem.signal_bytes
        reason = getattr(verdict, "reason", "") or "non-finite model bytes"
        rec["roofline_fallback"] = reason
        ROOFLINE_FALLBACKS.append(
            (f"{cand.key()} @ {problem.signature()}", reason))
    rec["model_flops"] = flops
    rec["model_bytes"] = bytes_
    rec["roofline_frac"] = fft_roofline_frac(
        best_s * 1e3, flops, bytes_, jax.devices()[0].device_kind)


def bench_backend(backend: str, extents: tuple[int, ...], batch: int,
                  reps: int, warmups: int) -> dict:
    import jax
    from repro.core.client import Problem
    from repro.core.extents import classify
    from repro.core.candidates import Candidate, backend_supports
    from repro.core.clients.jax_fft import build_forward

    problem = Problem(extents, "Outplace_Complex", "float", batch=batch)
    rec = {"backend": backend, "extent": "x".join(map(str, extents)),
           "rank": len(extents), "batch": batch,
           "kind": problem.kind, "precision": problem.precision,
           "class": classify(extents)}
    if not backend_supports(backend, problem):
        rec.update(ok=False, error="unsupported extents/rank")
        return rec
    try:
        cand = Candidate(backend)
        fn = build_forward(problem, cand)
        rng = np.random.default_rng(0)
        shape = (batch, *extents)
        x = (rng.standard_normal(shape) +
             1j * rng.standard_normal(shape)).astype(np.complex64)
        xd = jax.device_put(x)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(xd))
        rec["compile_ms"] = (time.perf_counter() - t0) * 1e3
        for _ in range(warmups):
            jax.block_until_ready(fn(xd))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(xd))
            times.append(time.perf_counter() - t0)
        best = _record_times(rec, times)
        moved = 2 * x.nbytes          # one read + one write of the signal
        rec["gib_per_s"] = moved / best / 2**30
        _annotate_roofline(rec, problem, cand, best)
        rec["ok"] = True
    except Exception as e:  # infeasible extent for this backend: record it
        rec.update(ok=False, error=f"{type(e).__name__}: {e}")
    return rec


def bench_dist_backend(backend: str, extents: tuple[int, ...], batch: int,
                       reps: int, warmups: int) -> dict:
    """Time one mesh-parallel decomposition over every visible device, in
    the production TRANSPOSED-output layout (no reordering pass) with the
    planner's default local engines."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.client import Problem
    from repro.core.extents import classify
    from repro.core.candidates import Candidate, _pencil_mesh_shapes
    from repro.core.clients.dist_fft import dist_engines
    from repro.fft import distributed as dist
    from repro.launch.mesh import flat_mesh, reshaped_mesh

    p_dev = jax.device_count()
    b = 1 if backend == "dist1d" else batch    # dist1d consumes the whole axis
    problem = Problem(extents, "Outplace_Complex", "float", batch=b)
    rec = {"backend": backend, "extent": "x".join(map(str, extents)),
           "rank": len(extents), "batch": b,
           "kind": problem.kind, "precision": problem.precision,
           "class": classify(extents), "devices": p_dev}
    if backend == "pencil":
        shapes = _pencil_mesh_shapes(p_dev)
        if not shapes and p_dev == 1:
            shapes = [(1, 1)]   # degenerate 1-device baseline point
        mesh_shape = shapes[0] if shapes else None
    else:
        mesh_shape = (p_dev,)
    rank = len(extents)
    feasible = mesh_shape is not None and (
        (backend == "dist1d" and rank == 1
         and dist.can_shard_1d(extents[0], p_dev))
        or (backend == "slab" and rank in (2, 3)
            and dist.slab_divisible(extents, p_dev))
        or (backend == "pencil" and rank == 3
            and dist.pencil_divisible(extents, *mesh_shape)))
    if not feasible:
        rec.update(ok=False, error="unsupported extents/rank/device count")
        return rec
    rec["mesh"] = "x".join(map(str, mesh_shape))
    try:
        base = flat_mesh()
        cand = Candidate(backend, mesh=mesh_shape)
        engines = dist_engines(problem, cand)
        if backend == "dist1d":
            mesh = reshaped_mesh(base, mesh_shape, names=("data",))
            fn, _ = dist.make_fft1d(mesh, "data", extents[0],
                                    engines=engines)
            sharding = NamedSharding(mesh, P("data"))
            shape = (extents[0],)
        else:
            mesh = reshaped_mesh(base, mesh_shape)
            if backend == "slab":
                fn, in_spec, _ = dist.make_slab_fftnd(
                    mesh, "d0", extents, engines=engines)
            else:
                fn, in_spec, _ = dist.make_pencil_fftnd(
                    mesh, "d0", "d1", extents, engines=engines)
            sharding = NamedSharding(mesh, in_spec)
            shape = (b, *extents)
        rng = np.random.default_rng(0)
        x = (rng.standard_normal(shape) +
             1j * rng.standard_normal(shape)).astype(np.complex64)
        xd = jax.device_put(x, sharding)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(xd))
        rec["compile_ms"] = (time.perf_counter() - t0) * 1e3
        for _ in range(warmups):
            jax.block_until_ready(fn(xd))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(xd))
            times.append(time.perf_counter() - t0)
        best = _record_times(rec, times)
        moved = 2 * x.nbytes          # one read + one write of the signal
        rec["gib_per_s"] = moved / best / 2**30
        _annotate_roofline(rec, problem, cand, best)
        _annotate_hlo_collectives(rec, fn, xd)
        rec["ok"] = True
    except Exception as e:
        rec.update(ok=False, error=f"{type(e).__name__}: {e}")
    return rec


def _annotate_hlo_collectives(rec: dict, fn, xd) -> None:
    """Loop-aware collective traffic from the compiled HLO (per-device
    SPMD module) on the distributed rows — the measured-side cross-check of
    the planner's interconnect term in ``estimate_bytes_moved``.  Best
    effort: not every callable exposes its compiled module."""
    try:
        from repro.roofline.hlo_parse import analyze
        hlo = analyze(fn.lower(xd).compile().as_text())
        rec["hlo_collective_bytes"] = hlo["collective_total"]
        rec["hlo_collective_counts"] = hlo["collective_counts"]
    except Exception:
        pass


#: Backends the serving replay is pinned to, plus the planner default
#: (backend None → per-request plan selection through the shared cache).
SERVE_BACKENDS = (None, "xla", "stockham_pallas")


def bench_serve_replay(backend, requests: int, smoke: bool) -> dict:
    """One seeded Zipf mixed-shape replay against a fresh service pinned to
    ``backend`` (None = planner-selected); records tail latency, sustained
    GiB/s, and the coalescing/cache counters."""
    from repro.serve import FFTService, ServeConfig, TrafficSpec, replay

    spec = TrafficSpec(
        extents=(("256", "1024", "16x16") if smoke
                 else ("1024", "4096", "256", "64x64")),
        kinds=("Outplace_Complex",) if smoke
        else ("Outplace_Complex", "Outplace_Real"),
        precisions=("float",), requests=requests, rate_hz=0.0,
        zipf_s=1.1, seed=2017)
    rec = {"mode": "serve_replay", "backend": backend or "planned",
           "traffic": spec.to_dict()}
    try:
        cfg = ServeConfig(coalesce_window_ms=2.0, max_batch=16,
                          backend=backend)
        with FFTService(config=cfg) as svc:
            for ext, kind, prec in spec.mix():   # steady state, not compiles
                svc.prewarm(ext, kind, prec)
            rep = replay(svc, spec)
        s = rep.service
        lat = s.get("latency_ms", {})
        rec.update(ok=True, requests=s["requests"], completed=s["completed"],
                   errors=s["errors"], timeouts=s["timeouts"],
                   batches=s["batches"],
                   batched_requests=s["batched_requests"],
                   coalesce_rate=s["coalesce_rate"], rps=s["rps"],
                   gib_per_s=s["gib_per_s"], wall_s=rep.wall_s,
                   mean_ms=lat.get("mean"), p50_ms=lat.get("p50"),
                   p95_ms=lat.get("p95"), p99_ms=lat.get("p99"),
                   plan_cache=s.get("plan_cache"))
    except Exception as e:
        rec.update(ok=False, error=f"{type(e).__name__}: {e}")
    return rec


def bench_serve_burst(n_requests: int, ext: int = 4096) -> dict:
    """Coalesced vs serial-FIFO throughput on a same-shape closed-loop
    burst — the acceptance number for the coalescer (>= 2x on CPU).

    Serial means what it says: one request per launch, one launch at a
    time (window 0, max_batch 1, inflight 1).  Both sides use the batch
    intake (``submit_many``) and a prewarmed executable ladder, so the
    ratio isolates dispatch coalescing, not producer overhead or compiles.
    """
    from repro.serve import FFTService, ServeConfig

    x = ((np.arange(ext) % 512) / 512.0).astype(np.complex64)

    def run(cfg):
        with FFTService(config=cfg) as svc:
            svc.prewarm((ext,))                 # compiles outside the timing
            t0 = time.perf_counter()
            reqs = svc.submit_many([x] * n_requests)
            for r in reqs:
                r.result(timeout=600)
            wall = time.perf_counter() - t0
        rep = svc.report()
        return n_requests / wall, rep["batches"]

    rec = {"mode": "serve_burst", "extent": str(ext), "requests": n_requests}
    try:
        serial_rps, _ = run(ServeConfig(coalesce_window_ms=0.0, max_batch=1,
                                        inflight=1, backend="xla"))
        coalesced_rps, batches = run(ServeConfig(coalesce_window_ms=5.0,
                                                 max_batch=32,
                                                 backend="xla"))
        rec.update(ok=True, serial_rps=serial_rps,
                   coalesced_rps=coalesced_rps, coalesced_batches=batches,
                   speedup=coalesced_rps / serial_rps)
    except Exception as e:
        rec.update(ok=False, error=f"{type(e).__name__}: {e}")
    return rec


def bench_chaos_fallback(requests: int) -> dict:
    """Chaos scenario 1: the top-ranked backend for the hot shape hard-fails
    at compile, plus one transient execute fault.  Serial FIFO (window 0,
    max_batch 1) so the recovery path is deterministic: every request must
    still be delivered — via the fallback chain (the compile fault) or a
    backoff retry (the transient) — with the demotion recorded."""
    from repro.core.client import Problem
    from repro.core.plan import fallback_chain
    from repro.serve import FFTService, ServeConfig, TrafficSpec, chaos_replay

    hot = Problem((256,), "Outplace_Complex", "float")
    top = fallback_chain(hot)[0].backend
    spec = TrafficSpec(extents=("256", "64"), kinds=("Outplace_Complex",),
                       precisions=("float",), requests=requests, rate_hz=0.0,
                       zipf_s=1.1, seed=2017,
                       faults=({"fault": "compile_error", "backend": top},
                               {"fault": "execute_error", "times": 1}))
    rec = {"mode": "chaos_fallback", "top_backend": top,
           "traffic": spec.to_dict()}
    try:
        cfg = ServeConfig(coalesce_window_ms=0.0, max_batch=1,
                          breaker_threshold=1, max_retries=2)
        with FFTService(config=cfg) as svc:
            rep = chaos_replay(svc, spec)
        s = rep.replay.service
        rec.update(ok=rep.ok and s["demotions"] >= 1
                   and s["retry_successes"] >= 1,
                   clean_success_rate=rep.clean_success_rate,
                   poisoned=rep.poisoned, violations=rep.violations,
                   demotions=s["demotions"], retries=s["retries"],
                   retry_successes=s["retry_successes"],
                   faults_injected=s["faults_injected"],
                   quarantined=[k for k, v in s["quarantine"].items()
                                if v["state"] != "closed"],
                   wedged=s["wedged"], completed=s["completed"])
    except Exception as e:
        rec.update(ok=False, error=f"{type(e).__name__}: {e}")
    return rec


def bench_chaos_kill(requests: int) -> dict:
    """Chaos scenario 2: a worker thread is killed mid-dispatch.  The
    watchdog must fail the in-flight request cleanly (its future completes
    with an error, not a hang), restart the worker, and the service must
    finish the rest of the tape — no wedge, at most the one orphaned
    request lost."""
    from repro.serve import FFTService, ServeConfig, TrafficSpec, chaos_replay

    spec = TrafficSpec(extents=("256",), kinds=("Outplace_Complex",),
                       precisions=("float",), requests=requests, rate_hz=0.0,
                       seed=2017,
                       faults=({"fault": "kill_worker", "after": 2,
                                "times": 1},))
    rec = {"mode": "chaos_kill", "traffic": spec.to_dict()}
    try:
        cfg = ServeConfig(coalesce_window_ms=0.0, max_batch=1,
                          watchdog_interval_s=0.05)
        with FFTService(config=cfg) as svc:
            # orphaned in-flight requests are failed by design: the dying
            # worker can hold its current batch plus up to `inflight`
            # pending batches, so the gate tolerates that much loss
            lost = 1 + cfg.inflight
            rep = chaos_replay(svc, spec,
                               min_clean_success=1.0 - (lost + 1) / requests)
        s = rep.replay.service
        rec.update(ok=rep.ok and s["worker_restarts"] >= 1
                   and s["wedged"] == 0,
                   clean_success_rate=rep.clean_success_rate,
                   violations=rep.violations, completed=s["completed"],
                   failed_in_flight=s["errors"],
                   worker_restarts=s["worker_restarts"], wedged=s["wedged"],
                   worker_errors=s["worker_errors"],
                   faults_injected=s["faults_injected"])
    except Exception as e:
        rec.update(ok=False, error=f"{type(e).__name__}: {e}")
    return rec


def _run_chaos(args) -> int:
    """The --serve --chaos grid: seeded fault-injection replays validating
    the recovery machinery end to end (CI's chaos-smoke gate)."""
    import jax

    requests = 16 if args.smoke else 48
    dev = jax.devices()[0]
    doc = {
        "meta": make_meta(
            device_kind=dev.device_kind,
            platform=dev.platform,
            devices=jax.device_count(),
            interpret_kernels=dev.platform != "tpu",
            python=platform.python_version(),
            jax=jax.__version__,
            note="chaos replay: seeded FaultPlan against the Zipf tape; "
                 "clean_success_rate counts non-poisoned requests only",
        ),
        "results": [],
    }
    ok = True
    for rec in (bench_chaos_fallback(requests),
                bench_chaos_kill(max(8, requests // 2))):
        doc["results"].append(rec)
        ok = ok and rec["ok"]
        status = ("clean_success={:.3f} violations={}".format(
                      rec["clean_success_rate"], rec["violations"])
                  if "clean_success_rate" in rec
                  else f"failed: {rec.get('error')}")
        print(f"{rec['mode']:16s} ok={rec['ok']} {status}")
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"wrote {len(doc['results'])} records to {args.out}")
    return 0 if ok else 1


def _run_serve(args) -> int:
    """The --serve grid: per-backend Zipf replays + the burst speedup."""
    import jax

    requests = 24 if args.smoke else 96
    # a multiple of max_batch=32 (partially-filled batches linger for the
    # full coalesce window) and large enough that per-burst fixed costs
    # don't swamp the per-launch overhead the coalescer amortizes
    burst = 128
    dev = jax.devices()[0]
    doc = {
        "meta": make_meta(
            device_kind=dev.device_kind,
            platform=dev.platform,
            devices=jax.device_count(),
            interpret_kernels=dev.platform != "tpu",
            python=platform.python_version(),
            jax=jax.__version__,
            note="FFT serving layer: seeded Zipf mixed-shape replay per "
                 "backend (p50/p95/p99 enqueue-to-complete) + coalesced "
                 "vs serial same-shape burst",
        ),
        "results": [],
    }
    for backend in SERVE_BACKENDS:
        rec = bench_serve_replay(backend, requests, args.smoke)
        doc["results"].append(rec)
        status = (f"p50={rec['p50_ms']:8.1f} ms  p99={rec['p99_ms']:8.1f} ms "
                  f"{rec['rps']:6.1f} rps  coalesce={rec['coalesce_rate']:.2f}"
                  if rec["ok"] else f"failed: {rec['error']}")
        print(f"serve_replay {rec['backend']:16s} {status}")
    rec = bench_serve_burst(burst)
    doc["results"].append(rec)
    if rec["ok"]:
        print(f"serve_burst  {'coalesced/serial':16s} "
              f"{rec['serial_rps']:6.1f} -> {rec['coalesced_rps']:6.1f} rps "
              f"({rec['speedup']:.1f}x)")
    else:
        print(f"serve_burst  failed: {rec['error']}")
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"wrote {len(doc['results'])} records to {args.out}")
    return 0


def _fan_out_devices(args, device_counts: list[int]) -> int:
    """Run the scaling grid: one subprocess per device count (the XLA host
    device count is frozen at first jax init), merge into one document.

    CPU only: the children see ``--xla_force_host_platform_device_count``
    virtual devices.  On a TPU host every child would claim every chip, so
    the fan-out refuses there."""
    from repro.core.device import platform as jax_platform

    if jax_platform() == "tpu":
        raise SystemExit(
            "--devices fans out one process per device count over virtual "
            "CPU devices; on a TPU host each child would claim every chip. "
            "Run it with JAX_PLATFORMS=cpu; the four-chip slab/pencil path "
            "is `python chip_smoke.py --chips 4`.")
    merged = {"meta": None, "results": []}
    for n in device_counts:
        fd, out = tempfile.mkstemp(suffix=f".dev{n}.json")
        os.close(fd)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--_worker", "--out", out,
               "--batch", str(args.batch), "--reps", str(args.reps),
               "--warmups", str(args.warmups)]
        if args.smoke:
            cmd.append("--smoke")
        if args.extents:
            cmd += ["--extents"] + [str(e) for e in args.extents]
        env = dict(os.environ)
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
        flags.append(f"--xla_force_host_platform_device_count={n}")
        env["XLA_FLAGS"] = " ".join(flags)
        print(f"--- devices={n} ---")
        subprocess.run(cmd, check=True, env=env)
        with open(out) as f:
            doc = json.load(f)
        os.unlink(out)
        if merged["meta"] is None:
            merged["meta"] = dict(doc["meta"])
            merged["meta"]["device_counts"] = []
            merged["meta"]["workers"] = []
        merged["meta"]["device_counts"].append(n)
        # preserve every worker's full meta (device kind / platform / jax /
        # reps per count), not just the first one's, so bench_diff can
        # attribute provenance per device-count axis point
        merged["meta"]["workers"].append({"devices": n, **doc["meta"]})
        merged["results"].extend(doc["results"])
    with open(args.out, "w") as f:
        json.dump(merged, f, indent=1)
        f.write("\n")
    _maybe_report(args)
    print(f"wrote {len(merged['results'])} records "
          f"({len(device_counts)}-point device axis) to {args.out}")
    return 0


def _maybe_report(args) -> None:
    """Emit the gearshifft-style Fig. 7 (backend x extent class x achieved
    roofline fraction) from the document just written."""
    if not getattr(args, "report", None):
        return
    report = fig7_report(load_bench(args.out))
    with open(args.report, "w") as f:
        f.write(report)
    print(f"wrote Fig. 7 report to {args.report}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", default="BENCH_PR5.json")
    p.add_argument("--backends", nargs="+", default=None)
    p.add_argument("--extents", nargs="+", default=None,
                   help="extent specs like 4096 64x64 16x16x16")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--warmups", type=int, default=1)
    p.add_argument("--smoke", action="store_true",
                   help="tiny grid + 1 rep (CI interpret-mode smoke)")
    p.add_argument("--devices", nargs="+", type=int, default=None,
                   help="device-count scaling axis, e.g. --devices 1 2 4 8 "
                        "(one subprocess per count; benches xla + the "
                        "distributed decompositions)")
    p.add_argument("--serve", action="store_true",
                   help="bench the FFT serving layer instead of raw "
                        "transforms: per-backend Zipf mixed-shape replays "
                        "(tail latency, GiB/s, coalesce rate) + the "
                        "coalesced-vs-serial burst speedup")
    p.add_argument("--chaos", action="store_true",
                   help="with --serve: run the seeded fault-injection "
                        "replays (fallback-chain recovery, watchdog worker "
                        "restart) instead of the perf grid; exits nonzero "
                        "if any recovery invariant is violated")
    p.add_argument("--report", default=None, metavar="PATH",
                   help="also write the gearshifft-style Fig. 7 markdown "
                        "(backend x extent class x achieved roofline "
                        "fraction) rendered from the written document")
    p.add_argument("--_worker", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    from repro.core.device import setup_compile_cache
    setup_compile_cache()

    if args.serve and args.chaos:
        return _run_chaos(args)
    if args.serve:
        return _run_serve(args)
    if args.devices:
        return _fan_out_devices(args, args.devices)

    scaling = args._worker   # per-device-count subprocess: the scaling grid
    if args.smoke:
        extents = list(args.extents
                       or (SMOKE_SCALING_EXTENTS if scaling else SMOKE_EXTENTS))
        reps, warmups = 1, 0
    else:
        extents = list(args.extents
                       or (SCALING_EXTENTS if scaling else DEFAULT_EXTENTS))
        reps, warmups = args.reps, args.warmups
    if args.backends:
        backends = list(args.backends)
    elif scaling:
        backends = ["xla", *DIST_BACKENDS]   # dist vs the vendor reference
    else:
        backends = list(DEFAULT_BACKENDS)

    from repro.core.extents import parse_extents
    grid = [parse_extents(str(e)) for e in extents]

    import jax
    dev = jax.devices()[0]
    n_dev = jax.device_count()
    doc = {
        "meta": make_meta(
            device_kind=dev.device_kind,
            platform=dev.platform,
            devices=n_dev,
            interpret_kernels=dev.platform != "tpu",
            python=platform.python_version(),
            jax=jax.__version__,
            batch=args.batch,
            reps=reps,
            note="forward c64 transform, min-of-reps (mean/sd/n per row); "
                 "gib_per_s assumes the one-read+one-write algorithmic "
                 "minimum; roofline_frac is the achieved fraction of the "
                 "modeled device roofline (5*N*log2(N) flops, planner "
                 "bytes-moved model)",
        ),
        "results": [],
    }
    for ext in grid:
        for backend in backends:
            if backend in DIST_BACKENDS:
                rec = bench_dist_backend(backend, ext, args.batch, reps,
                                         warmups)
            else:
                rec = bench_backend(backend, ext, args.batch, reps, warmups)
                rec["devices"] = 1 if not scaling else n_dev
            doc["results"].append(rec)
            status = (f"{rec['time_ms']:9.3f} ms  {rec['gib_per_s']:7.2f} GiB/s"
                      if rec["ok"] else f"infeasible: {rec['error']}")
            print(f"{rec['extent']:>12s} {backend:16s} {status}")
    if ROOFLINE_FALLBACKS:
        print(f"{len(ROOFLINE_FALLBACKS)} row(s) used the 2x-signal-bytes "
              "roofline fallback (model called them infeasible):")
        for what, why in ROOFLINE_FALLBACKS:
            print(f"  {what}: {why}")
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    _maybe_report(args)
    print(f"wrote {len(doc['results'])} records to {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
