#!/usr/bin/env python3
"""Chip smoke test: the planned FFT path and the FFT service, once, on a TPU.

    python chip_smoke.py                  # one chip: suite, then service
    python chip_smoke.py --chips 4        # four chips: slab[4], pencil[2x2]
    python chip_smoke.py --cpu-rehearsal  # tiny sizes on the CPU (tests)

One chip (default):

1. *Suite.*  ``Session().run(SuiteSpec(...))`` with the ``Planned`` client
   at rigor ``estimate``, float, ``Outplace_Complex`` and ``Outplace_Real``,
   forward and inverse, at sizes gearshifft users call real (hundreds of
   MiB per problem).  Besides the suite's own roundtrip check, the forward
   executable the suite timed is fetched from the session's plan cache and
   its spectrum checked against ``numpy.fft`` in float64 on the first 8
   batch entries (all of them when the batch is smaller).
2. *Service.*  An ``FFTService`` prewarms its plans, replays a seeded
   ``TrafficSpec`` over three shapes and both kinds, and every answer is
   checked against numpy.

Four chips (``--chips 4``, this phase only): the ``DistFFTND`` client
through a ``Session``, ``slab[4]`` and ``pencil[2x2]``, 3-D C2C at 512^3
c64, compared with single-chip ``jnp.fft.fftn`` of the same input, and the
compiled HLO checked for its all-to-alls (1 for slab, 2 for pencil).

Every problem prints one line (backend, plan source, errors, median execute
times of the suite's repetitions, each ending in ``block_until_ready``).
The script exits nonzero, printing no result line, when it finds no TPU,
when a node fails, when any plan came from a fallback walk, when the
service reports a demotion, error or shed, or when a numpy check fails.  On
success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
``--cpu-rehearsal`` runs the same phases at tiny sizes on the CPU with the
Pallas kernels interpreted; it refuses to run on a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: Relative L2 bound of a c64 spectrum against numpy in float64
#: (tests/helpers/accuracy.py's c64 policy).
REL_L2_BOUND = 1e-3

#: Batch entries of each problem checked against numpy.
CHECK_ROWS = 8

KINDS = ("Outplace_Complex", "Outplace_Real")

#: (extents, batch): c64 signal sizes 512 MiB, 512 MiB, 256 MiB, 512 MiB,
#: 512 MiB, 576 MiB, 214 MiB, 256 MiB.  4096 and 256x256 are extents where
#: the planner offers Pallas kernels; 18432 is radix357, 6859 = 19^3
#: oddshape, 128 the matmul-DFT range.
SUITE = (((1048576,), 64), ((4096, 4096), 4), ((256, 256, 256), 2),
         ((4096,), 16384), ((256, 256), 1024), ((18432,), 4096),
         ((6859,), 4096), ((128,), 262144))
SUITE_REHEARSAL = (((1024,), 4), ((16, 32), 2), ((8, 8, 16), 2),
                   ((4096,), 2), ((18,), 8), ((19,), 8), ((64,), 16))

SERVE_SHAPES = ((4096,), (256, 256), (18432,))
SERVE_SHAPES_REHEARSAL = ((64,), (8, 16), (18,))
SERVE_REQUESTS = 128
SERVE_REQUESTS_REHEARSAL = 24
SERVE_MAX_BATCH = 16

DIST_SHAPE = (512, 512, 512)
DIST_SHAPE_REHEARSAL = (16, 16, 16)
DIST_CHIPS = 4
#: all_to_alls in the default TRANSPOSED-output layout.
DIST_A2A = {"slab": 1, "pencil": 2}


def rel_l2(got, ref) -> float:
    got = np.asarray(got, dtype=np.complex128)
    ref = np.asarray(ref, dtype=np.complex128)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-300))


def numpy_forward(x: np.ndarray, rank: int, real: bool) -> np.ndarray:
    axes = tuple(range(-rank, 0))
    if real:
        return np.fft.rfftn(x.astype(np.float64), axes=axes)
    return np.fft.fftn(x.astype(np.complex128), axes=axes)


def random_input(problem, rng) -> np.ndarray:
    shape = (problem.batch, *problem.extents)
    x = rng.standard_normal(shape, dtype=np.float32)
    if problem.complex_input:
        x = x + 1j * rng.standard_normal(shape, dtype=np.float32)
    return x.astype(problem.input_dtype)


def median_ms(rows, op: str) -> float:
    times = [r.time_ms for r in rows if r.op == op and r.run >= 0]
    return statistics.median(times) if times else float("nan")


def cached_forward(session, problem, scope: str, exec_name=None):
    """The plan and forward executable a suite run left in the session's
    plan cache (a hit: nothing is planned or compiled again)."""
    from repro.core.plan import PlanCache, PlanRigor

    def missing():
        raise RuntimeError(f"{problem.signature()} left no cached plan")

    kind = session.context.device_kind
    plan, _ = session.plan_cache.plan(
        PlanCache.plan_key(kind, problem, PlanRigor.ESTIMATE, scope=scope),
        missing)
    name = exec_name(plan.candidate) if exec_name else plan.candidate
    compiled, _, _ = session.plan_cache.executable(
        PlanCache.executable_key(kind, problem, name, "forward"), missing)
    return plan, compiled


def suite_phase(session, problems, failures: list) -> None:
    import jax
    from repro.core.client import Problem
    from repro.core.suite import SuiteSpec

    rng = np.random.default_rng(2017)
    for extents, batch in problems:
        spec = SuiteSpec(clients=("Planned",), extents=(extents,),
                         kinds=KINDS, precisions=("float",), batch=batch,
                         rigor="estimate", warmups=1, repetitions=3,
                         output=None)
        rs = session.run(spec)
        for kind in KINDS:
            problem = Problem(tuple(extents), kind, "float", batch=batch)
            label = f"suite {problem.signature()}"
            rows = [r for r in rs.rows if r.kind == kind]
            bad = [r.error for r in rows if not r.success]
            if bad:
                failures.append(f"{label}: node failed: {bad}")
                print(f"{label} FAIL {bad}", flush=True)
                continue
            try:
                plan, fwd = cached_forward(session, problem, scope="*")
                x = random_input(problem, rng)
                k = min(CHECK_ROWS, batch)
                y = np.asarray(fwd(jax.device_put(x))[:k])
                err = rel_l2(y, numpy_forward(x[:k], problem.rank,
                                              not problem.complex_input))
            except Exception as e:
                traceback.print_exc()
                failures.append(f"{label}: forward check raised {e!r}")
                continue
            validate = next(r for r in rows if r.op == "validate")
            line = (f"{label} backend={plan.candidate.key()} "
                    f"source={plan.source} fwd_rel_l2={err:.3e} "
                    f"checked_rows={k} "
                    f"roundtrip_ok={validate.success} "
                    f"exec_fwd_ms={median_ms(rows, 'execute_forward'):.4f} "
                    f"exec_inv_ms={median_ms(rows, 'execute_inverse'):.4f}")
            print(line, flush=True)
            if plan.source == "fallback":
                failures.append(f"{label}: plan came from a fallback walk")
            if not err <= REL_L2_BOUND:
                failures.append(f"{label}: forward rel-L2 {err:.3e} > "
                                f"{REL_L2_BOUND}")


def service_phase(session, shapes, requests: int, failures: list) -> None:
    from repro.serve.engine import FFTService, ServeConfig
    from repro.serve.replay import TrafficSpec, replay

    svc = FFTService(session, ServeConfig(max_batch=SERVE_MAX_BATCH))
    warm = sum(svc.prewarm(ext, kind) for ext in shapes for kind in KINDS)
    traffic = TrafficSpec(extents=shapes, kinds=KINDS, requests=requests,
                          seed=2017)
    with svc:
        rep = replay(svc, traffic)
    refs: dict[int, np.ndarray] = {}
    worst = 0.0
    unanswered = 0
    for req in rep.requests:
        if not req.ok:
            unanswered += 1
            continue
        ref = refs.get(id(req.payload))
        if ref is None:
            ref = refs[id(req.payload)] = numpy_forward(
                req.payload, len(req.extents), req.kind.endswith("Real"))
        worst = max(worst, rel_l2(req.result(), ref))
    s = rep.service
    lat = s.get("latency_ms", {})
    print(f"service requests={s['requests']} completed={s['completed']} "
          f"prewarmed={warm} p50_ms={lat.get('p50', float('nan')):.4f} "
          f"p99_ms={lat.get('p99', float('nan')):.4f} "
          f"errors={s['errors']} demotions={s['demotions']} "
          f"sheds={s['sheds']} worst_rel_l2={worst:.3e}", flush=True)
    for name in ("errors", "demotions", "sheds", "timeouts"):
        if s[name]:
            failures.append(f"service: {name}={s[name]}")
    if unanswered or s["completed"] != requests:
        failures.append(f"service: {unanswered} of {requests} requests "
                        "unanswered")
    if not worst <= REL_L2_BOUND:
        failures.append(f"service: worst rel-L2 {worst:.3e} > {REL_L2_BOUND}")


def dist_phase(devices, shape, failures: list) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.client import Context, Problem
    from repro.core.suite import Session, SuiteSpec
    from repro.launch.mesh import flat_mesh, use_mesh
    from repro.roofline.hlo_parse import count_source_collectives

    problem = Problem(tuple(shape), "Outplace_Complex", "float", batch=1)
    rng = np.random.default_rng(2017)
    x = random_input(problem, rng)
    ref = np.asarray(jax.jit(lambda v: jnp.fft.fftn(v, axes=(1, 2, 3)))(
        jax.device_put(x, devices[0])))
    spec = SuiteSpec(clients=("DistFFTND",), extents=(tuple(shape),),
                     kinds=("Outplace_Complex",), precisions=("float",),
                     batch=1, rigor="estimate", warmups=1, repetitions=3,
                     output=None)
    with use_mesh(flat_mesh(devices)):
        for backend in ("slab", "pencil"):
            label = f"dist {backend} {problem.signature()}"
            session = Session(context=Context({"dist_backend": backend}))
            rs = session.run(spec)
            bad = [r.error for r in rs.rows if not r.success]
            if bad:
                failures.append(f"{label}: node failed: {bad}")
                print(f"{label} FAIL {bad}", flush=True)
                continue
            try:
                plan, fwd = cached_forward(
                    session, problem, scope=f"dist[{len(devices)}]",
                    exec_name=lambda c: c.key())
                xd = jax.device_put(x, fwd.input_shardings[0][0])
                err = rel_l2(np.asarray(fwd(xd)), ref)
                a2a = count_source_collectives(fwd.as_text())
            except Exception as e:
                traceback.print_exc()
                failures.append(f"{label}: check raised {e!r}")
                continue
            print(f"{label} plan={plan.candidate.key()} "
                  f"rel_l2_vs_one_chip_fftn={err:.3e} all_to_all={a2a} "
                  f"exec_fwd_ms={median_ms(rs.rows, 'execute_forward'):.4f} "
                  f"exec_inv_ms={median_ms(rs.rows, 'execute_inverse'):.4f}",
                  flush=True)
            if plan.candidate.backend != backend:
                failures.append(f"{label}: planned {plan.candidate.key()}")
            if not err <= REL_L2_BOUND:
                failures.append(f"{label}: rel-L2 {err:.3e} > {REL_L2_BOUND}")
            if a2a != DIST_A2A[backend]:
                failures.append(f"{label}: {a2a} all-to-alls, expected "
                                f"{DIST_A2A[backend]}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, DIST_CHIPS), default=1,
                   help="4: run only the slab/pencil phase on four chips")
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="tiny sizes on the CPU, Pallas kernels interpreted")
    args = p.parse_args(argv)

    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = " ".join(
                [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "host_platform_device_count" not in f]
                + [f"--xla_force_host_platform_device_count={args.chips}"])
    src = os.path.join(HERE, "src")
    sys.path.insert(0, src)
    try:
        from repro.core import device
    except ImportError as e:
        print(f"chip_smoke: the repro package is not here ({e})",
              file=sys.stderr)
        return 2
    if not os.path.abspath(device.__file__).startswith(src + os.sep):
        print(f"chip_smoke: repro imported from {device.__file__}, not "
              f"from this checkout's {src}", file=sys.stderr)
        return 2
    device.setup_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if platform != want:
        print(f"chip_smoke: needs platform {want!r}, JAX found {platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(f"device platform={platform} kind={devices[0].device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)

    from repro.core.clients import jax_fft, dist_fft  # noqa: F401
    from repro.core.suite import Session

    failures: list[str] = []
    try:
        if args.chips > 1:
            dist_phase(devices[:args.chips], DIST_SHAPE_REHEARSAL
                       if args.cpu_rehearsal else DIST_SHAPE, failures)
        else:
            session = Session()
            suite_phase(session, SUITE_REHEARSAL if args.cpu_rehearsal
                        else SUITE, failures)
            service_phase(session, SERVE_SHAPES_REHEARSAL
                          if args.cpu_rehearsal else SERVE_SHAPES,
                          SERVE_REQUESTS_REHEARSAL if args.cpu_rehearsal
                          else SERVE_REQUESTS, failures)
    except Exception as e:
        traceback.print_exc()
        failures.append(f"phase raised {e!r}")
    if failures:
        for f in failures:
            print(f"chip_smoke FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
