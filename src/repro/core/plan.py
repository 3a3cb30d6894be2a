"""Plans, plan rigors, and the planner/autotuner.

fftw's planner concept (paper §2.1) mapped to JAX:

  plan          = (backend, factorization/tile knobs) + the AOT-compiled
                  executable for one Problem
  FFTW_ESTIMATE = static heuristic over the candidate space (no timing)
  FFTW_MEASURE  = compile + time every candidate, keep the fastest
  FFTW_PATIENT  = MEASURE over a widened space (kernel tile shapes too)
  FFTW_WISDOM_ONLY = look up a persisted choice; None plan if absent

Planning *time* is a first-class measurement (paper Figs. 4-5: MEASURE costs
3-4 orders of magnitude more than ESTIMATE and can exceed the transform time
by far) — the planner therefore reports plan_time_ms with every plan.

This module is the planning *driver*.  It plans over
:mod:`repro.core.candidates` (the search space) and
:mod:`repro.core.costmodel` (the bytes-moved model), whose module functions
delegate to the **active** cost model: installing a fitted per-device table
re-ranks ESTIMATE picks, fallback chains, and the serve engine's chain
memoization without any caller changing.  What a backend is lives in
:mod:`repro.core.backends`; the circuit breaker in :mod:`repro.core.breaker`.
"""

from __future__ import annotations

import enum
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import numpy as np

from .breaker import CircuitBreaker, breaker_key
from .candidates import Candidate, candidates
from .client import Problem
from .costmodel import estimate_bytes_moved, estimate_choice


class PlanRigor(enum.Enum):
    ESTIMATE = "estimate"
    MEASURE = "measure"
    PATIENT = "patient"
    WISDOM_ONLY = "wisdom_only"


@dataclass
class Plan:
    problem: Problem
    candidate: Candidate
    rigor: PlanRigor
    plan_time_ms: float = 0.0
    measured_ms: dict[str, float] = field(default_factory=dict)  # per-candidate timings
    fallbacks: tuple[str, ...] = ()   # candidate keys demoted before this one
    #: Where the selection came from — 'estimate' | 'measure' | 'patient' |
    #: 'wisdom' (exact persisted hit) | 'wisdom_near' (nearest-neighbor
    #: interpolated warm start) | 'fallback' (chain walk after demotions).
    #: Result rows surface this so interpolated picks stay distinguishable.
    source: str = ""


# ---------------------------------------------------------------------------
# Plan/executable cache (engine layer 2)
# ---------------------------------------------------------------------------
@dataclass
class PlanCacheStats:
    """Cold/warm accounting: misses pay the measured compile (cold) cost,
    hits dispatch the memoized executable (warm)."""

    hits: int = 0
    misses: int = 0
    cold_ms: float = 0.0   # total time spent building on misses

    def as_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "cold_ms": self.cold_ms}


class PlanCache:
    """Memoizes lowered/compiled executables and measured plan selections.

    Keys include the *device kind* (an executable compiled for one device
    kind must never serve another), the problem signature (extents,
    precision, kind, batch), the candidate (backend + knobs), and the
    transform direction.  Without the cache every repetition re-lowers and
    re-compiles (the honest per-run planning measurement of paper Figs. 4-5);
    with it, the first run to need an executable — possibly a warmup, whose
    cold-compile ops are then emitted with a negative run index — pays the
    measured cold compile, and warm repetitions reuse the executable.  Both
    quantities stay measured, and result rows carry a ``plan_cache``
    hit/miss marker so they remain distinguishable downstream.

    Lookups are **concurrency-safe**: the maps are guarded by a lock and
    builds are single-flight — when several serving workers race on the same
    cold key, exactly one runs ``build`` while the rest wait on its in-flight
    marker and then take the hit path, so ``misses`` always equals the number
    of distinct keys built and ``hits + misses`` the number of lookups (the
    invariant the threaded hammer test pins).
    """

    def __init__(self) -> None:
        self._execs: dict[str, Any] = {}
        self._plans: dict[str, Any] = {}
        self._lock = threading.Lock()
        self._inflight: dict[str, threading.Event] = {}
        self.stats = PlanCacheStats()

    def _single_flight(self, table: dict, kind: str, key: str,
                       build: Callable[[], Any],
                       count_stats: bool) -> tuple[Any, str, float]:
        """One builder per (kind, key); racing threads wait and read the
        published value.  The lock is dropped while ``build`` runs (compiles
        can take seconds) and re-taken to publish.  ``count_stats`` keeps the
        hit/miss accounting an executable-cache quantity, as before."""
        flight_key = f"{kind}|{key}"
        while True:
            with self._lock:
                if key in table:
                    if count_stats:
                        self.stats.hits += 1
                    return table[key], "hit", 0.0
                ev = self._inflight.get(flight_key)
                if ev is None:
                    ev = threading.Event()
                    self._inflight[flight_key] = ev
                    break           # we are the builder
            ev.wait()               # another thread is building this key
        t0 = time.perf_counter()
        try:
            built = build()
            ms = (time.perf_counter() - t0) * 1e3
            with self._lock:
                table[key] = built
                if count_stats:
                    self.stats.misses += 1
                    self.stats.cold_ms += ms
            return built, "miss", ms
        finally:
            with self._lock:
                self._inflight.pop(flight_key, None)
            ev.set()

    # --- keys -------------------------------------------------------------
    @staticmethod
    def executable_key(device_kind: str, problem: Problem,
                       candidate: "Candidate | str", direction: str) -> str:
        ck = candidate.key() if isinstance(candidate, Candidate) else str(candidate)
        return f"exec|{device_kind}|{problem.signature()}|{ck}|{direction}"

    @staticmethod
    def plan_key(device_kind: str, problem: Problem, rigor: "PlanRigor",
                 scope: str = "") -> str:
        return f"plan|{device_kind}|{problem.signature()}|{rigor.value}|{scope}"

    # --- lookups ----------------------------------------------------------
    def executable(self, key: str, build: Callable[[], Any]
                   ) -> tuple[Any, str, float]:
        """Return ``(executable, 'hit'|'miss', elapsed_ms)``.

        ``build`` runs only on a miss; its wall time is the measured cold
        compile cost.
        """
        return self._single_flight(self._execs, "exec", key, build,
                                   count_stats=True)

    def plan(self, key: str, make: Callable[[], Any]) -> tuple[Any, str]:
        """Memoized plan selection (candidate sweeps run at most once per
        key — a MEASURE sweep over repeated repetitions stops re-compiling
        every candidate).  ``None`` results (wisdom misses) are cached too:
        a deterministic miss stays a miss."""
        plan, event, _ = self._single_flight(self._plans, "plan", key, make,
                                             count_stats=False)
        return plan, event

    def __len__(self) -> int:
        with self._lock:
            return len(self._execs)


def cached_build(plan_cache: "PlanCache | None", events: dict, op_name: str,
                 key: str, build: Callable[[], Any]):
    """Memoize-or-build an executable, recording the hit/miss event for the
    result rows.  With no cache attached this is just ``build()`` — the
    per-run recompile measurement."""
    if plan_cache is None:
        return build()
    compiled, event, _ = plan_cache.executable(key, build)
    events[op_name] = event
    return compiled


def executable_bytes(compiled) -> int:
    """Bytes attributable to a compiled executable (plan size analogue)."""
    try:
        ma = compiled.memory_analysis()
        return int(getattr(ma, "temp_size_in_bytes", 0) +
                   getattr(ma, "generated_code_size_in_bytes", 0))
    except Exception:
        return 0


def fallback_chain(problem: Problem, patient: bool = False,
                   mesh=None) -> list[Candidate]:
    """The ordered degradation path: ESTIMATE's pick first (its dft pin for
    tiny rank-1 problems included), then every other feasible candidate by
    ascending modeled cost, with a plain ``xla`` candidate guaranteed
    present — the always-feasible terminal fallback.  Pure ordering under
    the *active* cost model — a fitted per-device table re-ranks the chain
    for every walker: the walkers (:func:`make_plan`'s fault-tolerant mode,
    the serve engine) apply wisdom-demotion and circuit-breaker filtering
    at try time."""
    cands = candidates(problem, patient=patient, mesh=mesh)
    scored = [(estimate_bytes_moved(problem, c), i, c)
              for i, c in enumerate(cands)]
    ranked = [c for cost, _, c in sorted(scored, key=lambda t: t[:2])
              if cost != float("inf")]
    top = estimate_choice(problem)
    chain = [top] + [c for c in ranked if c.key() != top.key()]
    if not any(c.backend == "xla" and not c.axes for c in chain):
        chain.append(Candidate("xla"))
    return chain


def probe_finite(fn: Callable, problem: Problem) -> None:
    """Cheap output-finiteness probe: push one all-ones batch through a
    freshly built executable and reject it on any non-finite output — the
    'compiles fine, computes garbage' failure mode a build error misses."""
    x = np.ones((problem.batch, *problem.extents), dtype=problem.real_dtype)
    if problem.complex_input:
        x = x.astype(problem.input_dtype)
    out = np.asarray(fn(x))
    if not np.isfinite(out).all():
        raise RuntimeError(
            f"finiteness probe failed for {problem.signature()}: "
            f"executable produced non-finite output")


def measure_plan(problem: Problem, build: Callable[[Candidate], Callable],
                 cands: Sequence[Candidate], reps: int = 3) -> tuple[Candidate, dict[str, float]]:
    """MEASURE: compile + run each candidate, return fastest + timing table."""
    import jax

    timings: dict[str, float] = {}
    rng = np.random.default_rng(0)
    x = rng.standard_normal((problem.batch, *problem.extents)).astype(problem.real_dtype)
    if problem.complex_input:
        x = x.astype(problem.input_dtype)
    xd = jax.device_put(x)
    for cand in cands:
        try:
            fn = build(cand)
            fn(xd)  # compile + warmup
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(xd))
                best = min(best, (time.perf_counter() - t0) * 1e3)
            timings[cand.key()] = best
        except Exception as e:  # infeasible candidate: record, keep going
            timings[cand.key()] = float("nan")
    feasible = {k: v for k, v in timings.items() if v == v}
    if not feasible:
        raise RuntimeError(f"no feasible plan for {problem.signature()}")
    best_key = min(feasible, key=feasible.get)
    best_cand = next(c for c in cands if c.key() == best_key)
    return best_cand, timings


def _demoted_backends(wisdom, problem: Problem) -> frozenset:
    """Backends wisdom has quarantined for this problem-class (empty when
    the wisdom store is absent or predates demotion records)."""
    if wisdom is None:
        return frozenset()
    demoted = getattr(wisdom, "demoted", None)
    return demoted(problem) if callable(demoted) else frozenset()


def _near_lookup(wisdom, problem: Problem, demoted: frozenset):
    """Nearest-neighbor wisdom consultation (schema v3): a candidate tuned
    for the closest same-feasibility-class shape, or None.  Duck-typed so
    pre-v3 stores (and stand-ins without ``lookup_near``) just miss."""
    near = getattr(wisdom, "lookup_near", None)
    if near is None:
        return None
    hit = near(problem)
    if hit is None:
        return None
    cand, _neighbor = hit
    if cand.backend in demoted and cand.backend != "xla":
        return None
    return cand


def _fallback_plan(problem: Problem, rigor: PlanRigor,
                   build: Callable[[Candidate], Callable], wisdom,
                   breaker: CircuitBreaker, probe: bool, t0: float,
                   demoted: frozenset) -> Plan:
    """Fault-tolerant planning: walk the cost-ordered fallback chain,
    demoting past candidates that fail at build (or at the optional
    finiteness probe), with circuit-breaker bookkeeping per (backend,
    problem-class) pair.  A demotion that OPENS the breaker is persisted to
    wisdom so warm sessions skip the known-bad pick outright.  The terminal
    candidate — by construction a plain ``xla`` is always in the chain —
    is tried regardless of quarantine state."""
    chain = fallback_chain(problem, patient=(rigor is PlanRigor.PATIENT))
    fallbacks: list[str] = []
    last_err: Exception | None = None
    for i, cand in enumerate(chain):
        terminal = i == len(chain) - 1
        is_xla = cand.backend == "xla" and not cand.axes
        if not terminal and not is_xla:
            if cand.backend in demoted:
                fallbacks.append(cand.key())
                continue
            if not breaker.allows(breaker_key(cand.backend, problem)):
                fallbacks.append(cand.key())
                continue
        try:
            fn = build(cand)
            if probe:
                probe_finite(fn, problem)
        except Exception as e:
            last_err = e
            state = breaker.record_failure(breaker_key(cand.backend, problem))
            if wisdom is not None and not is_xla \
                    and state == CircuitBreaker.OPEN:
                wisdom.record_demotion(problem, cand.backend)
            fallbacks.append(cand.key())
            continue
        breaker.record_success(breaker_key(cand.backend, problem))
        return Plan(problem, cand, rigor, (time.perf_counter() - t0) * 1e3,
                    fallbacks=tuple(fallbacks),
                    source="fallback" if fallbacks else "estimate")
    raise RuntimeError(
        f"no feasible plan for {problem.signature()}: all {len(chain)} "
        f"candidates failed (last: {type(last_err).__name__}: {last_err})")


def make_plan(problem: Problem, rigor: PlanRigor,
              build: Callable[[Candidate], Callable] | None = None,
              wisdom=None, breaker: CircuitBreaker | None = None,
              probe: bool = False, near: bool = True) -> Plan | None:
    """The planner. Returns None for WISDOM_ONLY misses (fftw NULL plan).

    MEASURE/PATIENT consult wisdom first, fftw-style: a persisted selection
    for this (device, problem) short-circuits the candidate sweep entirely,
    so a warm Session (or a second process sharing the wisdom file) plans in
    microseconds instead of re-compiling every candidate.  On an exact miss
    a schema-v3 wisdom store is consulted for a **nearest-neighbor** warm
    start (``Wisdom.lookup_near``): the selection tuned for the closest
    shape in the same backend-feasibility class, returned with plan source
    ``'wisdom_near'`` so results stay honest.  ``near=False`` disables the
    interpolated path — the pregeneration tools use it so every swept shape
    gets a real sweep rather than inheriting its neighbor's pick.

    Fault tolerance: with both ``build`` and ``breaker`` supplied, planning
    walks the :func:`fallback_chain` instead — each candidate is actually
    built (and optionally finiteness-probed with ``probe=True``) before it
    is returned, failures demote to the next candidate by modeled cost, and
    the (backend, problem-class) pair is quarantined in the breaker; see
    :func:`_fallback_plan`.  Without a breaker, behavior is unchanged except
    that wisdom-recorded demotions steer the ESTIMATE pick away from
    known-bad backends.
    """
    t0 = time.perf_counter()
    if rigor is PlanRigor.WISDOM_ONLY:
        if wisdom is None:
            return None
        cand = wisdom.lookup(problem)
        if cand is not None:
            return Plan(problem, cand, rigor,
                        (time.perf_counter() - t0) * 1e3, source="wisdom")
        if near:
            cand = _near_lookup(wisdom, problem,
                                _demoted_backends(wisdom, problem))
            if cand is not None:
                return Plan(problem, cand, rigor,
                            (time.perf_counter() - t0) * 1e3,
                            source="wisdom_near")
        return None

    demoted = _demoted_backends(wisdom, problem)
    if wisdom is not None and rigor in (PlanRigor.MEASURE, PlanRigor.PATIENT):
        cand = wisdom.lookup(problem)
        if cand is not None and cand.backend not in demoted:
            # tuned knobs persisted by an earlier sweep
            return Plan(problem, cand, rigor,
                        (time.perf_counter() - t0) * 1e3, source="wisdom")
        if cand is None and near:
            # nearest-neighbor warm start: MEASURE-grade pick without the
            # sweep — the selection tuned for the closest same-class shape
            cand = _near_lookup(wisdom, problem, demoted)
            if cand is not None:
                return Plan(problem, cand, rigor,
                            (time.perf_counter() - t0) * 1e3,
                            source="wisdom_near")

    if build is not None and breaker is not None:
        return _fallback_plan(problem, rigor, build, wisdom, breaker, probe,
                              t0, demoted)

    if rigor is PlanRigor.ESTIMATE or build is None:
        cand, timings = estimate_choice(problem), {}
        if cand.backend in demoted and cand.backend != "xla":
            # warm session: skip the known-bad pick without a live breaker
            for c in fallback_chain(problem):
                if c.backend == "xla" or c.backend not in demoted:
                    cand = c
                    break
    else:
        cands = candidates(problem, patient=(rigor is PlanRigor.PATIENT))
        if demoted:
            cands = [c for c in cands
                     if c.backend == "xla" or c.backend not in demoted]
        cand, timings = measure_plan(problem, build, cands)
    plan = Plan(problem, cand, rigor, (time.perf_counter() - t0) * 1e3,
                timings, source=rigor.value if timings else "estimate")
    # persist only selections a sweep actually timed: a build-less
    # MEASURE/PATIENT call falls back to the untimed ESTIMATE pick, and
    # recording that would let the wisdom-first short-circuit lock it in
    # forever as if it had been measured
    if wisdom is not None and timings \
            and rigor in (PlanRigor.MEASURE, PlanRigor.PATIENT):
        wisdom.record(problem, cand,
                      measured_ms=timings.get(cand.key()),
                      rigor=rigor.value)
    return plan
