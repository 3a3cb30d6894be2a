"""The FFT service engine: a long-lived worker loop over a Session.

Architecture (README "FFT service" section has the sketch):

    submit() ──▶ RequestQueue (bounded: backpressure) ──▶ Coalescer
                                                            │ batches
                                                            ▼
                  ┌──────────────── worker loop ────────────────────┐
                  │ stage rows into a host buffer (pow2 bucket)     │
                  │ upload + dispatch donated executable (async)    │
                  │ retire oldest in-flight batch, slice results    │
                  └─────────────────────────────────────────────────┘

Perf machinery:

* **Coalescing** — same-plan requests stack on the batch axis of one
  compiled executable (see :mod:`repro.serve.coalescer`).
* **Batch buckets** — coalesced row counts are rounded up to powers of two,
  so at most log2(max_batch) executables exist per plan instead of one per
  observed batch size; slack rows are staged but sliced away (counted in
  the metrics as ``padded_rows``).
* **Donated buffers** — executables are jitted with ``donate_argnums=(0,)``:
  XLA reuses the uploaded staging buffer for scratch/output instead of
  allocating fresh device memory per launch.
* **Double buffering** — dispatch is asynchronous; up to ``inflight``
  batches are on device while the worker stages the next host buffer, so
  host staging overlaps device compute.  Two alternating host staging
  arrays per (plan, bucket) avoid re-allocation.

Fault tolerance (README "Failure semantics" section):

* **Fallback chains** — an executable that fails to build (or a batch that
  fails to execute) demotes the service to the next candidate by modeled
  cost, with ``xla`` the always-feasible terminal fallback; the (backend,
  problem-class) pair is quarantined in a :class:`CircuitBreaker`, and a
  quarantine that opens is persisted to wisdom as a demotion.
* **Retries** — requests carry ``retries_left``; retryable failures
  re-enqueue through a jittered exponential-backoff timer.
* **Bisection** — a failed coalesced batch splits in two and each half is
  re-dispatched, so one poison request cannot fail its batchmates.
* **Watchdog** — a supervisor thread detects a dead worker, fails its
  in-flight requests cleanly, and restarts the thread; ``stop()`` reports
  (and raises on) workers still wedged after the join deadline.
* **Fault injection** — a seeded :class:`FaultPlan` (``ServeConfig.faults``)
  fires deterministic failures at the build / dispatch / execute sites so
  every path above is testable without real hardware faults.

Concurrency: the PlanCache is shared with the owning Session — its lookups
are single-flight and lock-guarded (PR 7), so several workers (or a worker
plus a foreground ``Session.run``) race safely on cold plans.
"""

from __future__ import annotations

import random
import threading
import time
from collections import deque
from dataclasses import dataclass, fields
from typing import Any, Optional

import numpy as np

from ..core.client import Problem
from ..core.extents import classify, format_extents, next_pow2
from ..core.breaker import CircuitBreaker, breaker_key
from ..core.candidates import Candidate
from ..core.plan import PlanCache, PlanRigor, fallback_chain, make_plan
from ..core.results import Row
from .coalescer import Batch, Coalescer
from .faults import FaultInjected, FaultPlan, WorkerKilled
from .metrics import ServiceMetrics
from .queue import RequestQueue
from .request import (FFTRequest, QueueFull, RequestTimeout, ServeError,
                      make_request)


class WorkerWedged(ServeError):
    """``stop()`` gave up on one or more workers that would not join within
    the configured deadline.  ``snapshot`` carries the final report (with
    ``wedged_workers`` naming the stuck threads) so the caller still gets
    the metrics it came for."""

    retryable = False

    def __init__(self, msg: str, snapshot: Optional[dict] = None):
        super().__init__(msg)
        self.snapshot = snapshot or {}


@dataclass(frozen=True)
class ServeConfig:
    """Service tuning knobs (all plain data: round-trips via to/from_dict
    like every other spec in the suite)."""

    max_queue: int = 1024            # bounded intake: the backpressure knob
    coalesce_window_ms: float = 2.0  # linger for stragglers; 0 = serial FIFO
    max_batch: int = 32              # row budget per coalesced launch
    workers: int = 1                 # consumer threads
    inflight: int = 2                # double-buffer depth per worker
    rigor: str = "estimate"          # planner rigor for request-time plans
    backend: Optional[str] = None    # pin one backend (bench per-library)
    costmodel: Optional[str] = None  # fitted coefficient-table path: plans
    #                                  and fallback chains rank under it
    timeout_ms: Optional[float] = None   # default per-request deadline
    bucket_batches: bool = True      # pow2-pad coalesced rows
    record_requests: bool = True     # keep per-request rows for ResultSet
    # --- fault tolerance ----------------------------------------------------
    fallback: bool = True            # demote past failed plan candidates
    max_retries: int = 2             # re-enqueues per request on failure
    backoff_base_ms: float = 0.5     # first-retry backoff (doubles per try)
    backoff_max_ms: float = 50.0     # backoff cap
    bisect_batches: bool = True      # split failed coalesced batches in two
    probe_output: bool = True        # reject non-finite outputs at retire
    breaker_threshold: int = 3       # consecutive failures to quarantine
    breaker_cooldown_s: float = 5.0  # quarantine time before half-open probe
    watchdog_interval_s: float = 0.25    # worker liveness poll; 0 = off
    join_timeout_s: float = 60.0     # stop(): per-worker join deadline
    drain_timeout_s: float = 60.0    # stop(drain=True): total drain budget
    faults: tuple = ()               # FaultRule dicts (chaos injection)

    def __post_init__(self):
        if self.max_queue < 1 or self.max_batch < 1 or self.workers < 1 \
                or self.inflight < 1:
            raise ValueError(f"bad ServeConfig bounds: {self}")
        if self.rigor not in {r.value for r in PlanRigor}:
            raise ValueError(f"unknown rigor {self.rigor!r}")
        if self.max_retries < 0 or self.breaker_threshold < 1:
            raise ValueError(f"bad ServeConfig fault-tolerance bounds: {self}")
        # normalize fault rules to a tuple of plain dicts (validated by
        # round-tripping each through FaultRule) so configs stay JSON-ready
        # and equality/round-trip semantics match every other spec
        from .faults import FaultRule
        rules = tuple(
            (r if isinstance(r, FaultRule)
             else FaultRule.from_dict(dict(r))).to_dict()
            for r in self.faults)
        object.__setattr__(self, "faults", rules)

    def to_dict(self) -> dict:
        d = {}
        for f in fields(self):
            v = getattr(self, f.name)
            if f.name == "faults":
                if v:
                    d[f.name] = [dict(r) for r in v]
            elif v is not None:
                d[f.name] = v
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ServeConfig":
        known = {f.name for f in fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown ServeConfig key(s) {sorted(unknown)}; "
                             f"known: {', '.join(sorted(known))}")
        return cls(**d)


class _Inflight:
    """One dispatched batch awaiting retirement."""

    __slots__ = ("batch", "out", "row_spans", "t_dispatch", "cand")

    def __init__(self, batch: Batch, out: Any,
                 row_spans: list[tuple[int, int]], t_dispatch: float,
                 cand: Optional[Candidate] = None):
        self.batch = batch
        self.out = out
        self.row_spans = row_spans
        self.t_dispatch = t_dispatch
        self.cand = cand


class FFTService:
    """Long-lived FFT serving loop on top of a Session.

    Use as a context manager (``with FFTService(session) as svc``) or call
    :meth:`start` / :meth:`stop` explicitly.  ``submit`` returns the request
    itself, which doubles as the completion future.
    """

    def __init__(self, session=None, config: ServeConfig = ServeConfig(),
                 wisdom=None, fault_plan: Optional[FaultPlan] = None):
        from ..core.suite import Session

        self.session = session if session is not None else Session()
        self.config = config
        self.wisdom = wisdom if wisdom is not None \
            else getattr(self.session, "_wisdom", None)
        self.fault_plan = fault_plan if fault_plan is not None \
            else (FaultPlan(config.faults) if config.faults else None)
        self.breaker = CircuitBreaker(threshold=config.breaker_threshold,
                                      cooldown_s=config.breaker_cooldown_s)
        self.queue = RequestQueue(config.max_queue)
        self.metrics = ServiceMetrics()
        self._coalescer = Coalescer(self.queue,
                                    window_ms=config.coalesce_window_ms,
                                    max_rows=config.max_batch)
        self._threads: list[threading.Thread] = []
        self._threads_lock = threading.Lock()
        self._staging: dict[tuple, list[np.ndarray]] = {}
        self._staging_flip: dict[tuple, int] = {}
        self._staging_lock = threading.Lock()
        self._chains: dict[str, list[Candidate]] = {}
        self._chains_lock = threading.Lock()
        self._cost_model = None   # resolved lazily: device discovery needs jax
        self._rows: list[Row] = []
        self._rows_lock = threading.Lock()
        self._started = False
        self._worker_errors: list[BaseException] = []
        # watchdog state: per-worker in-flight registries so a dead worker's
        # requests can be failed cleanly instead of hanging their futures
        self._pending_by_worker: dict[str, deque] = {}
        self._orphans: dict[str, list[FFTRequest]] = {}
        self._worker_state_lock = threading.Lock()
        self._worker_seq = 0
        self._watchdog: Optional[threading.Thread] = None
        self._stop_event = threading.Event()

    # --- lifecycle ---------------------------------------------------------
    def start(self) -> "FFTService":
        if self._started:
            return self
        self._started = True
        self._stop_event.clear()
        with self._threads_lock:
            for i in range(self.config.workers):
                t = threading.Thread(target=self._worker_loop,
                                     name=f"fft-serve-{i}", daemon=True)
                t.start()
                self._threads.append(t)
        if self.config.watchdog_interval_s > 0:
            self._watchdog = threading.Thread(target=self._watchdog_loop,
                                              name="fft-serve-watchdog",
                                              daemon=True)
            self._watchdog.start()
        return self

    def stop(self, drain: bool = True) -> dict:
        """Shut down: close the intake, let workers drain what is queued
        (``drain=False`` fails queued requests instead), join, and return
        the final metrics snapshot (``worker_errors`` / ``wedged_workers``
        included).

        Bounded: each worker gets at most ``join_timeout_s`` and the drain
        as a whole at most ``drain_timeout_s`` — when the budget runs out,
        still-queued requests are failed (so a still-feeding producer can't
        hold shutdown hostage) and any worker that *still* won't join is
        reported wedged via :class:`WorkerWedged` rather than silently
        abandoned."""
        self._stop_event.set()           # watchdog: no more restarts
        if self._watchdog is not None:
            self._watchdog.join(timeout=5.0)
            self._watchdog = None
        if not drain:
            failed = []
            while True:
                req = self.queue.get(timeout=0)
                if req is None:
                    break
                failed.append(req)
            for req in failed:
                self._fail(req, ServeError("service stopped"))
        self.queue.close()
        deadline = time.perf_counter() + self.config.drain_timeout_s
        with self._threads_lock:
            threads = list(self._threads)
        for t in threads:
            budget = min(self.config.join_timeout_s,
                         deadline - time.perf_counter())
            t.join(timeout=max(0.0, budget))
        still = [t for t in threads if t.is_alive()]
        if still and drain:
            # drain budget blown: shed the remaining queue so the workers
            # can reach their shutdown signal, then give one last grace join
            while True:
                req = self.queue.get(timeout=0)
                if req is None:
                    break
                self._fail(req, ServeError(
                    f"service stopping: drain deadline "
                    f"({self.config.drain_timeout_s:.0f}s) exceeded"))
            for t in still:
                t.join(timeout=1.0)
            still = [t for t in still if t.is_alive()]
        wedged = [t.name for t in still]
        if wedged:
            self.metrics.on_wedge(len(wedged))
        with self._threads_lock:
            self._threads.clear()
        self._started = False
        snap = self.report()
        snap["wedged_workers"] = wedged
        if wedged:
            raise WorkerWedged(
                f"{len(wedged)} worker(s) failed to join within "
                f"join_timeout_s={self.config.join_timeout_s:.0f}: "
                f"{', '.join(wedged)}", snapshot=snap)
        return snap

    def __enter__(self) -> "FFTService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # --- intake ------------------------------------------------------------
    def submit(self, payload: np.ndarray, kind: str = "Outplace_Complex",
               precision: Optional[str] = None, rank: Optional[int] = None,
               timeout_ms: Optional[float] = None, block: bool = True,
               block_timeout: Optional[float] = None) -> FFTRequest:
        """Enqueue one forward-FFT job; returns its future.

        ``block=False`` sheds load instead of waiting on a full queue
        (raises :class:`QueueFull`).  ``timeout_ms`` overrides the service
        default deadline for this request.
        """
        if not self._started:
            raise ServeError("service not started (use 'with FFTService(...)'"
                             " or call start())")
        if timeout_ms is None:
            timeout_ms = self.config.timeout_ms
        req = make_request(payload, kind=kind, precision=precision,
                           rank=rank, timeout_ms=timeout_ms,
                           retries=self.config.max_retries)
        if req.rows > self.config.max_batch:
            raise ServeError(
                f"request rows {req.rows} exceed max_batch "
                f"{self.config.max_batch}")
        self.metrics.on_submit()
        try:
            self.queue.put(req, block=block, timeout=block_timeout)
        except QueueFull:
            self.metrics.on_shed()
            raise
        return req

    def submit_many(self, payloads, kind: str = "Outplace_Complex",
                    precision: Optional[str] = None,
                    rank: Optional[int] = None,
                    timeout_ms: Optional[float] = None, block: bool = True,
                    block_timeout: Optional[float] = None
                    ) -> list[FFTRequest]:
        """Enqueue a burst of jobs in one shot (single queue lock + one
        worker wakeup, vs a lock/notify/GIL-handoff per ``submit``) —
        all-or-nothing on a full queue.  All payloads share the kind /
        precision / deadline; returns the request futures in order."""
        if not self._started:
            raise ServeError("service not started (use 'with FFTService(...)'"
                             " or call start())")
        if timeout_ms is None:
            timeout_ms = self.config.timeout_ms
        reqs = [make_request(p, kind=kind, precision=precision, rank=rank,
                             timeout_ms=timeout_ms,
                             retries=self.config.max_retries)
                for p in payloads]
        for req in reqs:
            if req.rows > self.config.max_batch:
                raise ServeError(
                    f"request rows {req.rows} exceed max_batch "
                    f"{self.config.max_batch}")
        self.metrics.on_submit(len(reqs))
        try:
            self.queue.put_many(reqs, block=block, timeout=block_timeout)
        except QueueFull:
            self.metrics.on_shed(len(reqs))
            raise
        return reqs

    def prewarm(self, extents, kind: str = "Outplace_Complex",
                precision: str = "float") -> int:
        """Compile the executables this plan's traffic can hit — every pow2
        batch bucket up to ``max_batch`` — before opening the doors, so
        steady-state percentiles measure serving, not XLA compiles.
        Returns the number of bucket executables now warm."""
        batch = Batch(key=(tuple(int(v) for v in extents), kind, precision))
        n, bucket = 0, 1
        while bucket <= self.config.max_batch:
            self._executable(batch, bucket)
            n += 1
            if not self.config.bucket_batches:
                break   # unbucketed rows are unbounded; warm bucket 1 only
            bucket *= 2
        return n

    # --- worker loop -------------------------------------------------------
    def _worker_loop(self) -> None:
        name = threading.current_thread().name
        pending: deque[_Inflight] = deque()
        with self._worker_state_lock:
            self._pending_by_worker[name] = pending
        batch: Optional[Batch] = None
        try:
            while True:
                batch = None
                # With work in flight, poll without blocking so an idle
                # queue retires batches instead of stalling them behind
                # the inflight threshold.
                batch = self._coalescer.next_batch(
                    poll_ms=0.0 if pending else 50.0)
                if batch is None:
                    if pending:
                        self._retire(pending.popleft())
                        continue
                    if self.queue.closed:
                        break
                    continue
                inflight = self._dispatch(batch)
                batch = None
                if inflight is not None:
                    pending.append(inflight)
                while len(pending) >= self.config.inflight:
                    self._retire(pending.popleft())
        except WorkerKilled as e:
            # dirty death: leave the current batch and the pending registry
            # behind for the watchdog to fail + restart — exactly what a
            # real thread-killing failure would look like
            with self._worker_state_lock:
                self._orphans[name] = (list(batch.requests)
                                       if batch is not None else [])
            self._worker_errors.append(e)
            return
        except BaseException as e:      # defensive: never die silently
            self._worker_errors.append(e)
        while pending:
            self._retire(pending.popleft())
        with self._worker_state_lock:
            self._pending_by_worker.pop(name, None)

    # --- watchdog ----------------------------------------------------------
    def _watchdog_loop(self) -> None:
        """Supervise the workers: a thread that died while the service is
        live gets its in-flight requests failed cleanly (their futures
        complete with an error instead of hanging) and is replaced."""
        while not self._stop_event.wait(self.config.watchdog_interval_s):
            with self._threads_lock:
                threads = list(self._threads)
            for t in threads:
                if t.is_alive():
                    continue
                if self.queue.closed or self._stop_event.is_set():
                    continue    # clean shutdown exits are not deaths
                self._restart_worker(t)

    def _restart_worker(self, dead: threading.Thread) -> None:
        with self._worker_state_lock:
            orphans = self._orphans.pop(dead.name, [])
            pending = self._pending_by_worker.pop(dead.name, None)
        if pending:
            orphans = orphans + [req for inf in pending
                                 for req in inf.batch.requests]
        for req in orphans:
            if not req.done():
                self._fail(req, ServeError(
                    f"worker {dead.name} died with request {req.rid} in "
                    f"flight; failed by watchdog"))
        with self._threads_lock:
            if dead in self._threads:
                self._threads.remove(dead)
            self._worker_seq += 1
            nt = threading.Thread(target=self._worker_loop,
                                  name=f"fft-serve-r{self._worker_seq}",
                                  daemon=True)
            self._threads.append(nt)
        self.metrics.on_worker_restart()
        nt.start()

    # --- fault injection ---------------------------------------------------
    def _apply_faults(self, site: str, backend: str, batch: Batch) -> list:
        """Fire any matching FaultPlan rules at ``site``.  Sleeps are
        applied here; ``kill_worker`` raises :class:`WorkerKilled` (a
        BaseException — it escapes the engine's batch error handling);
        ``compile_error`` raises inline (the build site calls this from
        inside the executable builder).  Error/corruption rules for the
        execute site are returned for the caller to apply."""
        if self.fault_plan is None:
            return []
        rules = self.fault_plan.check(
            site, backend=backend, extents=batch.extents, kind=batch.kind,
            rids=[r.rid for r in batch.requests])
        if rules:
            self.metrics.on_fault(len(rules))
        for rule in rules:
            if rule.fault in ("transfer_stall", "latency_spike"):
                time.sleep(rule.stall_ms / 1e3)
            elif rule.fault == "kill_worker":
                raise WorkerKilled(
                    f"injected worker kill at {site} "
                    f"({format_extents(batch.extents)})")
            elif rule.fault == "compile_error":
                raise FaultInjected(
                    f"injected compile error: {backend} @ "
                    f"{format_extents(batch.extents)}")
        return rules

    # --- dispatch / retire -------------------------------------------------
    def _dispatch(self, batch: Batch) -> Optional[_Inflight]:
        now = time.perf_counter()
        live: list[FFTRequest] = []
        for req in batch.requests:
            req.t_dispatch = now
            req.coalesced = batch.n_requests
            if req.expired(now):
                limit = ((req.deadline - req.t_enqueue) * 1e3
                         if req.deadline is not None else float("nan"))
                self._fail(req, RequestTimeout(
                    f"request {req.rid} expired in queue: waited "
                    f"{req.queue_ms:.1f} ms against a {limit:.0f} ms "
                    f"deadline (queue depth {len(self.queue)}/"
                    f"{self.queue.maxsize})"), timeout=True)
            else:
                live.append(req)
        if not live:
            return None
        batch.requests = live
        rows = batch.rows
        bucket = next_pow2(rows) if self.config.bucket_batches else rows
        cand: Optional[Candidate] = None
        try:
            cand, compiled = self._executable(batch, bucket)
            self._apply_faults("dispatch", cand.backend, batch)
            staged = self._stage(batch, bucket)
            import jax
            device_in = jax.device_put(staged)
            out = compiled(device_in)   # async dispatch: do not block here
        except Exception as e:
            self._handle_failure(batch, e, cand)
            return None
        self.metrics.on_batch(batch.n_requests, rows, bucket - rows)
        spans = []
        r0 = 0
        for req in live:
            spans.append((r0, r0 + req.rows))
            r0 += req.rows
        return _Inflight(batch, out, spans, now, cand)

    def _retire(self, inflight: _Inflight) -> None:
        batch = inflight.batch
        cand = inflight.cand
        try:
            rules = self._apply_faults(
                "execute", cand.backend if cand else "*", batch)
            for rule in rules:
                if rule.fault == "execute_error":
                    raise FaultInjected(
                        f"injected execute error: "
                        f"{cand.key() if cand else '?'} @ "
                        f"{format_extents(batch.extents)}")
            import jax
            jax.block_until_ready(inflight.out)
            host_out = np.asarray(inflight.out)
            nan_rules = [r for r in rules if r.fault == "nan_output"]
            if nan_rules:
                host_out = np.array(host_out)   # corrupt a private copy
                for rule in nan_rules:
                    if rule.rid is None:
                        host_out[:] = np.nan
                    else:
                        for req, (r0, r1) in zip(batch.requests,
                                                 inflight.row_spans):
                            if req.rid == rule.rid:
                                host_out[r0:r1] = np.nan
        except Exception as e:
            self._handle_failure(batch, e, cand)
            return
        now = time.perf_counter()
        problem = Problem(batch.extents, batch.kind, batch.precision)
        any_ok = False
        for req, (r0, r1) in zip(batch.requests, inflight.row_spans):
            if req.expired(now):
                limit = ((req.deadline - req.t_enqueue) * 1e3
                         if req.deadline is not None else float("nan"))
                self._fail(req, RequestTimeout(
                    f"request {req.rid} missed its {limit:.0f} ms deadline "
                    f"(completed {req.latency_ms:.1f} ms after enqueue)"),
                    timeout=True)
                continue
            out = host_out[r0:r1]
            if self.config.probe_output and not np.isfinite(out).all():
                # 'computed garbage' failure mode: per-request, so a poison
                # payload in a coalesced batch fails alone
                self._retry_or_fail(req, ServeError(
                    f"non-finite output from "
                    f"{cand.key() if cand else 'engine'} for request "
                    f"{req.rid}"))
                continue
            req._complete(result=out)
            any_ok = True
            self.metrics.on_complete(req.latency_ms, req.queue_ms,
                                     req.signal_bytes,
                                     retried=req.attempts > 0)
            self._record(req, success=True)
        if any_ok and cand is not None:
            # a delivered batch is the half-open probe's success signal
            self.breaker.record_success(breaker_key(cand.backend, problem))

    # --- failure handling --------------------------------------------------
    def _handle_failure(self, batch: Batch, err: Exception,
                        cand: Optional[Candidate]) -> None:
        """A batch failed at dispatch or execute.  Book the failure against
        the candidate's breaker entry, then isolate: multi-request batches
        bisect (one poison request must not fail its batchmates), single
        requests retry with backoff or fail cleanly."""
        problem = Problem(batch.extents, batch.kind, batch.precision)
        if cand is not None:
            state = self.breaker.record_failure(
                breaker_key(cand.backend, problem))
            if state == CircuitBreaker.OPEN \
                    and not (cand.backend == "xla" and not cand.axes):
                self._record_demotion(problem, cand.backend)
        reqs = list(batch.requests)
        if len(reqs) > 1 and self.config.bisect_batches:
            self.metrics.on_bisect()
            mid = len(reqs) // 2
            for half in (reqs[:mid], reqs[mid:]):
                sub = Batch(key=batch.key, requests=list(half))
                inflight = self._dispatch(sub)
                if inflight is not None:
                    self._retire(inflight)   # synchronous: bounded depth
        else:
            for req in reqs:
                self._retry_or_fail(req, err)

    def _retry_or_fail(self, req: FFTRequest, err: Exception) -> None:
        retryable = getattr(err, "retryable", True)
        if retryable and req.retries_left > 0 and not self.queue.closed \
                and not req.expired():
            req.retries_left -= 1
            req.attempts += 1
            self.metrics.on_retry()
            timer = threading.Timer(self._backoff_s(req), self._requeue,
                                    args=(req,))
            timer.daemon = True
            timer.start()
            return
        if isinstance(err, RequestTimeout):
            self._fail(req, err, timeout=True)
        elif isinstance(err, ServeError):
            self._fail(req, err)
        else:
            self._fail(req, ServeError(
                f"engine error: {type(err).__name__}: {err}"))

    def _backoff_s(self, req: FFTRequest) -> float:
        """Jittered exponential backoff: doubles per attempt up to the cap,
        scaled by a deterministic per-(request, attempt) factor in
        [0.5, 1.0) so retry storms decorrelate reproducibly."""
        base = self.config.backoff_base_ms * (2 ** max(0, req.attempts - 1))
        jitter = random.Random((req.rid << 8) ^ req.attempts).uniform(0.5, 1.0)
        return min(base, self.config.backoff_max_ms) * jitter / 1e3

    def _requeue(self, req: FFTRequest) -> None:
        if not self.queue.requeue(req):
            self._fail(req, ServeError(
                f"request {req.rid} dropped: service stopped before its "
                f"retry could run"))

    def _record_demotion(self, problem: Problem, backend: str) -> None:
        """Persist an opened quarantine to wisdom (best-effort) so warm
        sessions skip the known-bad pick outright."""
        self.metrics.on_demotion()
        if self.wisdom is None:
            return
        try:
            self.wisdom.record_demotion(problem, backend)
            self.wisdom.save()
        except Exception as e:       # persistence must never kill serving
            self._worker_errors.append(e)

    # --- plan + staging ----------------------------------------------------
    def _cost_model_cm(self):
        """Scoped install of the config's fitted coefficient table (no-op
        without one): request-time plans and fallback-chain rankings both
        run under the per-device fit instead of the hand-written defaults."""
        from contextlib import nullcontext

        if not self.config.costmodel:
            return nullcontext()
        from ..core.costmodel import model_for_device, use_model

        if self._cost_model is None:
            self._cost_model = model_for_device(self.session.device_kind,
                                                self.config.costmodel)
        return use_model(self._cost_model)

    def _plan_candidate(self, problem: Problem) -> Candidate:
        if self.config.backend is not None:
            return Candidate(self.config.backend)
        rigor = PlanRigor(self.config.rigor)
        cache = self.session.plan_cache
        key = PlanCache.plan_key(self.session.device_kind, problem, rigor,
                                 scope="serve")
        with self._cost_model_cm():
            plan, _ = cache.plan(
                key, lambda: make_plan(problem, rigor, wisdom=self.wisdom))
        if plan is None:
            raise ServeError(f"NULL plan for {problem.signature()} "
                             f"(wisdom miss under wisdom_only rigor)")
        return plan.candidate

    def _plan_chain(self, problem: Problem) -> list[Candidate]:
        """The ordered candidates this problem may be served with: the
        planner's pick first, then — when fallback is on — every other
        feasible candidate by modeled cost, ``xla`` guaranteed present."""
        top = self._plan_candidate(problem)
        if not self.config.fallback or self.config.backend is not None:
            # pinned backends never fall back: a per-library bench must fail
            # honestly rather than quietly serve another library's numbers
            return [top]
        ckey = problem.signature()
        with self._chains_lock:
            rest = self._chains.get(ckey)
        if rest is None:
            with self._cost_model_cm():
                rest = fallback_chain(problem)
            with self._chains_lock:
                self._chains[ckey] = rest
        return [top] + [c for c in rest if c.key() != top.key()]

    def _executable(self, batch: Batch, bucket: int
                    ) -> tuple[Candidate, Any]:
        """The AOT-compiled, donated executable for this plan at the bucket
        batch size — built once per (plan, bucket) via the shared
        single-flight PlanCache.  Walks the fallback chain: a candidate
        whose build fails (or that is quarantined / wisdom-demoted) demotes
        to the next, and the terminal candidate is tried regardless."""
        import jax
        from ..core.clients.jax_fft import forward_fn
        from ..core.trace import executable_name, named

        problem = Problem(batch.extents, batch.kind, batch.precision,
                          batch=bucket)
        chain = self._plan_chain(problem)
        demoted = (self.wisdom.demoted(problem)
                   if self.wisdom is not None else frozenset())
        last_err: Optional[Exception] = None
        for i, cand in enumerate(chain):
            terminal = i == len(chain) - 1
            is_xla = cand.backend == "xla" and not cand.axes
            bkey = breaker_key(cand.backend, problem)
            if not terminal and not is_xla:
                if cand.backend in demoted or not self.breaker.allows(bkey):
                    continue     # quarantined: skip without a fresh build
            key = PlanCache.executable_key(self.session.device_kind, problem,
                                           cand, "serve_forward")

            def build(cand=cand):
                self._apply_faults("build", cand.backend, batch)
                # Donation only pays off when XLA can alias input to
                # output — c2c transforms, where shapes and dtypes match.
                # For r2c the real input can never back the complex output,
                # and donating it just emits a warning per compile.
                donate = (0,) if problem.complex_input else ()
                fn = jax.jit(named(forward_fn(problem, cand),
                                   executable_name(problem, cand, "fwd")),
                             donate_argnums=donate)
                spec = jax.ShapeDtypeStruct((bucket, *batch.extents),
                                            problem.input_dtype.name)
                return fn.lower(spec).compile()

            try:
                compiled, _, _ = self.session.plan_cache.executable(key, build)
            except Exception as e:
                last_err = e
                state = self.breaker.record_failure(bkey)
                if state == CircuitBreaker.OPEN and not is_xla:
                    self._record_demotion(problem, cand.backend)
                else:
                    self.metrics.on_demotion()
                continue
            return cand, compiled
        if last_err is not None:
            raise last_err
        raise ServeError(
            f"no live plan candidate for {problem.signature()}: every "
            f"backend in the fallback chain is quarantined")

    def _stage(self, batch: Batch, bucket: int) -> np.ndarray:
        """Copy request payloads into one of two alternating host staging
        buffers (double buffering: buffer k-1 may still be uploading while
        we fill buffer k)."""
        problem = Problem(batch.extents, batch.kind, batch.precision)
        skey = (batch.key, bucket)
        with self._staging_lock:
            bufs = self._staging.get(skey)
            if bufs is None:
                shape = (bucket, *batch.extents)
                bufs = [np.zeros(shape, dtype=problem.input_dtype)
                        for _ in range(2)]
                self._staging[skey] = bufs
                self._staging_flip[skey] = 0
            flip = self._staging_flip[skey]
            self._staging_flip[skey] = 1 - flip
        buf = bufs[flip]
        r0 = 0
        for req in batch.requests:
            buf[r0:r0 + req.rows] = req.payload
            r0 += req.rows
        return buf

    # --- bookkeeping -------------------------------------------------------
    def _fail(self, req: FFTRequest, err: ServeError,
              timeout: bool = False) -> None:
        req._complete(error=err)
        self.metrics.on_error(timeout=timeout)
        self._record(req, success=False, error=str(err))

    def _record(self, req: FFTRequest, success: bool,
                error: str = "") -> None:
        if not self.config.record_requests:
            return
        try:
            device = self.session.device_kind
        except Exception:
            device = "?"
        row = Row(library="ServeFFT", device=device,
                  extents=format_extents(req.extents),
                  rank=len(req.extents),
                  extent_class=classify(req.extents),
                  precision=req.precision, kind=req.kind,
                  rigor=self.config.rigor, run=req.rid, op="serve_request",
                  time_ms=req.latency_ms if success else 0.0,
                  bytes=req.signal_bytes, success=success, error=error)
        with self._rows_lock:
            self._rows.append(row)

    def rows(self) -> list[Row]:
        """Per-request result rows (op ``serve_request``; failed requests
        carry their error) — feed them to a ResultSet for the shared
        percentile aggregation."""
        with self._rows_lock:
            return list(self._rows)

    def result_set(self):
        from ..core.results import columns_for
        from ..core.suite import ResultSet

        return ResultSet(self.rows(), columns_for(False),
                         plan_stats=self.session.plan_cache.stats)

    def report(self) -> dict:
        """Metrics snapshot: the shared plan cache's counters, the
        quarantine (circuit breaker) states, worker errors, and — when a
        FaultPlan is attached — the injected-fault accounting."""
        snap = self.metrics.snapshot(
            plan_stats=self.session.plan_cache.stats,
            quarantine=self.breaker.snapshot())
        snap["worker_errors"] = [f"{type(e).__name__}: {e}"
                                 for e in self._worker_errors]
        if self.fault_plan is not None:
            snap["faults"] = self.fault_plan.snapshot()
        return snap
