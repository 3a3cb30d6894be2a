"""FFT serving layer: queue/coalescer mechanics, end-to-end correctness
against numpy, timeout/error robustness, traffic replay determinism, the
percentile plumbing, and concurrency hammers for the shared PlanCache and
wisdom store."""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

from repro.core.client import Problem
from repro.core.candidates import Candidate
from repro.core.plan import Plan, PlanCache, PlanRigor
from repro.core.results import (aggregate_rows, percentile,
                                percentile_summary, Row)
from repro.core.wisdom import Wisdom
from repro.serve import (Coalescer, FaultPlan, FFTService, QueueFull,
                         RequestQueue, RequestTimeout, ServeConfig,
                         ServeError, TrafficSpec, WorkerWedged, chaos_replay,
                         make_request, replay)


def _payload(ext=(64,), rows=None, dtype=np.complex64, seed=0):
    """A transform input: shape ``ext``, or ``(rows, *ext)`` when a request
    should occupy several batch rows (submit those with ``rank=len(ext)``)."""
    rng = np.random.default_rng(seed)
    shape = ext if rows is None else (rows, *ext)
    x = rng.standard_normal(shape)
    if np.issubdtype(dtype, np.complexfloating):
        x = x + 1j * rng.standard_normal(shape)
    return x.astype(dtype)


def _service(**kw):
    kw.setdefault("coalesce_window_ms", 2.0)
    kw.setdefault("max_batch", 8)
    return FFTService(config=ServeConfig(**kw))


# ---------------------------------------------------------------------------
# percentile math (results.py satellite)
# ---------------------------------------------------------------------------
def test_percentile_matches_numpy_linear_interpolation():
    rng = np.random.default_rng(42)
    vals = list(rng.standard_normal(37) * 10)
    for q in (0, 25, 50, 75, 95, 99, 100):
        assert percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)), rel=1e-12)


def test_percentile_summary_keys_and_single_sample():
    s = percentile_summary([3.0])
    assert s == {"p50": 3.0, "p95": 3.0, "p99": 3.0}
    assert percentile([1.0, 2.0], 50) == pytest.approx(1.5)


def test_aggregate_rows_percentiles_opt_in_preserves_default_shape():
    rows = [Row(library="L", device="d", extents="8", rank=1,
                extent_class="powerof2", precision="float",
                kind="Outplace_Complex", rigor="estimate", run=i,
                op="execute_forward", time_ms=float(i + 1), bytes=0)
            for i in range(10)]
    default = aggregate_rows(rows, op="execute_forward")
    assert len(default[0]) == 9                      # legacy 9-tuple intact
    wide = aggregate_rows(rows, op="execute_forward", percentiles=True)
    (*key, mean, sd, p50, p95, p99, n) = wide[0]
    assert n == 10 and mean == pytest.approx(5.5)
    assert p50 == pytest.approx(np.percentile(range(1, 11), 50))
    assert p99 == pytest.approx(np.percentile(range(1, 11), 99))


# ---------------------------------------------------------------------------
# request + queue mechanics
# ---------------------------------------------------------------------------
def test_make_request_infers_precision_and_rank():
    req = make_request(_payload((16,), dtype=np.complex128))
    assert req.precision == "double" and req.extents == (16,)
    assert req.rows == 1
    req = make_request(_payload((4, 8), rows=2), rank=2)
    assert req.extents == (4, 8) and req.rows == 2
    with pytest.raises(ValueError):
        make_request(np.zeros((4,), np.int32))


def test_queue_backpressure_and_load_shed():
    q = RequestQueue(maxsize=2)
    q.put(make_request(_payload()))
    q.put(make_request(_payload()))
    with pytest.raises(QueueFull):
        q.put(make_request(_payload()), block=False)
    with pytest.raises(QueueFull):
        q.put(make_request(_payload()), timeout=0.01)
    assert q.get(timeout=0.01) is not None
    q.put(make_request(_payload()), block=False)    # space again


def test_queue_put_many_is_all_or_nothing():
    q = RequestQueue(maxsize=3)
    q.put_many([make_request(_payload()) for _ in range(3)])
    with pytest.raises(QueueFull):
        q.put_many([make_request(_payload())], block=False)
    assert len(q) == 3
    q.close()
    with pytest.raises(QueueFull):
        q.put_many([make_request(_payload())])


def test_queue_close_drains_then_none():
    q = RequestQueue()
    q.put(make_request(_payload()))
    q.close()
    assert q.get(timeout=0.1) is not None   # drain what remains
    assert q.get(timeout=0.1) is None       # then the shutdown signal


def test_coalescer_groups_same_plan_only():
    q = RequestQueue()
    a1 = make_request(_payload((32,)))
    b = make_request(_payload((64,)))
    a2 = make_request(_payload((32,)))
    for r in (a1, b, a2):
        q.put(r)
    c = Coalescer(q, window_ms=0.0, max_rows=8)
    batch = c.next_batch()
    assert [r.rid for r in batch.requests] == [a1.rid, a2.rid]
    assert batch.rows == 2 and batch.extents == (32,)
    assert c.next_batch().requests == [b]


def test_coalescer_respects_row_budget():
    q = RequestQueue()
    reqs = [make_request(_payload((16,), rows=2), rank=1) for _ in range(4)]
    for r in reqs:
        q.put(r)
    c = Coalescer(q, window_ms=0.0, max_rows=5)
    batch = c.next_batch()
    assert batch.rows == 4 and batch.n_requests == 2   # 3rd would exceed 5
    assert c.next_batch().rows == 4


def test_serial_fifo_when_coalescing_disabled():
    q = RequestQueue()
    reqs = [make_request(_payload((16,))) for _ in range(3)]
    for r in reqs:
        q.put(r)
    c = Coalescer(q, window_ms=0.0, max_rows=1)
    got = [c.next_batch().requests[0].rid for _ in range(3)]
    assert got == [r.rid for r in reqs]


# ---------------------------------------------------------------------------
# end-to-end service correctness
# ---------------------------------------------------------------------------
def test_service_burst_matches_numpy_and_coalesces():
    xs = [_payload((128,), seed=i) for i in range(6)]
    with _service(coalesce_window_ms=10.0) as svc:
        reqs = [svc.submit(x) for x in xs]
        outs = [np.asarray(r.result(timeout=300)) for r in reqs]
    for x, y in zip(xs, outs):
        ref = np.fft.fft(x)
        assert np.max(np.abs(y[0] - ref)) / np.max(np.abs(ref)) < 1e-3
    rep = svc.report()
    assert rep["completed"] == 6 and rep["errors"] == 0
    assert rep["batches"] < 6 and rep["coalesce_rate"] > 0
    assert {"p50", "p95", "p99"} <= set(rep["latency_ms"])


def test_service_mixed_shapes_kinds_precisions():
    jobs = [
        (_payload((64,), dtype=np.complex64), "Outplace_Complex"),
        (_payload((32, 16), dtype=np.complex128), "Outplace_Complex"),
        (_payload((64,), dtype=np.float32), "Outplace_Real"),
        (_payload((128,), dtype=np.float64), "Outplace_Real"),
    ]
    with _service() as svc:
        reqs = [svc.submit(x, kind=k) for x, k in jobs]
        outs = [np.asarray(r.result(timeout=300)) for r in reqs]
    for (x, kind), y in zip(jobs, outs):
        if kind == "Outplace_Complex":
            ref = np.fft.fftn(x.astype(np.complex128))
        else:
            ref = np.fft.rfftn(x.astype(np.float64))
        tol = 1e-3 if x.dtype.itemsize <= 8 else 1e-9
        assert np.max(np.abs(y[0] - ref)) / np.max(np.abs(ref)) < tol


def test_submit_many_returns_futures_in_order():
    xs = [_payload((32,), seed=i) for i in range(5)]
    with _service() as svc:
        reqs = svc.submit_many(xs)
        outs = [np.asarray(r.result(timeout=300)) for r in reqs]
    for x, y in zip(xs, outs):
        assert np.allclose(y[0], np.fft.fft(x), rtol=1e-3, atol=1e-3)


def test_request_timeout_fails_cleanly_and_worker_survives():
    with _service(timeout_ms=0.0) as svc:      # every request pre-expired
        req = svc.submit(_payload((32,)))
        with pytest.raises(RequestTimeout):
            req.result(timeout=60)
        # the worker must still serve fresh (un-expired) work
        ok = svc.submit(_payload((32,)), timeout_ms=60_000)
        assert ok.result(timeout=300) is not None
    rep = svc.report()
    assert rep["timeouts"] == 1 and rep["completed"] == 1
    failed = [r for r in svc.rows() if not r.success]
    assert len(failed) == 1 and "expired" in failed[0].error


def test_engine_error_fails_batch_not_worker():
    with _service(backend="fft2_pallas") as svc:   # rank-2 only: 1D must fail
        bad = svc.submit(_payload((32,)))
        with pytest.raises(ServeError, match="engine error"):
            bad.result(timeout=300)
        good = svc.submit(_payload((8, 8), dtype=np.complex64))
        assert good.result(timeout=300) is not None
    assert svc.report()["errors"] == 1


def test_submit_validates_rows_and_started():
    svc = _service(max_batch=2)
    with pytest.raises(ServeError, match="not started"):
        svc.submit(_payload((16,)))
    with svc:
        with pytest.raises(ServeError, match="exceed max_batch"):
            svc.submit(_payload((16,), rows=4), rank=1)


def test_prewarm_compiles_bucket_ladder():
    with _service(max_batch=8) as svc:
        n = svc.prewarm((32,))
        assert n == 4                         # buckets 1, 2, 4, 8
        stats = svc.session.plan_cache.stats
        misses0 = stats.misses
        svc.submit(_payload((32,))).result(timeout=300)
        assert stats.misses == misses0        # served entirely warm


def test_serve_config_roundtrip_and_validation():
    cfg = ServeConfig(max_batch=4, workers=2, backend="xla")
    assert ServeConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError, match="unknown ServeConfig"):
        ServeConfig.from_dict({"max_batch": 4, "nope": 1})
    with pytest.raises(ValueError):
        ServeConfig(max_batch=0)
    with pytest.raises(ValueError):
        ServeConfig(rigor="bogus")


# ---------------------------------------------------------------------------
# fault tolerance: fallback, retry, bisection, watchdog, wedge detection
# ---------------------------------------------------------------------------
def test_engine_falls_back_past_compile_fault_and_persists_demotion(tmp_path):
    from repro.core.plan import fallback_chain
    from repro.core.client import Problem

    top = fallback_chain(Problem((64,), "Outplace_Complex", "float")).pop(0)
    wisdom = Wisdom(str(tmp_path / "wisdom.json"), device_kind="cpu")
    svc = FFTService(config=ServeConfig(max_batch=8, breaker_threshold=1),
                     wisdom=wisdom,
                     fault_plan=FaultPlan([{"fault": "compile_error",
                                            "backend": top.backend}]))
    with svc:
        x = _payload((64,))
        out = np.asarray(svc.submit(x).result(timeout=300))
    assert np.allclose(out[0], np.fft.fft(x), rtol=1e-3, atol=1e-3)
    rep = svc.report()
    assert rep["completed"] == 1 and rep["errors"] == 0
    assert rep["demotions"] >= 1 and rep["faults_injected"] >= 1
    # the quarantine shows up in the report and survived to wisdom on disk
    assert any(k.startswith(top.backend) and v["state"] == "open"
               for k, v in rep["quarantine"].items())
    fresh = Wisdom(str(tmp_path / "wisdom.json"), device_kind="cpu")
    assert top.backend in fresh.demoted(
        Problem((64,), "Outplace_Complex", "float"))


def test_poison_request_fails_alone_batchmates_succeed():
    xs = [_payload((32,), seed=i) for i in range(4)]
    reqs = [make_request(x) for x in xs]
    poison = reqs[1]
    svc = _service(coalesce_window_ms=20.0)
    svc.fault_plan = FaultPlan([{"fault": "execute_error",
                                 "rid": poison.rid}])
    with svc:
        svc.queue.put_many(reqs)      # one coalesced batch, rids known
        with pytest.raises(ServeError, match="injected execute error"):
            poison.result(timeout=300)
        for i, req in enumerate(reqs):
            if req is poison:
                continue
            out = np.asarray(req.result(timeout=300))
            ref = np.fft.fft(xs[i])
            assert np.max(np.abs(out[0] - ref)) / np.max(np.abs(ref)) < 1e-2
    rep = svc.report()
    assert rep["completed"] == 3 and rep["errors"] == 1
    assert rep["bisections"] >= 2     # 4 -> 2+2 -> 1+1: poison isolated


def test_transient_fault_recovered_by_retry():
    svc = _service(faults=({"fault": "execute_error", "times": 2},),
                   max_retries=3)
    with svc:
        req = svc.submit(_payload((32,)))
        out = np.asarray(req.result(timeout=300))
    assert out is not None and req.ok and req.attempts >= 1
    rep = svc.report()
    assert rep["completed"] == 1 and rep["errors"] == 0
    assert rep["retries"] >= 1 and rep["retry_successes"] >= 1
    assert rep["faults_injected"] == 2


def test_kill_worker_watchdog_restarts_and_service_survives():
    svc = _service(faults=({"fault": "kill_worker", "times": 1},),
                   watchdog_interval_s=0.05)
    with svc:
        doomed = svc.submit(_payload((32,)))
        with pytest.raises(ServeError, match="failed by watchdog"):
            doomed.result(timeout=60)
        ok = svc.submit(_payload((32,)))     # the restarted worker serves it
        assert ok.result(timeout=300) is not None
    rep = svc.report()
    assert rep["worker_restarts"] >= 1 and rep["completed"] == 1
    assert any("WorkerKilled" in e for e in rep["worker_errors"])
    assert rep["wedged"] == 0


def test_stop_reports_wedged_worker():
    svc = _service(faults=({"fault": "transfer_stall", "stall_ms": 3000.0,
                            "times": 1},),
                   join_timeout_s=0.2, drain_timeout_s=0.2,
                   watchdog_interval_s=0.0)
    svc.start()
    req = svc.submit(_payload((32,)))
    time.sleep(0.1)                   # let the worker enter the stall
    with pytest.raises(WorkerWedged, match="failed to join") as ei:
        svc.stop()
    assert ei.value.snapshot["wedged_workers"]
    assert ei.value.snapshot["wedged"] >= 1
    req.result(timeout=60)            # the stalled worker still finishes it


def test_failure_messages_carry_actionable_context():
    q = RequestQueue(maxsize=2)
    q.put(make_request(_payload()))
    q.put(make_request(_payload()))
    with pytest.raises(QueueFull, match=r"2/2 requests pending"):
        q.put(make_request(_payload()), block=False)
    with pytest.raises(QueueFull, match=r"after waiting 0.01s"):
        q.put(make_request(_payload()), timeout=0.01)
    with _service(timeout_ms=0.0) as svc:
        req = svc.submit(_payload((32,)))
        with pytest.raises(RequestTimeout, match=r"0 ms deadline"):
            req.result(timeout=60)
    assert "queue depth" in str(req.error)


def test_serve_config_fault_fields_roundtrip_and_validation():
    cfg = ServeConfig(max_retries=5, breaker_threshold=2,
                      faults=({"fault": "latency_spike", "stall_ms": 1.0},))
    assert ServeConfig.from_dict(cfg.to_dict()) == cfg
    assert "faults" not in ServeConfig().to_dict()
    with pytest.raises(ValueError):
        ServeConfig(max_retries=-1)
    with pytest.raises(ValueError):
        ServeConfig(breaker_threshold=0)
    with pytest.raises(ValueError, match="unknown fault"):
        ServeConfig(faults=({"fault": "gremlins"},))


def test_chaos_replay_grades_recovery(tmp_path):
    from repro.core.plan import fallback_chain
    from repro.core.client import Problem

    top = fallback_chain(Problem((64,), "Outplace_Complex", "float")).pop(0)
    spec = TrafficSpec(extents=((64,), (32,)), requests=10, seed=11,
                       faults=({"fault": "compile_error",
                                "backend": top.backend},
                               {"fault": "execute_error", "after": 1,
                                "times": 1}))
    svc = FFTService(config=ServeConfig(coalesce_window_ms=2.0, max_batch=8,
                                        breaker_threshold=1))
    with svc:
        rep = chaos_replay(svc, spec)
    assert rep.ok, rep.violations
    assert rep.total == 10 and rep.poisoned == 0
    assert rep.clean_success_rate == 1.0
    assert rep.faults["injected"] >= 2
    assert rep.replay.service["demotions"] >= 1
    json.dumps(rep.to_dict())


# ---------------------------------------------------------------------------
# traffic replay
# ---------------------------------------------------------------------------
def test_traffic_spec_roundtrip_and_validation():
    spec = TrafficSpec(extents=("256", (64, 64)), requests=10, rate_hz=50.0)
    assert spec.extents == ((256,), (64, 64))
    assert TrafficSpec.from_dict(spec.to_dict()) == spec
    with pytest.raises(ValueError, match="unknown TrafficSpec"):
        TrafficSpec.from_dict({"requests": 5, "bogus": 1})
    with pytest.raises(ValueError):
        TrafficSpec(kinds=("Sideways_Complex",))
    with pytest.raises(ValueError):
        TrafficSpec(requests=0)


def test_traffic_schedule_deterministic_and_zipf_skewed():
    spec = TrafficSpec(extents=((32,), (64,), (128,)), requests=200, seed=9)
    tape1, tape2 = list(spec.schedule()), list(spec.schedule())
    assert tape1 == tape2
    counts = {}
    for _, ext, _, _ in tape1:
        counts[ext] = counts.get(ext, 0) + 1
    assert counts[(32,)] > counts[(128,)]     # rank-1 entry is the hot one
    # burst mode: all arrivals at t=0
    assert all(t == 0.0 for t, *_ in tape1)


def test_replay_end_to_end_report():
    spec = TrafficSpec(extents=((32,), (64,)), requests=12, rate_hz=0.0,
                       seed=5)
    with _service(coalesce_window_ms=5.0) as svc:
        rep = replay(svc, spec)
    assert rep.service["completed"] == 12
    assert rep.service["batches"] < 12        # burst traffic must coalesce
    assert {"p50", "p95", "p99"} <= set(rep.service["latency_ms"])
    assert sum(m["requests"] for m in rep.per_mix) == 12
    json.dumps(rep.to_dict())                 # report is JSON-clean


def test_replay_through_result_set_summary():
    spec = TrafficSpec(extents=((32,),), requests=6, seed=1)
    with _service() as svc:
        replay(svc, spec)
    summary = svc.result_set().summary(latency_op="serve_request")
    assert summary["latency_ms"]["n"] == 6
    assert {"p50", "p95", "p99"} <= set(summary["latency_ms"])


# ---------------------------------------------------------------------------
# ServeFFT through the ordinary suite
# ---------------------------------------------------------------------------
def test_serve_client_through_run_suite():
    from repro.core.client import Context
    from repro.core.suite import Session, SuiteSpec

    spec = SuiteSpec(clients=("ServeFFT",), extents=((64,),),
                     kinds=("Outplace_Complex", "Outplace_Real"),
                     precisions=("float",), warmups=0, repetitions=2,
                     output=None)
    rs = Session(context=Context({"serve_burst": 3})).run(spec)
    assert rs.n_failures == 0
    ops = {r.op for r in rs.rows}
    assert "execute_forward" in ops and "init_inverse" not in ops
    wide = rs.aggregate(op="execute_forward", percentiles=True)
    assert len(wide[0]) == 12                 # percentile columns present


# ---------------------------------------------------------------------------
# concurrency hammers: shared PlanCache + wisdom store
# ---------------------------------------------------------------------------
def _hammer(n_threads, fn):
    errors = []
    barrier = threading.Barrier(n_threads)

    def work(i):
        try:
            barrier.wait(timeout=30)
            fn(i)
        except Exception as e:             # surface, don't swallow
            errors.append(e)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)


def test_plan_cache_hammer_single_flight_invariants():
    cache = PlanCache()
    keys = [("exec", k) for k in range(4)]
    builds = []
    build_lock = threading.Lock()
    n_threads, per_thread = 8, 20

    def work(i):
        rng = np.random.default_rng(i)
        for _ in range(per_thread):
            key = keys[int(rng.integers(len(keys)))]

            def build():
                with build_lock:
                    builds.append(key)
                time.sleep(0.001)          # widen the race window
                return object()

            obj, _, _ = cache.executable(key, build)
            assert obj is not None

    _hammer(n_threads, work)
    # single-flight: each key built exactly once, no lost updates
    assert len(builds) == len(keys)
    assert set(builds) == set(keys)
    stats = cache.stats
    assert stats.misses == len(keys)
    assert stats.hits + stats.misses == n_threads * per_thread
    assert len(cache) == len(keys)


def test_plan_cache_hammer_plan_lookups():
    cache = PlanCache()
    problem = Problem((64,), "Outplace_Complex", "float")
    built = []

    def make():
        built.append(1)
        time.sleep(0.001)
        return Plan(problem, Candidate("xla"), PlanRigor.ESTIMATE, 0.0)

    plans = []

    def work(i):
        plan, _ = cache.plan(("plan", "k"), make)
        plans.append(plan)

    _hammer(8, work)
    assert len(built) == 1                 # one builder, 7 waiters
    assert all(p is plans[0] for p in plans)


def test_wisdom_hammer_concurrent_record_and_save(tmp_path):
    path = tmp_path / "wisdom.json"
    w = Wisdom(str(path), device_kind="cpu")
    n_threads = 6

    def work(i):
        for j in range(10):
            p = Problem((64 * (i + 1),), "Outplace_Complex", "float",
                        batch=j % 3 + 1)
            w.record(p, Candidate("xla"))
            w.save()                       # interleaved atomic merges

    _hammer(n_threads, work)
    # the file is valid JSON and a fresh load sees every key
    with open(path) as f:
        json.load(f)
    fresh = Wisdom(str(path), device_kind="cpu")
    for i in range(n_threads):
        for b in (1, 2, 3):
            p = Problem((64 * (i + 1),), "Outplace_Complex", "float", batch=b)
            assert fresh.lookup(p) is not None, p.signature()


def test_service_hammer_many_submitters_one_cache():
    """N producer threads against one service: shared PlanCache misses stay
    bounded by the distinct (plan, bucket) set and every request completes."""
    n_threads, per_thread = 4, 5
    results = {}
    lock = threading.Lock()
    with _service(coalesce_window_ms=1.0, max_batch=8) as svc:
        def work(i):
            for j in range(per_thread):
                x = _payload((32,) if i % 2 else (64,), seed=i * 100 + j)
                out = np.asarray(svc.submit(x).result(timeout=300))
                ref = np.fft.fft(x)
                with lock:
                    results[(i, j)] = np.max(np.abs(out[0] - ref))

        _hammer(n_threads, work)
    assert len(results) == n_threads * per_thread
    assert all(v < 1e-2 for v in results.values())
    rep = svc.report()
    assert rep["completed"] == n_threads * per_thread
    assert rep["errors"] == 0 and rep["timeouts"] == 0
    # 2 plans x pow2 buckets <= 8 -> at most 8 distinct executables
    assert rep["plan_cache"]["misses"] <= 8
