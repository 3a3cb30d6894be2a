"""Engine-layer tests: Runner/OpSchedule, PlanCache, client registry,
streaming result sinks, and the CLI plumbing that ties them together."""

import csv
import json

import numpy as np
import pytest

from repro.core.benchmark import Benchmark, BenchmarkConfig
from repro.core.client import Context, Problem
from repro.core.plan import PlanCache, PlanRigor
from repro.core.registry import (client_names, get_client, register_client,
                                 registered_clients)
from repro.core.results import (COLUMNS, CsvSink, JsonlSink, ResultWriter,
                                Row, columns_for, open_sink)
from repro.core.schedule import FFT_SCHEDULE, OpSchedule, OpStep, Runner
from repro.core.tree import BenchNode, build_tree
from repro.core.wisdom import Wisdom
from repro.core.clients import jax_fft as jf
from repro.core.clients.dist_fft import DistFFT1DClient, DistFFTNDClient


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------
def test_registry_discovers_builtin_clients():
    names = client_names()
    for expected in ("XlaFFT", "Stockham", "FourStep", "Bluestein",
                     "Planned", "DistFFT1D", "DistFFTND"):
        assert expected in names
    assert get_client("XlaFFT") is jf.XlaFFTClient
    assert registered_clients()["DistFFT1D"] is DistFFT1DClient
    assert registered_clients()["DistFFTND"] is DistFFTNDClient


def test_registry_rejects_duplicate_name():
    @register_client("EngineTestClient")
    class A:
        pass

    # same class again: idempotent (modules may be re-imported)
    assert register_client("EngineTestClient")(A) is A

    with pytest.raises(ValueError, match="already registered"):
        @register_client("EngineTestClient")
        class B:
            pass


def test_registry_unknown_name_lists_known():
    with pytest.raises(KeyError, match="XlaFFT"):
        get_client("NoSuchClient")


# --------------------------------------------------------------------------
# plan cache
# --------------------------------------------------------------------------
def test_plan_cache_hit_miss_accounting():
    cache = PlanCache()
    calls = []

    def build():
        calls.append(1)
        return object()

    e1, ev1, _ = cache.executable("k1", build)
    e2, ev2, _ = cache.executable("k1", build)
    assert (ev1, ev2) == ("miss", "hit") and e1 is e2 and len(calls) == 1
    assert cache.stats.misses == 1 and cache.stats.hits == 1
    e3, ev3, _ = cache.executable("k2", build)
    assert ev3 == "miss" and e3 is not e1 and len(cache) == 2


def test_plan_cache_keys_on_device_and_candidate():
    p = Problem((64,))
    from repro.core.candidates import Candidate
    k_cpu = PlanCache.executable_key("cpu", p, Candidate("xla"), "forward")
    k_tpu = PlanCache.executable_key("TPU v4", p, Candidate("xla"), "forward")
    k_cand = PlanCache.executable_key("cpu", p, Candidate("stockham"), "forward")
    k_dir = PlanCache.executable_key("cpu", p, Candidate("xla"), "inverse")
    assert len({k_cpu, k_tpu, k_cand, k_dir}) == 4
    # batch/precision/kind are part of the problem signature
    k_b2 = PlanCache.executable_key("cpu", Problem((64,), batch=2),
                                    Candidate("xla"), "forward")
    assert k_b2 != k_cpu


def test_plan_cache_memoizes_plan_selection():
    cache = PlanCache()
    made = []

    def make():
        made.append(1)
        return "the-plan"

    p1, ev1 = cache.plan("pk", make)
    p2, ev2 = cache.plan("pk", make)
    assert (p1, p2) == ("the-plan", "the-plan")
    assert (ev1, ev2) == ("miss", "hit") and len(made) == 1
    # None results (wisdom misses) are cached too
    pn, _ = cache.plan("pk-none", lambda: None)
    pn2, ev = cache.plan("pk-none", lambda: None)
    assert pn is None and pn2 is None and ev == "hit"


# --------------------------------------------------------------------------
# runner / schedule
# --------------------------------------------------------------------------
class _ToyClient:
    """Records the op order the Runner drives; 'download' returns run count."""

    instances = 0
    schedule = OpSchedule("toy", (
        OpStep("setup", "setup", bytes_method="setup_bytes"),
        OpStep("work", "work", needs_input=True),
        OpStep("fetch", "fetch", captures_output=True),
        OpStep("teardown", "teardown"),
    ))

    def __init__(self):
        type(self).instances += 1
        self.calls = []
        self.cache_events = {"work": "hit"}

    def setup(self):
        self.calls.append("setup")

    def setup_bytes(self):
        return 123

    def work(self, x):
        self.calls.append(("work", x))

    def fetch(self):
        self.calls.append("fetch")
        return np.full(3, type(self).instances)

    def teardown(self):
        self.calls.append("teardown")


def test_runner_drives_schedule_and_skips_warmups():
    _ToyClient.instances = 0
    seen = []
    runner = Runner(_ToyClient.schedule, warmups=2, repetitions=3)
    records, out = runner.run(lambda: _ToyClient(), host_input="payload",
                              on_record=seen.append)
    assert _ToyClient.instances == 5            # a fresh client per run
    assert len(records) == 3 and seen == records  # warmups unrecorded
    rec = records[0]
    assert set(rec.times) == {"setup", "work", "fetch", "teardown", "total"}
    assert rec.nbytes == {"setup": 123}
    assert rec.cache == {"work": "hit"}
    assert all(v >= 0 for v in rec.times.values())
    np.testing.assert_array_equal(out, np.full(3, 5))  # last run's output


def test_fft_schedule_matches_paper_sequence():
    assert FFT_SCHEDULE.op_names == (
        "allocate", "init_forward", "upload", "execute_forward",
        "init_inverse", "execute_inverse", "download", "destroy", "total")


@pytest.mark.parametrize("warmups", [0, 1])
def test_benchmark_zero_reps_reports_no_runs(tmp_path, warmups):
    # warmups=1 matters: warmup output must not be blessed as a result
    nodes = build_tree([jf.XlaFFTClient], [(16,)], kinds=("Outplace_Real",),
                       precisions=("float",))
    cfg = BenchmarkConfig(warmups=warmups, repetitions=0,
                          output=str(tmp_path / "r.csv"))
    writer = Benchmark(Context(), cfg).run_nodes(nodes)
    vals = [r for r in writer.rows if r.op == "validate"]
    assert len(vals) == 1 and vals[0].success is False
    assert "no runs executed" in vals[0].error
    assert "AttributeError" not in vals[0].error


# --------------------------------------------------------------------------
# plan cache through the benchmark: compile-once, hit/miss columns
# --------------------------------------------------------------------------
def test_benchmark_plan_cache_compiles_each_direction_once(tmp_path):
    nodes = build_tree([jf.XlaFFTClient], [(32,)], kinds=("Outplace_Real",),
                       precisions=("float",))
    cache = PlanCache()
    cfg = BenchmarkConfig(warmups=0, repetitions=5,
                          output=str(tmp_path / "r.csv"))
    writer = Benchmark(Context(), cfg, plan_cache=cache).run_nodes(nodes)
    # one (node, direction) executable compiled at most once
    assert cache.stats.misses == 2                    # forward + inverse
    assert cache.stats.hits == 2 * 4                  # 4 warm reps, both dirs
    events = {(r.run, r.op): r.plan_cache for r in writer.rows
              if r.op in ("init_forward", "init_inverse")}
    assert events[(0, "init_forward")] == "miss"
    assert events[(0, "init_inverse")] == "miss"
    for run in range(1, 5):
        assert events[(run, "init_forward")] == "hit"
        assert events[(run, "init_inverse")] == "hit"
    assert writer.columns[-1] == "plan_cache"
    # validation still passes with the cached executables
    assert all(r.success for r in writer.rows if r.op == "validate")


def test_warmup_cold_compile_still_emitted(tmp_path):
    """With warmups > 0 the cache's cold compile happens in a warmup run —
    its init ops must still appear (negative run index), or planning cost
    silently vanishes from the output."""
    nodes = build_tree([jf.XlaFFTClient], [(32,)], kinds=("Outplace_Real",),
                       precisions=("float",))
    cfg = BenchmarkConfig(warmups=2, repetitions=2,
                          output=str(tmp_path / "r.csv"))
    writer = Benchmark(Context(), cfg, plan_cache=PlanCache()).run_nodes(nodes)
    inits = [(r.run, r.op, r.plan_cache) for r in writer.rows
             if r.op in ("init_forward", "init_inverse")]
    assert (-2, "init_forward", "miss") in inits
    assert (-2, "init_inverse", "miss") in inits
    # the second warmup hit the cache and stays unrecorded
    assert not any(run == -1 for run, _, _ in inits)
    assert all(pc == "hit" for run, _, pc in inits if run >= 0)
    # warmup records carry ONLY the cold-compile ops, no execute/total rows
    assert not any(r.run < 0 and r.op not in ("init_forward", "init_inverse")
                   for r in writer.rows)


def test_csv_schema_unchanged_without_cache(tmp_path):
    out = str(tmp_path / "r.csv")
    nodes = build_tree([jf.XlaFFTClient], [(16,)], kinds=("Outplace_Real",),
                       precisions=("float",))
    cfg = BenchmarkConfig(warmups=0, repetitions=1, output=out)
    Benchmark(Context(), cfg).run_nodes(nodes).save()
    with open(out) as f:
        header = f.readline().strip()
    assert header == ",".join(COLUMNS)   # byte-for-byte seed column order


# --------------------------------------------------------------------------
# sinks
# --------------------------------------------------------------------------
def _rows():
    return [Row("lib", "cpu", "64", 1, "powerof2", "float", "Outplace_Real",
                "estimate", i, "execute_forward", 1.5 * (i + 1), 64, True, "")
            for i in range(3)]


def test_csv_sink_streams_rows(tmp_path):
    path = str(tmp_path / "s.csv")
    sink = CsvSink(path)
    rows = _rows()
    sink.add(rows[0])
    with open(path) as f:        # flushed before save(): header + first row
        assert len(f.readlines()) == 2
    for r in rows[1:]:
        sink.add(r)
    sink.add(Row("lib", "cpu", "64", 1, "powerof2", "float", "Outplace_Real",
                 "estimate", 0, "validate", 0.0, 0, False, "boom"))
    assert sink.save() == path
    assert sink.n_rows == 4 and sink.n_failures == 1
    with open(path) as f:
        data = list(csv.DictReader(f))
    assert len(data) == 4 and data[0]["op"] == "execute_forward"


def test_jsonl_sink_roundtrip_parity_with_csv(tmp_path):
    cols = columns_for(plan_cache=True)
    cpath, jpath = str(tmp_path / "p.csv"), str(tmp_path / "p.jsonl")
    csink, jsink = CsvSink(cpath, cols), JsonlSink(jpath, cols)
    for r in _rows():
        csink.add(r)
        jsink.add(r)
    csink.save(), jsink.save()
    with open(cpath) as f:
        creader = csv.reader(f)
        header = next(creader)
        crows = list(creader)
    jrows = [json.loads(line) for line in open(jpath)]
    assert header == cols
    assert all(list(j.keys()) == cols for j in jrows)   # same column order
    for c, j in zip(crows, jrows):
        assert c == [str(j[k]) for k in cols]           # same values
    assert isinstance(jrows[0]["success"], bool)        # native types survive
    assert isinstance(jrows[0]["time_ms"], float)


def test_open_sink_by_extension(tmp_path):
    assert isinstance(open_sink(str(tmp_path / "a.jsonl")), JsonlSink)
    assert isinstance(open_sink(str(tmp_path / "a.csv")), CsvSink)
    assert isinstance(open_sink(str(tmp_path / "weird.out")), CsvSink)
    assert isinstance(open_sink(str(tmp_path / "x.csv"), fmt="jsonl"), JsonlSink)
    with pytest.raises(ValueError):
        open_sink(str(tmp_path / "a.csv"), fmt="xml")


def test_result_writer_counts_and_headers(tmp_path):
    w = ResultWriter(str(tmp_path / "w.csv"))
    for r in _rows():
        w.add(r)
    assert w.n_rows == 3 and w.n_failures == 0
    assert w.to_csv_string().splitlines()[0] == ",".join(COLUMNS)


# --------------------------------------------------------------------------
# CLI integration
# --------------------------------------------------------------------------
def test_cli_jsonl_sink_with_plan_cache_column(tmp_path, capsys):
    from repro.core.cli import main
    out = str(tmp_path / "cli.jsonl")
    rc = main(["-e", "16", "--client", "XlaFFT", "--kinds", "Outplace_Real",
               "--precisions", "float", "--reps", "2", "--warmups", "0",
               "-o", out])
    assert rc == 0
    rows = [json.loads(line) for line in open(out)]
    inits = [r for r in rows if r["op"] == "init_forward"]
    assert [r["plan_cache"] for r in sorted(inits, key=lambda r: r["run"])] \
        == ["miss", "hit"]
    assert "plan cache:" in capsys.readouterr().out


def test_cli_no_plan_cache_restores_seed_schema(tmp_path):
    from repro.core.cli import main
    out = str(tmp_path / "cli.csv")
    rc = main(["-e", "16", "--client", "XlaFFT", "--kinds", "Outplace_Real",
               "--precisions", "float", "--reps", "1", "--warmups", "0",
               "--no-plan-cache", "-o", out])
    assert rc == 0
    with open(out) as f:
        assert f.readline().strip() == ",".join(COLUMNS)


def test_cli_wisdom_uses_discovered_device_kind(tmp_path):
    """Regression: CLI used to build Wisdom with device_kind='' so lookups
    never matched stores pre-generated with the real JAX device kind."""
    import jax
    from repro.core.cli import main
    from repro.core.candidates import Candidate

    wpath = str(tmp_path / "wisdom.json")
    w = Wisdom(wpath, device_kind=jax.devices()[0].device_kind)
    problem = Problem((64,), "Outplace_Real", "float")
    w.record(problem, Candidate("xla"))
    w.save()

    out = str(tmp_path / "w.csv")
    rc = main(["-e", "64", "--client", "Planned", "--kinds", "Outplace_Real",
               "--precisions", "float", "--rigor", "wisdom_only",
               "--wisdom", wpath, "--reps", "1", "--warmups", "0", "-o", out])
    assert rc == 0
    with open(out) as f:
        rows = list(csv.DictReader(f))
    vals = [r for r in rows if r["op"] == "validate"]
    assert vals and all(r["success"] == "True" for r in vals), \
        [r["error"] for r in vals]   # NULL-plan failure == device-key mismatch


# --------------------------------------------------------------------------
# distributed FFT through the shared runner
# --------------------------------------------------------------------------
def test_dist_fft_client_through_benchmark(tmp_path):
    nodes = [BenchNode(DistFFT1DClient, Problem((64,), "Outplace_Complex",
                                                "float"))]
    cache = PlanCache()
    cfg = BenchmarkConfig(warmups=0, repetitions=2,
                          output=str(tmp_path / "d.csv"))
    writer = Benchmark(Context(), cfg, plan_cache=cache).run_nodes(nodes)
    vals = [r for r in writer.rows if r.op == "validate"]
    assert vals and all(r.success for r in vals), [r.error for r in vals]
    assert cache.stats.misses == 2 and cache.stats.hits == 2
    # infeasible problems are recorded failures, not suite aborts
    bad = [BenchNode(DistFFT1DClient, Problem((32, 32), "Outplace_Complex",
                                              "float"))]
    writer2 = Benchmark(Context(), BenchmarkConfig(
        warmups=0, repetitions=1, output=str(tmp_path / "d2.csv"))).run_nodes(bad)
    v2 = [r for r in writer2.rows if r.op == "validate"]
    assert v2 and not v2[0].success and "rank-1" in v2[0].error


def test_dist_fftnd_client_through_benchmark(tmp_path):
    """The ND client degrades gracefully to one device (the P=1 slab is the
    in-process identity-collective path); real meshes are exercised by the
    subprocess conformance sweep."""
    nodes = [BenchNode(DistFFTNDClient, Problem((8, 8, 16),
                                                "Outplace_Complex", "float"))]
    writer = Benchmark(Context(), BenchmarkConfig(
        warmups=0, repetitions=2,
        output=str(tmp_path / "nd.csv"))).run_nodes(nodes)
    vals = [r for r in writer.rows if r.op == "validate"]
    assert vals and all(r.success for r in vals), [r.error for r in vals]
    # constraint violations are recorded failures, not suite aborts
    bad = [BenchNode(DistFFTNDClient, Problem((64,), "Outplace_Complex",
                                              "float"))]
    writer2 = Benchmark(Context(), BenchmarkConfig(
        warmups=0, repetitions=1, output=str(tmp_path / "nd2.csv"))).run_nodes(bad)
    v2 = [r for r in writer2.rows if r.op == "validate"]
    assert v2 and not v2[0].success and "rank-2/3" in v2[0].error
