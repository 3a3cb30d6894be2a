"""gearshifft core framework tests: tree, selection, planner, runner, CSV."""

import numpy as np
import pytest

from repro.core.benchmark import Benchmark, BenchmarkConfig, make_input, roundtrip_error
from repro.core.client import Context, Problem
from repro.core.extents import classify, parse_extents, format_extents
from repro.core.candidates import Candidate, candidates
from repro.core.costmodel import estimate_choice
from repro.core.plan import PlanRigor, make_plan
from repro.core.tree import build_tree, select
from repro.core.wisdom import Wisdom
from repro.core.clients import jax_fft as jf


# --------------------------------------------------------------------------
# extents
# --------------------------------------------------------------------------
def test_parse_extents():
    assert parse_extents("128x128x128") == (128, 128, 128)
    assert parse_extents("1024") == (1024,)
    assert format_extents((32, 64)) == "32x64"
    with pytest.raises(ValueError):
        parse_extents("12x-1")
    with pytest.raises(ValueError):
        parse_extents("1x2x3x4")


def test_classify():
    assert classify((1024,)) == "powerof2"
    assert classify((128, 128, 128)) == "powerof2"
    assert classify((120,)) == "radix357"      # 2^3*3*5
    assert classify((19 * 19,)) == "oddshape"  # paper's power-of-19


# --------------------------------------------------------------------------
# planner
# --------------------------------------------------------------------------
def test_candidates_feasibility():
    backs = {c.backend for c in candidates(Problem((1024,)))}
    assert {"xla", "stockham", "fourstep", "fourstep_pallas", "bluestein"} <= backs
    assert "dft" in backs  # real 1024: the packed engine length is 512
    backs_c = {c.backend
               for c in candidates(Problem((1024,), "Outplace_Complex"))}
    assert "dft" not in backs_c  # 1024 > DFT_MAX_N = 512
    backs_odd = {c.backend for c in candidates(Problem((19 * 19,)))}
    assert "stockham" not in backs_odd and "bluestein" in backs_odd
    backs_tiny = {c.backend for c in candidates(Problem((64,)))}
    assert "dft" in backs_tiny


def test_estimate_heuristic():
    assert estimate_choice(Problem((64,))).backend == "dft"
    assert estimate_choice(Problem((1 << 20,))).backend == "xla"


def test_measure_plan_picks_feasible():
    problem = Problem((256,), "Outplace_Complex", "float")
    plan = make_plan(problem, PlanRigor.MEASURE,
                     build=lambda c: jf.build_forward(problem, c))
    # MEASURE picks by wall time: any feasible backend at n=256 may win
    assert plan.candidate.backend in {"xla", "stockham", "fourstep",
                                      "fourstep_pallas", "stockham_pallas",
                                      "sixstep", "chirpz_pallas", "dft",
                                      "bluestein"}
    assert plan.plan_time_ms > 0
    assert any(v == v for v in plan.measured_ms.values())  # some finite timing


def test_wisdom_roundtrip(tmp_path):
    w = Wisdom(str(tmp_path / "wisdom.json"), device_kind="cpu")
    problem = Problem((128,))
    assert w.lookup(problem) is None
    # WISDOM_ONLY with empty store -> NULL plan (fftw semantics)
    assert make_plan(problem, PlanRigor.WISDOM_ONLY, wisdom=w) is None
    w.record(problem, Candidate("fourstep", (("tile_b", 8),)))
    w.save()
    w2 = Wisdom(str(tmp_path / "wisdom.json"), device_kind="cpu")
    cand = w2.lookup(problem)
    assert cand.backend == "fourstep" and cand.opts() == {"tile_b": 8}
    plan = make_plan(problem, PlanRigor.WISDOM_ONLY, wisdom=w2)
    assert plan is not None and plan.candidate.backend == "fourstep"


# --------------------------------------------------------------------------
# tree + selection
# --------------------------------------------------------------------------
def test_tree_and_wildcards():
    nodes = build_tree([jf.XlaFFTClient, jf.StockhamClient], [(128,), (32, 32)],
                       kinds=("Inplace_Real", "Outplace_Complex"),
                       precisions=("float", "double"))
    assert len(nodes) == 2 * 2 * 2 * 2
    sel = select(nodes, "*/float/*/Inplace_Real")
    assert len(sel) == 4 and all("float/"
                                 in n.path and n.path.endswith("Inplace_Real") for n in sel)
    sel2 = select(nodes, "Stockham")
    assert len(sel2) == 8
    assert select(nodes, "NoSuch/*") == []


# --------------------------------------------------------------------------
# runner end-to-end
# --------------------------------------------------------------------------
def test_make_input_seesaw():
    x = make_input(Problem((1024,)), 0)
    assert x.dtype == np.float32 and x.min() >= 0 and x.max() < 1


def test_roundtrip_error_metric():
    x = np.ones((100,), np.float32)
    assert roundtrip_error(x, x) == 0.0
    assert roundtrip_error(x, x + 1e-3) < 1e-6  # constant offset: std ~ 0
    noisy = x + np.random.default_rng(0).normal(0, 1e-3, 100).astype(np.float32)
    assert roundtrip_error(x, noisy) > 1e-4


@pytest.mark.parametrize("client", [jf.XlaFFTClient, jf.StockhamClient,
                                    jf.FourStepClient])
def test_benchmark_runs_and_validates(client, tmp_path):
    nodes = build_tree([client], [(64,), (16, 16)],
                       kinds=("Outplace_Real", "Inplace_Complex"),
                       precisions=("float",))
    cfg = BenchmarkConfig(warmups=1, repetitions=2,
                          output=str(tmp_path / "result.csv"))
    writer = Benchmark(Context(), cfg).run_nodes(nodes)
    path = writer.save()
    rows = [r for r in writer.rows if r.op == "validate"]
    assert len(rows) == len(nodes)
    assert all(r.success for r in rows), [r.error for r in rows if not r.success]
    # every op recorded for every counted run
    ef = [r for r in writer.rows if r.op == "execute_forward"]
    assert len(ef) == len(nodes) * cfg.repetitions
    assert all(r.time_ms >= 0 for r in ef)
    with open(path) as f:
        header = f.readline().strip().split(",")
    assert header[0] == "library" and "time_ms" in header


def test_benchmark_failure_continues(tmp_path):
    # Stockham on non-pow2 extents must fail validation/planning but not abort
    nodes = build_tree([jf.StockhamClient], [(100,), (64,)],
                       kinds=("Outplace_Complex",), precisions=("float",))
    cfg = BenchmarkConfig(warmups=0, repetitions=1,
                          output=str(tmp_path / "r.csv"))
    writer = Benchmark(Context(), cfg).run_nodes(nodes)
    vals = {r.extents: r.success for r in writer.rows if r.op == "validate"}
    assert vals["100"] is False and vals["64"] is True


def test_cli_end_to_end(tmp_path):
    from repro.core.cli import main
    out = str(tmp_path / "cli.csv")
    rc = main(["-e", "64", "16x16", "--client", "XlaFFT", "--kinds",
               "Outplace_Real", "--precisions", "float", "--reps", "2",
               "--warmups", "0", "-o", out])
    assert rc == 0
    data = open(out).read()
    assert "XlaFFT" in data and "execute_forward" in data


def test_cli_exits_nonzero_when_a_node_fails(tmp_path):
    # the suite records the failed node and runs the rest; the exit status
    # still reports it
    from repro.core.cli import main
    out = str(tmp_path / "fail.csv")
    rc = main(["-e", "100", "64", "--client", "Stockham", "--kinds",
               "Outplace_Complex", "--precisions", "float", "--reps", "1",
               "--warmups", "0", "-o", out])
    assert rc == 1
    assert "64" in open(out).read()


def test_cli_wildcard_and_inplace(tmp_path):
    from repro.core.cli import main
    out = str(tmp_path / "cli2.csv")
    rc = main(["-e", "32x32", "--client", "FourStep", "-r",
               "*/float/*/Inplace_Real", "--reps", "1", "--warmups", "0",
               "-o", out])
    assert rc == 0
    data = open(out).read()
    assert "Inplace_Real" in data and "Outplace" not in data
