"""The calibrated cost-model layer: coefficient tables, the typed
Infeasible verdict, the active-model switch, fitted-table IO, and the
Spearman metric the fitter/CI assert on.

Golden-value guards live in test_planner_nd / test_planner_autotune /
test_dist_planner (every ESTIMATE pick and dist crossover is pinned there);
this file covers the cost model's own surface.
"""

import json
import math
import warnings
from dataclasses import replace
from pathlib import Path

import pytest

from repro.core.backends import TABLE, Infeasible
from repro.core.candidates import Candidate
from repro.core.client import Problem
from repro.core.costmodel import (DEFAULT_COEFFICIENTS, DEFAULT_MODEL,
                                  CostCoefficients, CostModel,
                                  estimate_bytes_moved, estimate_choice,
                                  get_active_model, hbm_passes, load_tables,
                                  model_for_device, save_tables,
                                  set_active_model, spearman, use_model)
from repro.core.plan import fallback_chain

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# default table = hand-written values, bit-for-bit
# ---------------------------------------------------------------------------
def test_default_table_reproduces_hand_written_model():
    # spot-check the literals the refactor tabulated (the full golden grid
    # is pinned by the planner tests); any drift here is a model change
    assert hbm_passes("xla", 1024) == 2.0
    assert hbm_passes("stockham", 1024) == 10.0          # log2(n) passes
    assert hbm_passes("stockham_pallas", 1024) == 1.0    # one fused pass
    assert hbm_passes("sixstep", 1 << 16) == 5.0
    p = Problem((64, 64, 64), "Outplace_Complex", "float")
    assert estimate_bytes_moved(p, Candidate("xla")) == 8388608.0
    assert estimate_choice(p).backend == "xla"


def test_round_trip_through_dict():
    c = CostCoefficients()
    assert CostCoefficients.from_dict(c.to_dict()) == c
    assert c == DEFAULT_COEFFICIENTS


def test_narrow_fourstep_coefficient_round_trips_and_scales():
    c = replace(CostCoefficients(), fourstep_pallas_narrow_passes=7.5)
    assert CostCoefficients.from_dict(c.to_dict()) == c
    # charged above the dense DFT by value, not by enumeration order
    assert (DEFAULT_COEFFICIENTS.fourstep_pallas_narrow_passes
            > DEFAULT_COEFFICIENTS.dft_passes)
    # the fitter scales both fourstep_pallas pass counts together
    m = DEFAULT_MODEL.scaled({"fourstep_pallas": 2.0})
    assert m.coeffs.fourstep_pallas_narrow_passes == \
        2.0 * DEFAULT_COEFFICIENTS.fourstep_pallas_narrow_passes
    assert m.hbm_passes("fourstep_pallas", 361) == \
        m.coeffs.fourstep_pallas_narrow_passes
    assert m.hbm_passes("fourstep_pallas", 4096) == \
        m.coeffs.fourstep_pallas_passes


def test_table_without_narrow_coefficient_loads_its_default():
    path = REPO / "benchmarks" / "baselines" / "costmodel_cpu.json"
    doc = json.loads(path.read_text())
    assert "fourstep_pallas_narrow_passes" not in doc["tables"]["cpu"]
    cpu = load_tables(str(path))["cpu"]
    assert cpu.coeffs.fourstep_pallas_narrow_passes == \
        DEFAULT_COEFFICIENTS.fourstep_pallas_narrow_passes
    assert cpu.coeffs.fourstep_pallas_passes == \
        doc["tables"]["cpu"]["fourstep_pallas_passes"]


def test_from_dict_warns_on_unknown_coefficient():
    with pytest.warns(UserWarning, match="unknown cost coefficients"):
        c = CostCoefficients.from_dict({"xla_smooth_passes": 3.0,
                                        "warp_drive_passes": 9.0})
    assert c.xla_smooth_passes == 3.0


# ---------------------------------------------------------------------------
# the typed Infeasible verdict
# ---------------------------------------------------------------------------
def test_infeasible_verdict_is_falsy_inf_with_reason():
    v = Infeasible("because")
    assert not v
    assert float(v) == float("inf")
    assert v.reason == "because"


def test_estimate_returns_verdict_numeric_view_is_inf():
    p = Problem((19 * 19,))                      # oddshape: no pow2 backends
    cand = Candidate("stockham")
    verdict = DEFAULT_MODEL.estimate(p, cand)
    assert isinstance(verdict, Infeasible)
    assert "stockham" in verdict.reason
    assert estimate_bytes_moved(p, cand) == float("inf")
    # feasible candidates return a plain float, never a verdict
    ok = DEFAULT_MODEL.estimate(p, Candidate("bluestein"))
    assert isinstance(ok, float) and math.isfinite(ok)


# ---------------------------------------------------------------------------
# scaled models + the active-model switch
# ---------------------------------------------------------------------------
def test_scaled_touches_only_the_backend_coefficients():
    m = DEFAULT_MODEL.scaled({"stockham": 3.0}, device_kind="test")
    assert m.coeffs.stockham_stage_passes == 3.0
    # everything outside the stockham group is untouched
    for name in (f for b, rec in TABLE.items() if b != "stockham"
                 for f in rec.coeffs):
        assert getattr(m.coeffs, name) == getattr(DEFAULT_COEFFICIENTS, name)
    assert m.device_kind == "test"
    # original model unchanged (frozen coefficients)
    assert DEFAULT_MODEL.coeffs == DEFAULT_COEFFICIENTS


def test_use_model_scopes_the_delegates():
    p = Problem((1024,))
    base = estimate_bytes_moved(p, Candidate("stockham_pallas"))
    heavy = DEFAULT_MODEL.scaled({"stockham_pallas": 100.0})
    with use_model(heavy):
        assert get_active_model() is heavy
        assert estimate_bytes_moved(p, Candidate("stockham_pallas")) \
            == pytest.approx(100.0 * base)
    assert get_active_model() is DEFAULT_MODEL
    assert estimate_bytes_moved(p, Candidate("stockham_pallas")) == base


def test_fitted_model_changes_estimate_pick_and_chain_order():
    # on the CI CPU the fitter massively up-prices the interpret-mode
    # Pallas kernels; emulate that and check ESTIMATE + fallback_chain
    # re-rank without any caller changes (the active-model contract)
    p = Problem((4096,))
    default_pick = estimate_choice(p).backend
    assert default_pick in {"stockham_pallas", "fourstep_pallas"}
    fitted = DEFAULT_MODEL.scaled(
        {b: 50.0 for b in ("stockham_pallas", "fourstep_pallas", "sixstep",
                           "chirpz_pallas", "dft")})
    with use_model(fitted):
        assert estimate_choice(p).backend != default_pick
        chain = fallback_chain(p)
        costs = [estimate_bytes_moved(p, c) for c in chain]
        assert costs == sorted(costs)


def test_set_active_model_none_restores_default():
    prev = set_active_model(DEFAULT_MODEL.scaled({"xla": 2.0}))
    try:
        assert get_active_model() is not DEFAULT_MODEL
    finally:
        set_active_model(None)
    assert get_active_model() is DEFAULT_MODEL
    assert prev is DEFAULT_MODEL


# ---------------------------------------------------------------------------
# versioned per-device tables
# ---------------------------------------------------------------------------
def test_save_load_tables_round_trip(tmp_path):
    path = str(tmp_path / "costmodel.json")
    fitted = DEFAULT_MODEL.scaled({"xla": 1.5, "bluestein": 0.25},
                                  device_kind="cpu")
    save_tables(path, {"cpu": fitted, "default": DEFAULT_MODEL},
                meta={"generated_by": "test"})
    loaded = load_tables(path)
    assert set(loaded) == {"cpu", "default"}
    assert loaded["cpu"].coeffs == fitted.coeffs
    assert loaded["default"].coeffs == DEFAULT_COEFFICIENTS
    assert "test" in loaded["cpu"].source


def test_load_tables_rejects_newer_schema(tmp_path):
    path = tmp_path / "costmodel.json"
    path.write_text('{"schema": 999, "tables": {}}')
    with pytest.raises(ValueError, match="schema"):
        load_tables(str(path))


def test_model_for_device_matching(tmp_path):
    path = str(tmp_path / "costmodel.json")
    save_tables(path, {
        "cpu": DEFAULT_MODEL.scaled({"xla": 2.0}, device_kind="cpu"),
        "nvidia": DEFAULT_MODEL.scaled({"xla": 3.0}, device_kind="nvidia"),
        "default": DEFAULT_MODEL})
    tables = load_tables(path)
    assert model_for_device("cpu", tables).coeffs.xla_smooth_passes == 4.0
    # case-insensitive prefix match finds the vendor table
    assert model_for_device("NVIDIA H100 80GB HBM3",
                            tables).coeffs.xla_smooth_passes == 6.0
    # unknown kinds fall back to the file's default table
    assert model_for_device("TPU v5e", tables).coeffs == DEFAULT_COEFFICIENTS
    # ...and to the hand-written model when the file has no default
    assert model_for_device("TPU v5e", {}) is DEFAULT_MODEL
    # a path is accepted directly
    assert model_for_device("cpu", path).coeffs.xla_smooth_passes == 4.0


# ---------------------------------------------------------------------------
# spearman (the fitter/CI metric)
# ---------------------------------------------------------------------------
def test_spearman_basic():
    assert spearman([1, 2, 3, 4], [10, 20, 30, 40]) == pytest.approx(1.0)
    assert spearman([1, 2, 3, 4], [40, 30, 20, 10]) == pytest.approx(-1.0)
    # monotone-invariant: rank correlation ignores the scale of the values
    assert spearman([1, 2, 3, 4], [1, 100, 1000, 10**6]) == pytest.approx(1.0)


def test_spearman_ties_get_average_ranks():
    # ties on both sides, still perfectly concordant
    assert spearman([1, 1, 2, 2], [5, 5, 9, 9]) == pytest.approx(1.0)
    r = spearman([1, 1, 2], [1, 2, 3])
    assert 0.0 < r < 1.0


def test_spearman_degenerate_cases():
    assert math.isnan(spearman([], []))
    assert math.isnan(spearman([1.0], [2.0]))
    assert math.isnan(spearman([3, 3, 3], [1, 2, 3]))   # zero rank variance
    with pytest.raises(ValueError):
        spearman([1, 2], [1])


# ---------------------------------------------------------------------------
# the fitter CLI (stdlib-only, runs against the committed BENCH data)
# ---------------------------------------------------------------------------
def _load_fitter():
    import importlib.util
    import os
    import sys
    spec = importlib.util.spec_from_file_location(
        "fit_costmodel", os.path.join(os.path.dirname(__file__), "..",
                                      "tools", "fit_costmodel.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["fit_costmodel"] = mod   # dataclasses needs the registration
    spec.loader.exec_module(mod)
    return mod


def test_fitter_on_committed_smoke_bench(tmp_path):
    import os
    fit = _load_fitter()
    root = os.path.join(os.path.dirname(__file__), "..")
    bench = os.path.join(root, "benchmarks", "baselines", "BENCH_smoke.json")
    out = str(tmp_path / "fitted.json")
    rc = fit.main([bench, "--out", out, "--assert-improves",
                   "--assert-kind", "cpu"])
    assert rc == 0
    tables = load_tables(out)
    assert "cpu" in tables
    assert tables["cpu"].coeffs != DEFAULT_COEFFICIENTS


def test_fitter_assertion_failure_is_nonzero(tmp_path):
    import os
    fit = _load_fitter()
    root = os.path.join(os.path.dirname(__file__), "..")
    bench = os.path.join(root, "benchmarks", "baselines", "BENCH_smoke.json")
    rc = fit.main([bench, "--assert-min-rho", "1.01", "--assert-kind", "cpu"])
    assert rc == 1


def test_roofline_fallback_tags_infeasible_rows():
    import importlib.util
    import os
    import sys
    spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(os.path.dirname(__file__), "..",
                                      "tools", "bench_compare.py"))
    bc = importlib.util.module_from_spec(spec)
    sys.modules["bench_compare"] = bc
    spec.loader.exec_module(bc)
    bc.ROOFLINE_FALLBACKS.clear()
    p = Problem((19 * 19,))                     # oddshape
    rec = {}
    # a row that ran but models as infeasible: tagged, logged, and still
    # gets a finite roofline from the 2x-signal-bytes algorithmic minimum
    bc._annotate_roofline(rec, p, Candidate("stockham"), 1e-3)
    assert "stockham" in rec["roofline_fallback"]
    assert rec["model_bytes"] == 2.0 * p.signal_bytes
    assert math.isfinite(rec["roofline_frac"]) and rec["roofline_frac"] > 0
    assert len(bc.ROOFLINE_FALLBACKS) == 1
    # feasible rows carry the model's own bytes and no tag
    rec2 = {}
    bc._annotate_roofline(rec2, p, Candidate("bluestein"), 1e-3)
    assert "roofline_fallback" not in rec2
    assert rec2["model_bytes"] == estimate_bytes_moved(p, Candidate("bluestein"))
    bc.ROOFLINE_FALLBACKS.clear()


def test_deprecated_wisdom_generate_warns():
    from repro.core import wisdom as wisdom_mod
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        with pytest.raises(DeprecationWarning):
            wisdom_mod.generate([(8,)], path="/nonexistent/never-written")
