"""Wisdom schema v3: provenance fields, legacy-file loading, the
nearest-neighbor ``lookup_near`` read path with its feasibility-class
boundary, ``wisdom_near``-tagged plans, and concurrent union-merge saves."""

import json

import pytest

from repro.core.client import Problem
from repro.core.candidates import Candidate
from repro.core.plan import PlanRigor, make_plan
from repro.core.wisdom import (WISDOM_SCHEMA_VERSION, Wisdom,
                               _feasibility_class, _strip_shape_knobs)


def _wisdom(tmp_path, name="wisdom.json", device_kind="cpu") -> Wisdom:
    return Wisdom(str(tmp_path / name), device_kind=device_kind)


# ---------------------------------------------------------------------------
# v1/v2 fixtures load unchanged
# ---------------------------------------------------------------------------
def test_v1_and_v2_fixtures_load_unchanged(tmp_path):
    path = tmp_path / "wisdom.json"
    path.write_text(json.dumps({
        # v1: the pre-versioning layout — no "v", no axes/mesh
        "cpu|256/float/Outplace_Complex/b1": {
            "backend": "stockham_pallas", "options": [["radix", 4]]},
        # v2: versioned, per-axis assignment
        "cpu|64x64/float/Outplace_Complex/b1": {
            "v": 2, "backend": "nd", "options": [],
            "axes": [{"v": 2, "backend": "stockham", "options": []},
                     {"v": 2, "backend": "fourstep", "options": []}]},
        # demotions table (any vintage)
        "__demoted__": {"cpu|powerof2|r1": ["sixstep"]},
    }))
    w = Wisdom(str(path), device_kind="cpu")
    assert len(w) == 2
    c1 = w.lookup(Problem((256,), "Outplace_Complex", "float"))
    assert c1 == Candidate("stockham_pallas", (("radix", 4),))
    c2 = w.lookup(Problem((64, 64), "Outplace_Complex", "float"))
    assert c2.backend == "nd" and [a.backend for a in c2.axes] \
        == ["stockham", "fourstep"]
    assert w.demoted(Problem((1024,), "Outplace_Complex", "float")) \
        == frozenset({"sixstep"})


def test_future_schema_and_malformed_records_are_skipped(tmp_path):
    path = tmp_path / "wisdom.json"
    path.write_text(json.dumps({
        "cpu|256/float/Outplace_Complex/b1": {
            "v": WISDOM_SCHEMA_VERSION + 1, "backend": "xla", "options": []},
        "cpu|512/float/Outplace_Complex/b1": {
            "v": 3, "backend": "xla", "options": [],
            "measured_ms": "fast"},                       # malformed field
        "cpu|1024/float/Outplace_Complex/b1": {
            "v": 3, "backend": "xla", "options": []},     # fine
    }))
    with pytest.warns(UserWarning):
        w = Wisdom(str(path), device_kind="cpu")
    assert len(w) == 1
    assert w.lookup(Problem((1024,), "Outplace_Complex", "float")) is not None


# ---------------------------------------------------------------------------
# v3 provenance round-trip + measurements()
# ---------------------------------------------------------------------------
def test_v3_provenance_round_trips(tmp_path):
    w = _wisdom(tmp_path)
    p = Problem((256,), "Outplace_Complex", "float")
    w.record(p, Candidate("stockham_pallas"), measured_ms=1.25,
             rigor="measure")
    w.save()
    doc = json.loads((tmp_path / "wisdom.json").read_text())
    rec = doc["cpu|256/float/Outplace_Complex/b1"]
    assert rec["v"] == WISDOM_SCHEMA_VERSION
    assert rec["measured_ms"] == 1.25 and rec["rigor"] == "measure"
    w2 = _wisdom(tmp_path)
    rows = w2.measurements()
    assert rows == [(p, Candidate("stockham_pallas"), 1.25)]


def test_record_omits_unset_and_nan_provenance(tmp_path):
    w = _wisdom(tmp_path)
    p = Problem((256,), "Outplace_Complex", "float")
    w.record(p, Candidate("xla"))                              # legacy call
    w.record(Problem((512,), "Outplace_Complex", "float"),
             Candidate("xla"), measured_ms=float("nan"))       # untimed
    w.save()
    doc = json.loads((tmp_path / "wisdom.json").read_text())
    for rec in doc.values():
        assert "measured_ms" not in rec and "rigor" not in rec
    assert w.measurements() == []


def test_measurements_includes_scoped_entries(tmp_path):
    w = _wisdom(tmp_path)
    p = Problem((256,), "Outplace_Complex", "float")
    w.record(p, Candidate("stockham_pallas"), scope="stockham_pallas",
             measured_ms=0.5)
    assert w.measurements() == [(p, Candidate("stockham_pallas"), 0.5)]


# ---------------------------------------------------------------------------
# lookup_near: nearest same-class neighbor, never across feasibility
# ---------------------------------------------------------------------------
def test_lookup_near_picks_log2_closest_shape(tmp_path):
    w = _wisdom(tmp_path)
    for n, backend in ((2048, "stockham_pallas"), (8192, "fourstep_pallas")):
        w.record(Problem((n,), "Outplace_Complex", "float"),
                 Candidate(backend))
    hit = w.lookup_near(Problem((1024,), "Outplace_Complex", "float"))
    assert hit is not None
    cand, neighbor_key = hit
    # 1024 is 1 octave from 2048, 3 from 8192 (all above DFT_MAX_N)
    assert cand.backend == "stockham_pallas"
    assert neighbor_key == "cpu|2048/float/Outplace_Complex/b1"


def test_lookup_near_skips_the_exact_key_and_empty_store(tmp_path):
    w = _wisdom(tmp_path)
    p = Problem((256,), "Outplace_Complex", "float")
    assert w.lookup_near(p) is None          # empty store
    w.record(p, Candidate("xla"))
    # only the exact shape is stored: a *near* lookup must not return it
    # (the caller already tried lookup())
    assert w.lookup_near(p) is None


def test_lookup_near_respects_class_rank_and_kind(tmp_path):
    w = _wisdom(tmp_path)
    w.record(Problem((1024,), "Outplace_Complex", "float"), Candidate("xla"))
    # different extent class (radix357 vs powerof2)
    assert w.lookup_near(
        Problem((384,), "Outplace_Complex", "float")) is None
    # different rank
    assert w.lookup_near(
        Problem((512, 512), "Outplace_Complex", "float")) is None
    # different kind
    assert w.lookup_near(
        Problem((512,), "Outplace_Real", "float")) is None


def test_lookup_near_never_crosses_feasibility_boundary(tmp_path):
    # 16384 and 65536 are both powerof2 rank-1 — but the stockham_pallas
    # VMEM cap sits between them, so their backend-support sets differ and
    # neither may warm-start the other
    a = Problem((16384,), "Outplace_Complex", "float")
    b = Problem((65536,), "Outplace_Complex", "float")
    assert _feasibility_class(a) != _feasibility_class(b)
    w = _wisdom(tmp_path)
    w.record(a, Candidate("stockham_pallas"))
    assert w.lookup_near(b) is None
    # same-side neighbor: feasibility class matches, the hit transfers
    c = Problem((8192,), "Outplace_Complex", "float")
    assert _feasibility_class(a) == _feasibility_class(c)
    assert w.lookup_near(c) is not None


def test_lookup_near_strips_shape_knobs_across_extents(tmp_path):
    w = _wisdom(tmp_path)
    tuned = Candidate("sixstep", (("split_n1", 64), ("tile_b", 8)))
    w.record(Problem((4096,), "Outplace_Complex", "float"), tuned)
    hit = w.lookup_near(Problem((2048,), "Outplace_Complex", "float"))
    assert hit is not None
    cand, _ = hit
    # the n1*n2 factorization of 4096 is meaningless at 2048; the batch
    # tile transfers
    assert cand == Candidate("sixstep", (("tile_b", 8),))
    # same extents, different batch: the knobs are shape-valid and kept
    hit = w.lookup_near(Problem((4096,), "Outplace_Complex", "float",
                                batch=4))
    assert hit is not None and hit[0] == tuned


def test_strip_shape_knobs_recurses_into_axes():
    nd = Candidate("nd", (), (Candidate("sixstep", (("split_n1", 32),)),
                              Candidate("stockham", (("engine", "pow2"),))))
    stripped = _strip_shape_knobs(nd)
    assert stripped.axes[0].options == ()
    assert stripped.axes[1].options == ()


def test_lookup_near_never_transfers_mesh_candidates(tmp_path):
    w = _wisdom(tmp_path)
    w.record(Problem((4096,), "Outplace_Complex", "float"),
             Candidate("slab", (), (), (4,)))
    assert w.lookup_near(
        Problem((2048,), "Outplace_Complex", "float")) is None


def test_lookup_near_scoped_namespaces_are_separate(tmp_path):
    w = _wisdom(tmp_path)
    w.record(Problem((1024,), "Outplace_Complex", "float"),
             Candidate("stockham_pallas"), scope="stockham_pallas")
    q = Problem((2048,), "Outplace_Complex", "float")
    assert w.lookup_near(q) is None                        # unscoped view
    assert w.lookup_near(q, scope="stockham_pallas") is not None


# ---------------------------------------------------------------------------
# make_plan integration: wisdom_near plan source + the near=False opt-out
# ---------------------------------------------------------------------------
def test_make_plan_tags_interpolated_pick_wisdom_near(tmp_path):
    w = _wisdom(tmp_path)
    w.record(Problem((1024,), "Outplace_Complex", "float"),
             Candidate("stockham_pallas"), measured_ms=0.8, rigor="measure")
    q = Problem((2048,), "Outplace_Complex", "float")
    plan = make_plan(q, PlanRigor.MEASURE, wisdom=w)
    assert plan.source == "wisdom_near"
    assert plan.candidate.backend == "stockham_pallas"
    # exact hit stays plain 'wisdom'
    exact = make_plan(Problem((1024,), "Outplace_Complex", "float"),
                      PlanRigor.MEASURE, wisdom=w)
    assert exact.source == "wisdom"
    # WISDOM_ONLY: near hit instead of the fftw NULL plan
    wo = make_plan(q, PlanRigor.WISDOM_ONLY, wisdom=w)
    assert wo is not None and wo.source == "wisdom_near"


def test_make_plan_near_false_disables_interpolation(tmp_path):
    w = _wisdom(tmp_path)
    w.record(Problem((1024,), "Outplace_Complex", "float"),
             Candidate("stockham_pallas"))
    q = Problem((2048,), "Outplace_Complex", "float")
    assert make_plan(q, PlanRigor.WISDOM_ONLY, wisdom=w, near=False) is None
    plan = make_plan(q, PlanRigor.MEASURE, wisdom=w, near=False)
    # build-less MEASURE falls through to the estimate pick — and must NOT
    # have been recorded as if it were measured
    assert plan.source == "estimate"
    assert w.lookup(q) is None


def test_near_pick_skips_demoted_backends(tmp_path):
    w = _wisdom(tmp_path)
    w.record(Problem((256,), "Outplace_Complex", "float"),
             Candidate("stockham_pallas"))
    q = Problem((512,), "Outplace_Complex", "float")
    w.record_demotion(q, "stockham_pallas")
    plan = make_plan(q, PlanRigor.MEASURE, wisdom=w)
    assert plan.source == "estimate"      # near hit rejected, estimate path
    assert plan.candidate.backend != "stockham_pallas"


# ---------------------------------------------------------------------------
# concurrent saves union-merge v3 fields
# ---------------------------------------------------------------------------
def test_concurrent_saves_union_merge_provenance(tmp_path):
    p = Problem((256,), "Outplace_Complex", "float")
    a = _wisdom(tmp_path)
    b = _wisdom(tmp_path)          # loaded before A saves
    a.record(p, Candidate("stockham_pallas"), measured_ms=0.9,
             rigor="measure")
    a.save()
    # B persists the same selection without provenance: A's fields survive
    b.record(p, Candidate("stockham_pallas"))
    b.save()
    doc = json.loads((tmp_path / "wisdom.json").read_text())
    rec = doc["cpu|256/float/Outplace_Complex/b1"]
    assert rec["measured_ms"] == 0.9 and rec["rigor"] == "measure"
    # ...and the merged store is what B now serves
    assert b.measurements() == [(p, Candidate("stockham_pallas"), 0.9)]


def test_concurrent_save_conflicting_selection_keeps_ours(tmp_path):
    p = Problem((256,), "Outplace_Complex", "float")
    a = _wisdom(tmp_path)
    b = _wisdom(tmp_path)
    a.record(p, Candidate("stockham_pallas"), measured_ms=0.9)
    a.save()
    b.record(p, Candidate("xla"), measured_ms=2.0, rigor="patient")
    b.save()
    doc = json.loads((tmp_path / "wisdom.json").read_text())
    rec = doc["cpu|256/float/Outplace_Complex/b1"]
    # different selection: B's record wins whole, no field bleed-through
    assert rec["backend"] == "xla" and rec["measured_ms"] == 2.0


def test_concurrent_demotions_union(tmp_path):
    p = Problem((256,), "Outplace_Complex", "float")
    a = _wisdom(tmp_path)
    b = _wisdom(tmp_path)
    a.record_demotion(p, "sixstep")
    a.save()
    b.record_demotion(p, "fourstep_pallas")
    b.save()
    fresh = _wisdom(tmp_path)
    assert fresh.demoted(p) == frozenset({"sixstep", "fourstep_pallas"})
