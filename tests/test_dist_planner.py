"""Distributed planning: slab/pencil/dist1d candidate enumeration gated on
the active mesh, the interconnect-aware cost model with its golden crossover
points, mesh-shaped wisdom records (legacy records still load), atomic
concurrent-tolerant wisdom writes, and the SuiteSpec device-count axis.

Pure planner/model tests — no fake-device mesh is spun up, so they run in
tier-1.  A stand-in with just ``.size`` is all the candidate enumeration
needs (numeric distributed checks live in test_distributed_fft.py and the
conformance subprocess sweep)."""

import json
import warnings

import pytest

from repro.core.client import Problem
from repro.core.candidates import (Candidate, DIST_BACKENDS,
                                   _pencil_mesh_shapes, candidates,
                                   dist_supports)
from repro.core.costmodel import dist_local_engine, estimate_bytes_moved
from repro.core.suite import SuiteSpec, dist_support_matrix
from repro.core.wisdom import Wisdom


class FakeMesh:
    """Enough mesh for the planner: candidate enumeration only reads
    ``.size`` (building the shard_map needs a real one)."""
    def __init__(self, size: int):
        self.size = size


# --------------------------------------------------------------------------
# candidate enumeration
# --------------------------------------------------------------------------
def test_no_mesh_no_dist_candidates():
    """Single-process runs see exactly the pre-PR candidate space."""
    for ext in ((4096,), (64, 64), (64, 64, 64)):
        backs = {c.backend for c in candidates(Problem(ext))}
        assert not backs & set(DIST_BACKENDS), ext


def test_single_device_mesh_adds_nothing():
    backs = {c.backend
             for c in candidates(Problem((64, 64, 64), "Outplace_Complex"),
                            mesh=FakeMesh(1))}
    assert not backs & set(DIST_BACKENDS)


def test_mesh_enumerates_sharded_decompositions():
    mesh = FakeMesh(8)
    keys = {c.key() for c in candidates(
        Problem((64, 64, 64), "Outplace_Complex"), mesh=mesh)}
    assert "slab[8]" in keys
    assert "pencil[2x4]" in keys            # most balanced factorization
    # rank-1: the four-step matrix decomposition
    keys1 = {c.key() for c in candidates(
        Problem((4096,), "Outplace_Complex"), mesh=mesh)}
    assert "dist1d[8]" in keys1
    # rank-2 gets slab only (pencil wants a third axis to keep local)
    keys2 = {c.key() for c in candidates(
        Problem((64, 64), "Outplace_Complex"), mesh=mesh)}
    assert "slab[8]" in keys2
    assert not any(k.startswith("pencil") for k in keys2)


def test_patient_sweeps_decomposition_and_local_engine():
    """PATIENT widens the distributed space on both knobs the tentpole
    names: alternate pencil mesh factorizations and forced local engines."""
    cands = candidates(Problem((64, 64, 64), "Outplace_Complex"),
                       patient=True, mesh=FakeMesh(8))
    keys = {c.key() for c in cands}
    assert len(keys) == len(cands)          # no duplicates
    assert {"pencil[2x4]", "pencil[4x2]"} <= keys
    locals_ = {c.opts().get("local") for c in cands
               if c.backend in DIST_BACKENDS and c.options}
    assert len(locals_) >= 1                # forced local-engine variants
    assert all(k for k in locals_)          # every knob names an engine


def test_dist_supports_gating():
    p3 = Problem((64, 64, 64), "Outplace_Complex")
    assert dist_supports("slab", p3, (8,))
    assert dist_supports("pencil", p3, (2, 4))
    # real kinds never shard: packed half-spectrum breaks a2a divisibility
    assert not dist_supports("slab", Problem((64, 64, 64), "Outplace_Real"),
                             (8,))
    # one device is pure overhead
    assert not dist_supports("slab", p3, (1,))
    # indivisible extents
    assert not dist_supports(
        "slab", Problem((65, 64, 64), "Outplace_Complex"), (8,))
    assert not dist_supports(
        "pencil", Problem((64, 63, 64), "Outplace_Complex"), (2, 4))
    # dist1d is rank-1 batch-1 only
    assert dist_supports(
        "dist1d", Problem((4096,), "Outplace_Complex"), (8,))
    assert not dist_supports(
        "dist1d", Problem((4096,), "Outplace_Complex", batch=4), (8,))
    assert not dist_supports("dist1d", p3, (8,))
    # pencil wants a 2-D mesh shape, slab a flat one
    assert not dist_supports("pencil", p3, (8,))
    assert not dist_supports("slab", p3, (2, 4))


def test_pencil_mesh_shapes():
    assert _pencil_mesh_shapes(8) == [(2, 4)]
    assert set(_pencil_mesh_shapes(8, patient=True)) == {(2, 4), (4, 2)}
    assert _pencil_mesh_shapes(16)[0] == (4, 4)
    assert _pencil_mesh_shapes(2) == []     # Pr >= 2 and Pc >= 2


# --------------------------------------------------------------------------
# interconnect-aware cost model: goldens + crossover
# --------------------------------------------------------------------------
def test_interconnect_cost_goldens_small_extent():
    """At (16,16,16) the a2a latency floor dominates: staying on one device
    is modeled cheapest, and the 1-collective slab undercuts the
    2-collective pencil."""
    p = Problem((16, 16, 16), "Outplace_Complex")
    xla = estimate_bytes_moved(p, Candidate("xla"))
    slab = estimate_bytes_moved(p, Candidate("slab", mesh=(8,)))
    pencil = estimate_bytes_moved(p, Candidate("pencil", mesh=(2, 4)))
    # slab: 7 local passes x 2 x 4 KiB/device + 1 a2a (4*4KiB + 1MiB floor)
    assert xla == 131072.0
    assert slab == 1122304.0
    assert pencil == 2187264.0
    assert xla < slab < pencil


def test_interconnect_cost_goldens_past_crossover():
    """At (64,64,64) x 8 devices the per-device shard shrink beats the
    link cost: both decompositions undercut the single-device plan."""
    p = Problem((64, 64, 64), "Outplace_Complex")
    xla = estimate_bytes_moved(p, Candidate("xla"))
    slab = estimate_bytes_moved(p, Candidate("slab", mesh=(8,)))
    pencil = estimate_bytes_moved(p, Candidate("pencil", mesh=(2, 4)))
    assert xla == 8388608.0
    assert slab == 5767168.0
    assert pencil == 7864320.0
    assert slab < pencil < xla


def test_dist1d_crossover():
    """Small 1-D: single-device wins.  At 2^22 the sharded four-step's
    1/P-sized local work wins despite two all_to_alls."""
    small = Problem((4096,), "Outplace_Complex")
    best_single = min(estimate_bytes_moved(small, c)
                      for c in candidates(small))
    assert best_single < estimate_bytes_moved(
        small, Candidate("dist1d", mesh=(8,)))
    big = Problem((1 << 22,), "Outplace_Complex")
    best_single = min(estimate_bytes_moved(big, c) for c in candidates(big))
    assert estimate_bytes_moved(
        big, Candidate("dist1d", mesh=(8,))) < best_single


def test_planner_picks_dist_only_past_crossover():
    """End-to-end candidate ranking on an 8-device mesh: the min-cost pick
    stays single-device at small extents and goes distributed at large."""
    mesh = FakeMesh(8)

    def best(problem):
        return min(candidates(problem, mesh=mesh),
                   key=lambda c: estimate_bytes_moved(problem, c))

    assert best(
        Problem((16, 16, 16), "Outplace_Complex")
    ).backend not in DIST_BACKENDS
    assert best(Problem((64, 64, 64), "Outplace_Complex")
                ).backend == "slab"


def test_infeasible_dist_candidate_costs_inf():
    p = Problem((64, 64, 64), "Outplace_Real")
    assert estimate_bytes_moved(p, Candidate("slab", mesh=(8,))) == \
        float("inf")


def test_dist_local_engine_minimizes_passes():
    from repro.core.costmodel import hbm_passes
    for n in (16, 64, 512, 4096):
        b = dist_local_engine(n)
        assert hbm_passes(b, n) == min(
            hbm_passes(bb, n) for bb in ("dft", "stockham", "fourstep",
                                         "stockham_pallas", "xla"))


# --------------------------------------------------------------------------
# mesh-shaped wisdom records
# --------------------------------------------------------------------------
def test_wisdom_roundtrips_mesh_field(tmp_path):
    wpath = str(tmp_path / "w.json")
    w = Wisdom(wpath, device_kind="testdev")
    problem = Problem((64, 64, 64), "Outplace_Complex")
    cand = Candidate("pencil", (("local", "stockham_pallas"),), mesh=(2, 4))
    w.record(problem, cand, scope="dist")
    w.save()
    rec = next(iter(json.load(open(wpath)).values()))
    assert rec["mesh"] == [2, 4]
    w2 = Wisdom(wpath, device_kind="testdev")
    got = w2.lookup(problem, scope="dist")
    assert got == cand
    assert got.key() == "pencil[2x4](local=stockham_pallas)"


def test_legacy_wisdom_records_still_load(tmp_path):
    """Pre-PR6 records have no ``mesh`` key — they must load with an empty
    mesh, and their serialized form must stay byte-stable (no mesh field
    sneaking into single-device records)."""
    wpath = str(tmp_path / "w.json")
    w = Wisdom(wpath, device_kind="testdev")
    problem = Problem((4096,), "Outplace_Complex")
    w.record(problem, Candidate("stockham_pallas", (("radix", 8),)))
    w.save()
    rec = next(iter(json.load(open(wpath)).values()))
    assert "mesh" not in rec
    got = Wisdom(wpath, device_kind="testdev").lookup(problem)
    assert got.mesh == ()
    assert got.key() == "stockham_pallas(radix=8)"


# --------------------------------------------------------------------------
# atomic, concurrent-tolerant wisdom writes
# --------------------------------------------------------------------------
def test_wisdom_save_is_atomic_and_leaves_no_temp(tmp_path):
    wpath = str(tmp_path / "w.json")
    w = Wisdom(wpath, device_kind="testdev")
    w.record(Problem((64,)), Candidate("dft"))
    w.save()
    assert json.load(open(wpath))           # complete document on disk
    leftovers = [p for p in tmp_path.iterdir() if p.name != "w.json"]
    assert leftovers == []                  # mkstemp temp replaced, not left


def test_concurrent_sessions_merge_on_save(tmp_path):
    """Two sessions share a wisdom path; the slower save must not clobber
    entries the faster one persisted (merge-on-save, ours win conflicts)."""
    wpath = str(tmp_path / "w.json")
    a = Wisdom(wpath, device_kind="testdev")
    b = Wisdom(wpath, device_kind="testdev")    # loaded before a saved
    pa, pb = Problem((64,)), Problem((128,))
    a.record(pa, Candidate("dft"))
    a.save()
    b.record(pb, Candidate("stockham"))
    b.save()                                    # must keep a's entry
    w = Wisdom(wpath, device_kind="testdev")
    assert w.lookup(pa) == Candidate("dft")
    assert w.lookup(pb) == Candidate("stockham")
    # conflict: the saving session's own (newer) selection wins
    b2 = Wisdom(wpath, device_kind="testdev")
    b2.record(pa, Candidate("fourstep"))
    b2.save()
    assert Wisdom(wpath, device_kind="testdev").lookup(pa) == \
        Candidate("fourstep")


def test_corrupt_wisdom_warns_and_loads_empty(tmp_path):
    """A torn/corrupt file from a crashed session must never take the
    benchmark down — warn, start empty, and the next save repairs it."""
    wpath = tmp_path / "w.json"
    wpath.write_text('{"truncated": ')
    with pytest.warns(UserWarning, match="unreadable wisdom"):
        w = Wisdom(str(wpath), device_kind="testdev")
    assert len(w) == 0
    w.record(Problem((64,)), Candidate("dft"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # save re-reads the corrupt file
        w.save()
    assert Wisdom(str(wpath), device_kind="testdev").lookup(
        Problem((64,))) == Candidate("dft")


# --------------------------------------------------------------------------
# SuiteSpec device-count axis + the distributed support matrix
# --------------------------------------------------------------------------
def test_suitespec_device_counts_roundtrip():
    spec = SuiteSpec(clients=("DistFFTND",), extents=("64x64x64",),
                     device_counts=(1, 2, 4, 8), output=None)
    d = spec.to_dict()
    assert d["device_counts"] == [1, 2, 4, 8]
    spec2 = SuiteSpec.from_dict(json.loads(json.dumps(d)))
    assert spec2.device_counts == (1, 2, 4, 8)
    assert SuiteSpec.from_toml(spec.to_toml()).device_counts == (1, 2, 4, 8)
    with pytest.raises(ValueError, match="device_counts"):
        SuiteSpec(clients=("Planned",), extents=("64",), device_counts=(0,),
                  output=None)


def test_suitespec_without_device_counts_is_legacy_stable():
    spec = SuiteSpec(clients=("Planned",), extents=("64",), output=None)
    assert "device_counts" not in spec.to_dict()
    assert SuiteSpec.from_dict(spec.to_dict()).device_counts == ()


def test_dist_support_matrix_shape_and_claims():
    rows = dist_support_matrix(device_counts=(2, 4, 8))
    by = {}
    for r in rows:
        if r["supported"]:
            by.setdefault(r["backend"], set()).add((r["rank"], r["devices"]))
    # slab covers rank 2+3 where the leading extents divide; the rank-3
    # probe (4,4,8) stops dividing at 8 devices, the rank-2 one (8,16) not
    assert {(2, 2), (3, 2), (2, 4), (3, 4), (2, 8)} <= by["slab"]
    assert (3, 8) not in by["slab"]
    assert all(rank in (2, 3) for rank, _ in by["slab"])
    assert by["pencil"] == {(3, 4), (3, 8)}     # p=2 has no (Pr>=2, Pc>=2)
    assert all(rank == 1 for rank, _ in by["dist1d"])
    # complex-only: no real kind is ever claimed
    assert not any(r["supported"] for r in rows
                   if "Real" in r["kind"])
