"""Widened planner: new fused-kernel backends in the candidate space, the
PATIENT knob sweep, the bytes-moved ESTIMATE model, and wisdom-persisted
PATIENT selections that let a warm Session skip the sweep entirely."""

import json
import math

import pytest

from repro.core.client import Problem
from repro.core.backends import STOCKHAM_PALLAS_VMEM_N
from repro.core.candidates import Candidate, candidates
from repro.core.costmodel import (estimate_bytes_moved, estimate_choice,
                                  hbm_passes)
from repro.core.plan import PlanRigor, make_plan
from repro.core.suite import Session, SuiteSpec
from repro.core.wisdom import Wisdom
from repro.core.clients.jax_fft import build_forward


# --------------------------------------------------------------------------
# candidate space
# --------------------------------------------------------------------------
def test_new_backends_offered_for_all_pow2_up_to_2_20():
    for e in range(2, 21):
        backs = {c.backend for c in candidates(Problem((1 << e,)))}
        assert "stockham_pallas" in backs, f"2^{e}"
        assert "sixstep" in backs, f"2^{e}"
    # rank-3 pow2 (per-axis feasibility)
    backs = {c.backend for c in candidates(Problem((16, 16, 16)))}
    assert {"stockham_pallas", "sixstep"} <= backs
    # non-smooth and too-small axes are excluded
    assert "stockham_pallas" not in {
        c.backend for c in candidates(Problem((97,)))}
    assert "sixstep" not in {c.backend for c in candidates(Problem((2,)))}


def test_mixed_radix_and_chirpz_offered_for_nonpow2():
    """The paper's radix357 and oddshape classes are first-class: 7-smooth
    lengths get the mixed-radix fused kernel, everything gets the fused
    chirp-Z (up to its padded six-step cap)."""
    for n in (12, 100, 3072, 18432):          # radix357
        backs = {c.backend for c in candidates(Problem((n,),
                                                       "Outplace_Complex"))}
        assert "stockham_pallas" in backs, n
        assert "chirpz_pallas" in backs, n
    for n in (19, 361, 6859):                 # oddshape
        backs = {c.backend for c in candidates(Problem((n,),
                                                       "Outplace_Complex"))}
        assert "stockham_pallas" not in backs, n
        assert "chirpz_pallas" in backs, n
        assert "bluestein" in backs, n


def test_sixstep_split_knobs_are_honored_by_engine():
    """Every split_n1 the PATIENT sweep emits must be one choose_split
    accepts — a rejected knob silently duplicates the default candidate."""
    from repro.fft.sixstep import choose_split
    for e in (8, 12, 16, 20, 22, 24):
        n = 1 << e
        for c in candidates(Problem((n,)), patient=True):
            if c.backend == "sixstep" and "split_n1" in c.opts():
                n1 = c.opts()["split_n1"]
                assert choose_split(n, n1) == (n1, n // n1), (n, n1)


def test_patient_widens_with_kernel_knobs():
    cands = candidates(Problem((1 << 16,)), patient=True)
    keys = {c.key() for c in cands}
    assert len(cands) >= 10
    knobbed = [c for c in cands if c.options]
    assert len(knobbed) >= 6        # the widened PATIENT space
    assert any(c.backend == "stockham_pallas" and "radix" in c.opts()
               and "tile_b" in c.opts() for c in knobbed)
    assert any(c.backend == "sixstep" and "split_n1" in c.opts()
               for c in knobbed)
    assert any(c.backend == "sixstep" and "tile_b" in c.opts()
               for c in knobbed)
    assert len(keys) == len(cands)  # no duplicate candidates


# --------------------------------------------------------------------------
# bytes-moved ESTIMATE model
# --------------------------------------------------------------------------
def test_hbm_passes_model():
    n = 1 << 12
    assert hbm_passes("stockham_pallas", n) == 1.0      # one HBM touch
    assert hbm_passes("fourstep_pallas", n) == 1.0
    assert hbm_passes("stockham", n) == 12.0            # one pass per stage
    assert hbm_passes("sixstep", n) == 5.0
    # beyond the VMEM tile budget the fused Stockham is not a real option
    assert math.isinf(hbm_passes("stockham_pallas",
                                 STOCKHAM_PALLAS_VMEM_N * 2))
    assert math.isinf(hbm_passes("fourstep_pallas", 1 << 15))
    assert math.isinf(hbm_passes("stockham_pallas", 97))   # not 7-smooth
    # mixed radix: any 7-smooth length is still a single touch
    assert hbm_passes("stockham_pallas", 100) == 1.0
    assert hbm_passes("stockham_pallas", 3072) == 1.0
    assert hbm_passes("stockham_pallas", 18432) == 1.0


def test_hbm_passes_chirpz_model():
    # n=6859 convolves on the mixed-radix kernel at the smallest 7-smooth
    # m >= 2n-1 (13720 = 2^3*5*7^3, tighter than pow2 16384):
    # (2*1 engine passes + 3 pointwise) * m/n
    assert hbm_passes("chirpz_pallas", 6859) == \
        pytest.approx(5.0 * 13720 / 6859)
    # past the VMEM tile budget the padded transforms ride sixstep (5
    # passes each) at the pow2 padding
    n_big = (1 << 15) + 1                 # pow2 m = 2^17
    assert hbm_passes("chirpz_pallas", n_big) == \
        pytest.approx(13.0 * (1 << 17) / n_big)
    assert math.isinf(hbm_passes("chirpz_pallas", (1 << 23) + 1))
    # the vendor path pays its own modeled chirp fallback on non-smooth n
    assert hbm_passes("xla", 1 << 12) == 2.0
    assert hbm_passes("xla", 6859) == pytest.approx(6.0 * (1 << 14) / 6859)
    # ...which the fused chirp undercuts
    assert hbm_passes("chirpz_pallas", 6859) < hbm_passes("xla", 6859)


def test_estimate_bytes_moved_scales():
    # complex kinds: the engine moves the full signal
    p64 = Problem((4096,), "Outplace_Complex")
    one_pass = estimate_bytes_moved(p64, Candidate("stockham_pallas"))
    staged = estimate_bytes_moved(p64, Candidate("stockham"))
    assert one_pass == 2.0 * 4096 * 8        # read + write, c64 bytes
    assert staged == 12 * one_pass           # log2(4096) passes
    # double precision doubles the traffic
    assert estimate_bytes_moved(Problem((4096,), "Outplace_Complex",
                                        precision="double"),
                                Candidate("stockham_pallas")) == 2 * one_pass
    # real kinds ride the packed half-length path: half the traffic (and
    # one fewer stage for the staged backend, which runs at n/2)
    real = estimate_bytes_moved(Problem((4096,), "Outplace_Real"),
                                Candidate("stockham_pallas"))
    assert real == one_pass / 2


def test_estimate_choice_uses_model():
    # seed-pinned behaviors stay
    assert estimate_choice(Problem((64,))).backend == "dft"
    assert estimate_choice(Problem((1 << 20,))).backend == "xla"
    # mid-size pow2: a single-HBM-touch fused kernel wins the model
    assert estimate_choice(Problem((4096,))).backend in (
        "fourstep_pallas", "stockham_pallas")
    # beyond every fused kernel's reach the vendor path wins again
    assert estimate_choice(Problem((1 << 18,))).backend == "xla"


def test_estimate_pins_nonpow2_classes():
    """Acceptance pins: radix357 and oddshape extents plan onto fused
    Pallas paths, never the xla / jnp-bluestein fallbacks."""
    fused = ("stockham_pallas", "fourstep_pallas", "chirpz_pallas")
    for kind in ("Outplace_Complex", "Outplace_Real"):
        # radix357 (e.g. 3072 = 3*2^10): one-touch mixed-radix territory
        assert estimate_choice(Problem((3072,), kind)).backend in fused
        assert estimate_choice(Problem((18432,), kind)).backend in fused
        # oddshape (e.g. 6859 = 19^3): the fused chirp-Z
        assert estimate_choice(
            Problem((6859,), kind)).backend == "chirpz_pallas"
    # past the fourstep kernel's 16384 cap only the mixed-radix kernel
    # offers a single touch, so the pick is specific
    assert estimate_choice(
        Problem((18432,), "Outplace_Complex")).backend == "stockham_pallas"


def test_patient_sweeps_chirpz_knobs():
    cands = candidates(Problem((6859,), "Outplace_Complex"), patient=True)
    keys = {c.key() for c in cands}
    assert "chirpz_pallas(engine=stockham_pallas)" in keys
    assert "chirpz_pallas(engine=sixstep)" in keys
    assert "chirpz_pallas(tile_b=16)" in keys
    assert len(keys) == len(cands)  # no duplicate candidates


def test_patient_chirpz_engine_knob_honors_every_axis():
    """A forced chirp engine applies to every axis of a separable ND plan,
    so a knob is only emitted when ALL axes' padded lengths fit it — a
    (2^21, 100) problem pads axis 0 to 2^22 > the stockham_pallas cap,
    which must exclude that engine (and keep sixstep, which covers 2^22)."""
    cands = candidates(Problem((1 << 21, 100), "Outplace_Complex"),
                       patient=True)
    keys = {c.key() for c in cands}
    assert "chirpz_pallas(engine=stockham_pallas)" not in keys
    assert "chirpz_pallas(engine=sixstep)" in keys
    # every emitted chirp engine knob must actually build (no raise)
    from repro.fft.bluestein import resolve_engine
    for c in cands:
        if c.backend == "chirpz_pallas" and "engine" in c.opts():
            for ax_n in (1 << 21, 100):
                eng, m = resolve_engine(ax_n, c.opts()["engine"])
                if eng == "stockham_pallas":
                    assert m <= 1 << 20, (c.key(), ax_n, m)


# --------------------------------------------------------------------------
# PATIENT sweep -> wisdom -> warm reuse
# --------------------------------------------------------------------------
def test_patient_measures_candidates_and_roundtrips_wisdom(tmp_path):
    """Acceptance: a PATIENT plan for a large extent records per-candidate
    measured_ms for >= 6 candidates and round-trips through wisdom."""
    problem = Problem((1 << 16,), "Outplace_Complex", "float")
    wpath = str(tmp_path / "wisdom.json")
    w = Wisdom(wpath, device_kind="testdev")
    plan = make_plan(problem, PlanRigor.PATIENT,
                     build=lambda c: build_forward(problem, c), wisdom=w)
    assert len(plan.measured_ms) >= 6
    finite = [v for v in plan.measured_ms.values() if v == v]
    assert len(finite) >= 6
    assert plan.candidate.key() in plan.measured_ms
    assert plan.plan_time_ms > 0

    # the winning candidate (knobs included) persists through the JSON store
    w.save()
    stored = json.load(open(wpath))
    assert len(stored) == 1
    w2 = Wisdom(wpath, device_kind="testdev")
    assert w2.lookup(problem) == plan.candidate

    # warm planner: wisdom short-circuits the sweep (no timings, ~instant)
    plan2 = make_plan(problem, PlanRigor.PATIENT,
                      build=lambda c: build_forward(problem, c), wisdom=w2)
    assert plan2.candidate == plan.candidate
    assert plan2.measured_ms == {}
    assert plan2.plan_time_ms < plan.plan_time_ms


def test_buildless_measure_never_records_wisdom(tmp_path):
    """make_plan under MEASURE/PATIENT without a build falls back to the
    untimed ESTIMATE pick; recording that would let the wisdom-first
    short-circuit lock in an unmeasured choice forever."""
    problem = Problem((1024,), "Outplace_Complex", "float")
    w = Wisdom(str(tmp_path / "w.json"), device_kind="testdev")
    plan = make_plan(problem, PlanRigor.MEASURE, wisdom=w)  # build=None
    assert plan.measured_ms == {}
    assert w.lookup(problem) is None       # nothing persisted
    # a real sweep afterwards still runs and records
    plan2 = make_plan(problem, PlanRigor.MEASURE,
                      build=lambda c: build_forward(problem, c), wisdom=w)
    assert plan2.measured_ms and w.lookup(problem) == plan2.candidate


def test_warm_session_reuses_patient_wisdom(tmp_path, monkeypatch):
    """Suite-level: PATIENT run 1 sweeps and persists wisdom; a second
    Session pointed at the same wisdom file never sweeps."""
    import repro.core.plan as plan_mod

    calls = []
    real_measure = plan_mod.measure_plan

    def counting_measure(*a, **kw):
        calls.append(1)
        return real_measure(*a, **kw)

    monkeypatch.setattr(plan_mod, "measure_plan", counting_measure)
    wpath = str(tmp_path / "wisdom.json")
    spec = SuiteSpec(clients=("Planned",), extents=("512",),
                     kinds=("Outplace_Complex",), precisions=("float",),
                     rigor="patient", warmups=0, repetitions=1,
                     wisdom=wpath, output=None)
    rs1 = Session().run(spec)
    assert not rs1.failures(), [r.error for r in rs1.failures()]
    assert len(calls) >= 1          # cold: the sweep ran
    import os
    assert os.path.exists(wpath)    # Session persisted the tuned selection

    calls.clear()
    rs2 = Session().run(spec)       # fresh Session, same wisdom file
    assert not rs2.failures(), [r.error for r in rs2.failures()]
    assert calls == []              # warm: sweep skipped entirely

    s = rs2.summary()
    assert s["failures"] == 0
    assert s["plan_time_ms"] > 0    # init ops still carry compile time


def test_pinned_client_persists_scoped_wisdom(tmp_path, monkeypatch):
    """Backend-pinned clients sweep only their own knobs; the winner
    persists under a backend-scoped wisdom key (so it can't clobber the
    open planner's entry) and a warm Session skips the pinned sweep too."""
    import repro.core.plan as plan_mod

    calls = []
    real_measure = plan_mod.measure_plan

    def counting_measure(*a, **kw):
        calls.append(1)
        return real_measure(*a, **kw)

    monkeypatch.setattr(plan_mod, "measure_plan", counting_measure)
    wpath = str(tmp_path / "wisdom.json")
    spec = SuiteSpec(clients=("StockhamPallas",), extents=("256",),
                     kinds=("Outplace_Complex",), precisions=("float",),
                     rigor="patient", warmups=0, repetitions=1,
                     wisdom=wpath, output=None)
    rs1 = Session().run(spec)
    assert not rs1.failures(), [r.error for r in rs1.failures()]
    assert len(calls) >= 1

    stored = json.load(open(wpath))
    assert all(k.endswith("|stockham_pallas") for k in stored)  # scoped
    assert all(v["backend"] == "stockham_pallas" for v in stored.values())

    calls.clear()
    rs2 = Session().run(spec)       # fresh Session, same wisdom file
    assert not rs2.failures(), [r.error for r in rs2.failures()]
    assert calls == []              # pinned sweep skipped

    # scoped entries are invisible to the open planner's unscoped lookup
    w = Wisdom(wpath, device_kind=Session().device_kind)
    assert w.lookup(Problem((256,), "Outplace_Complex", "float")) is None
    assert w.lookup(Problem((256,), "Outplace_Complex", "float"),
                    scope="stockham_pallas") is not None

    # WISDOM_ONLY honors the persisted scoped knobs...
    spec_wo = SuiteSpec(clients=("StockhamPallas",), extents=("256",),
                        kinds=("Outplace_Complex",), precisions=("float",),
                        rigor="wisdom_only", warmups=0, repetitions=1,
                        wisdom=wpath, output=None)
    rs3 = Session().run(spec_wo)
    assert not rs3.failures(), [r.error for r in rs3.failures()]
    assert calls == []
    # ...and a wisdom miss is an fftw NULL plan (recorded failure), not a
    # silent fall-back to untuned defaults
    spec_miss = SuiteSpec(clients=("StockhamPallas",), extents=("128",),
                          kinds=("Outplace_Complex",), precisions=("float",),
                          rigor="wisdom_only", warmups=0, repetitions=1,
                          wisdom=wpath, output=None)
    rs4 = Session().run(spec_miss)
    fails = rs4.failures()
    assert fails and "NULL plan" in fails[0].error
