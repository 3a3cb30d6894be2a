"""ND-native planner regression tests: golden candidate lists per problem
class, cost-model sanity (bytes-moved monotone in n, infeasible => inf, ND
transpose passes counted, r2c half-spectrum accounting), per-axis mixed
candidates, and wisdom round-trips of per-axis assignments — so model edits
can't silently flip ESTIMATE picks."""

import json
import math
from pathlib import Path

import pytest

from repro.core.backends import (BACKENDS, DFT_MAX_N, FFT2_PALLAS_MAX_ELEMS,
                                 FFT2_PALLAS_VMEM_ELEMS, axis_engine_n)
from repro.core.candidates import Candidate, backend_supports, candidates
from repro.core.client import KINDS, PRECISIONS, Problem
from repro.core.costmodel import (dist_local_engine, estimate_bytes_moved,
                                  estimate_choice, hbm_passes)
from repro.core.wisdom import Wisdom

INF = float("inf")


def homogeneous_backends(problem, patient=False):
    return [c.backend for c in candidates(problem, patient=patient)
            if not c.axes and not c.options]


# --------------------------------------------------------------------------
# golden candidate lists per problem class
# --------------------------------------------------------------------------
def test_golden_candidates_rank1_pow2():
    assert homogeneous_backends(Problem((64,), "Outplace_Complex")) == [
        "xla", "stockham", "fourstep", "dft", "fourstep_pallas",
        "stockham_pallas", "sixstep", "chirpz_pallas", "bluestein"]


def test_golden_candidates_rank1_smooth():
    # 100 = 2^2 * 5^2: 7-smooth (mixed-radix fused kernel applies) and
    # 10x10-factorable, but not pow2
    assert homogeneous_backends(Problem((100,), "Outplace_Complex")) == [
        "xla", "fourstep", "dft", "fourstep_pallas", "stockham_pallas",
        "chirpz_pallas", "bluestein"]


def test_golden_candidates_rank1_prime():
    # 97: prime; dft, the single-pass fft4step (97 x 1) and the chirp
    # paths still apply
    assert homogeneous_backends(Problem((97,), "Outplace_Complex")) == [
        "xla", "dft", "fourstep_pallas", "chirpz_pallas", "bluestein"]


def test_golden_candidates_rank2_pow2_offers_fft2():
    got = homogeneous_backends(Problem((8, 16), "Outplace_Complex"))
    assert got == ["xla", "stockham", "fourstep", "dft", "fourstep_pallas",
                   "stockham_pallas", "sixstep", "fft2_pallas",
                   "chirpz_pallas", "bluestein"]
    # the fused rank-2 kernel is rank-2 only and VMEM-capped
    assert "fft2_pallas" not in homogeneous_backends(
        Problem((16,), "Outplace_Complex"))
    assert "fft2_pallas" not in homogeneous_backends(
        Problem((8, 8, 8), "Outplace_Complex"))
    assert "fft2_pallas" not in homogeneous_backends(
        Problem((1024, 1024), "Outplace_Complex"))


def test_golden_estimate_picks():
    """The ESTIMATE picks the paper tables depend on, pinned per class."""
    assert estimate_choice(Problem((64,))).backend == "dft"
    assert estimate_choice(Problem((4096,))).backend in (
        "fourstep_pallas", "stockham_pallas")
    assert estimate_choice(Problem((1 << 20,))).backend == "xla"
    assert estimate_choice(Problem((64, 64, 64))).backend == "xla"
    for kind in KINDS:
        for precision in PRECISIONS:
            for ext in [(8, 8), (64, 64), (128, 512), (256, 256)]:
                c = estimate_choice(Problem(ext, kind, precision))
                assert c.backend == "fft2_pallas", (ext, kind, precision, c)
    # past the fused tile's VMEM budget the vendor path wins again
    assert estimate_choice(Problem((512, 512))).backend == "xla"


# --------------------------------------------------------------------------
# per-axis (mixed) candidates
# --------------------------------------------------------------------------
def test_mixed_candidates_enumerated_and_unique():
    cands = candidates(Problem((4, 4096), "Outplace_Complex"), patient=True)
    keys = [c.key() for c in cands]
    assert len(keys) == len(set(keys))
    mixed = [c for c in cands if c.axes]
    assert mixed, "rank-2 space must hold per-axis assignments"
    for c in mixed:
        assert c.backend == "nd" and len(c.axes) == 2
        assert estimate_bytes_moved(Problem((4, 4096), "Outplace_Complex"),
                                    c) < INF     # pruned by the model
    # rank-1 never gets mixed assignments
    assert not [c for c in candidates(Problem((4096,)), patient=True)
                if c.axes]


def test_mixed_candidate_cost_is_per_axis_sum():
    p = Problem((4, 4096), "Outplace_Complex")
    mixed = Candidate("nd", axes=(Candidate("dft"),
                                  Candidate("stockham_pallas")))
    elems = p.n_elems
    outer = (hbm_passes("dft", 4) + 2.0) * 2.0 * elems * 8   # + swap pair
    inner = hbm_passes("stockham_pallas", 4096) * 2.0 * elems * 8
    assert estimate_bytes_moved(p, mixed) == outer + inner


def test_per_axis_knobs_survive_in_plan():
    mixed = Candidate("nd", axes=(Candidate("dft"),
                                  Candidate("stockham_pallas",
                                            (("radix", 4),))))
    assert mixed.per_axis(2)[1].opts() == {"radix": 4}
    assert mixed.key() == "nd[dft;stockham_pallas(radix=4)]"
    with pytest.raises(ValueError):
        mixed.per_axis(3)


# --------------------------------------------------------------------------
# cost-model sanity
# --------------------------------------------------------------------------
def test_bytes_moved_monotone_in_n():
    for backend in ("xla", "stockham", "stockham_pallas", "chirpz_pallas",
                    "bluestein"):
        costs = [estimate_bytes_moved(Problem((1 << e,), "Outplace_Complex"),
                                      Candidate(backend))
                 for e in range(2, 15)]
        assert all(a <= b for a, b in zip(costs, costs[1:])), backend


def test_infeasible_is_inf():
    assert estimate_bytes_moved(Problem((100,), "Outplace_Complex"),
                                Candidate("stockham")) == INF
    assert estimate_bytes_moved(Problem((1024, 1024), "Outplace_Complex"),
                                Candidate("fft2_pallas")) == INF
    # offered (within the hard cap) but past the VMEM budget: modeled inf
    p512 = Problem((512, 512), "Outplace_Complex")
    assert 512 * 512 <= FFT2_PALLAS_MAX_ELEMS
    assert 512 * 512 > FFT2_PALLAS_VMEM_ELEMS
    assert backend_supports("fft2_pallas", p512)
    assert estimate_bytes_moved(p512, Candidate("fft2_pallas")) == INF
    # ...but the VMEM budget binds the PACKED tile for real kinds: a
    # 512x256 real problem really holds a 512x128 = 2^16 tile, so the
    # fused kernel stays modeled-feasible (and wins ESTIMATE) there
    pr = Problem((512, 256), "Outplace_Real")
    assert estimate_bytes_moved(pr, Candidate("fft2_pallas")) < INF
    assert estimate_choice(pr).backend == "fft2_pallas"
    assert estimate_bytes_moved(Problem((512, 256), "Outplace_Complex"),
                                Candidate("fft2_pallas")) == INF


def test_nd_transpose_passes_counted():
    """nd._apply_last pays one swapaxes in + one out per NON-innermost axis
    and none for the innermost: the model must charge exactly that."""
    p1 = Problem((4096,), "Outplace_Complex")
    p2 = Problem((4096, 4096), "Outplace_Complex")
    one = estimate_bytes_moved(p1, Candidate("stockham_pallas"))
    both = estimate_bytes_moved(p2, Candidate("stockham_pallas"))
    # rank-2: inner axis = 1 engine pass, outer = 1 engine + 2 swap passes;
    # rank-2 signal holds 4096x more elements than the rank-1 probe
    assert both == (1 + 3) * 4096 * one
    # the fused whole-transform backends pay no transpose traffic
    assert estimate_bytes_moved(p2, Candidate("xla")) == 2 * 4096 * one


def test_r2c_half_spectrum_accounting():
    pc = Problem((4096,), "Outplace_Complex")
    pr = Problem((4096,), "Outplace_Real")
    assert estimate_bytes_moved(pr, Candidate("stockham_pallas")) == \
        estimate_bytes_moved(pc, Candidate("stockham_pallas")) / 2
    # outer axes of a real transform run on n//2+1 half-spectrum bins
    pr2 = Problem((8, 4096), "Outplace_Real")
    inner = hbm_passes("stockham_pallas", 2048) * 2.0 * (8 * 2048) * 8
    outer = (hbm_passes("stockham_pallas", 8) + 2.0) * 2.0 * (8 * 2049) * 8
    assert estimate_bytes_moved(pr2, Candidate("stockham_pallas")) == \
        inner + outer
    # odd real lengths fall back to the full-length complex engine
    assert axis_engine_n(Problem((15,), "Outplace_Real"), 0) == 15
    assert axis_engine_n(Problem((16,), "Outplace_Real"), 0) == 8
    assert axis_engine_n(Problem((16,), "Outplace_Complex"), 0) == 16


def test_backend_supports_respects_packed_length():
    """Real-kind feasibility looks at the engine length (n//2), not the
    nominal extent — a backend that can't run the packed half is out."""
    # stockham needs pow2 at the ENGINE length; real 2*odd fails even
    # though... (6 is not pow2 either way; 2*pow2 always halves to pow2)
    assert backend_supports("stockham", Problem((8,), "Outplace_Real"))
    assert not backend_supports("stockham", Problem((6,), "Outplace_Real"))
    # sixstep's packed half can drop below its own composition minimum;
    # the engine falls back to the fused kernel there, so support holds
    assert backend_supports("sixstep", Problem((4,), "Outplace_Real"))
    assert not backend_supports("sixstep", Problem((2,), "Outplace_Real"))


def test_odd_length_real_kinds_route_to_full_complex_chirp():
    """The packed r2c trick only exists for even n: an odd-length real kind
    plans at the FULL extent, on the full-complex chirp path — feasibility,
    caps, and the cost model all see n, never a meaningless n//2."""
    p = Problem((6859,), "Outplace_Real")
    assert axis_engine_n(p, 0) == 6859              # full length, not 3429
    backs = [c.backend for c in candidates(p) if not c.axes]
    assert "chirpz_pallas" in backs and "bluestein" in backs
    # the chirp candidates enter through backend_supports like everyone
    # else (no unconditional append), so the cap binds at the full length:
    # an odd n past CHIRPZ_PALLAS_MAX_N keeps only the jnp chirp
    from repro.core.backends import CHIRPZ_PALLAS_MAX_N
    p_big = Problem(((CHIRPZ_PALLAS_MAX_N + 1),), "Outplace_Real")
    assert not backend_supports("chirpz_pallas", p_big)
    assert backend_supports("bluestein", p_big)
    assert "chirpz_pallas" not in [c.backend for c in candidates(p_big)]
    assert "bluestein" in [c.backend for c in candidates(p_big)]
    # the model charges full-length traffic for the odd real extent (the
    # even neighbor runs packed at half the elements)
    odd = estimate_bytes_moved(p, Candidate("bluestein"))
    even = estimate_bytes_moved(Problem((6860,), "Outplace_Real"),
                                Candidate("bluestein"))
    assert odd > even
    # and the ESTIMATE pick lands on the fused chirp, not xla/jnp-bluestein
    assert estimate_choice(p).backend == "chirpz_pallas"


# --------------------------------------------------------------------------
# wisdom round-trips per-axis assignments
# --------------------------------------------------------------------------
def test_wisdom_roundtrips_axes_candidates(tmp_path):
    p = Problem((4, 4096), "Outplace_Complex")
    cand = Candidate("nd", axes=(Candidate("dft"),
                                 Candidate("stockham_pallas",
                                           (("radix", 4), ("tile_b", 16)))))
    path = str(tmp_path / "w.json")
    w = Wisdom(path, device_kind="testdev")
    w.record(p, cand)
    w.save()
    stored = json.load(open(path))
    assert len(stored) == 1
    w2 = Wisdom(path, device_kind="testdev")
    assert w2.lookup(p) == cand
    # legacy flat records (no 'axes') still load
    key = next(iter(stored))
    stored[key] = {"backend": "xla", "options": []}
    json.dump(stored, open(path, "w"))
    assert Wisdom(path, device_kind="testdev").lookup(p) == Candidate("xla")


def test_backends_registry_is_complete():
    """Every backend the candidate space can emit appears in BACKENDS (the
    conformance matrix sweeps exactly this tuple)."""
    seen = set()
    for ext in [(64,), (100,), (97,), (8, 16), (4, 4, 8), (1 << 16,)]:
        for c in candidates(Problem(ext, "Outplace_Complex"), patient=True):
            for ax in (c.per_axis(len(ext)) if c.axes else (c,)):
                seen.add(ax.backend)
    assert seen <= set(BACKENDS) | {"nd"}
    assert set(BACKENDS) <= seen | {"nd"}


# --------------------------------------------------------------------------
# ESTIMATE on the TPU for the chip benchmark's problems
# --------------------------------------------------------------------------
TRAFFIC = Path(__file__).resolve().parents[1] / "bench" / "traffic"

#: (mix, extents, batch) -> ESTIMATE's key for (Outplace_Complex,
#: Outplace_Real) on the TPU.  Only the length-361 problems run the dense
#: DFT; every other pick is the one the planner made before DFT_MAX_N.
TPU_PICKS = {
    ("pow2", "1048576", 64): ("xla", "xla"),
    ("pow2", "4096x4096", 4): ("xla", "xla"),
    ("pow2", "256x256x256", 4): ("xla", "xla"),
    ("pow2", "4096", 16384): ("fourstep_pallas", "fourstep_pallas"),
    ("pow2", "256x256", 1024): ("xla", "xla"),
    ("pow2", "128", 262144): ("dft", "dft"),
    ("nonpow2", "18432", 4096): ("xla", "fourstep_pallas"),
    ("nonpow2", "6859", 8192): ("xla", "xla"),
    ("nonpow2", "361x361", 384): ("dft", "dft"),
    ("nonpow2", "361x361x361", 1): ("dft", "dft"),
}


@pytest.fixture
def on_tpu(monkeypatch):
    import repro.core.device as device

    monkeypatch.setattr(device, "on_tpu", lambda: True)


def test_tpu_picks_cover_the_benchmark_mixes():
    mixes = {(mix, ext, batch)
             for mix in ("pow2", "nonpow2")
             for ext, batch in json.loads(
                 (TRAFFIC / f"{mix}.json").read_text())["problems"]}
    assert mixes == set(TPU_PICKS)


@pytest.mark.parametrize("mix,ext,batch", sorted(TPU_PICKS),
                         ids=[f"{m}-{e}" for m, e, _ in sorted(TPU_PICKS)])
def test_tpu_estimate_picks(on_tpu, mix, ext, batch):
    extents = tuple(int(v) for v in ext.split("x"))
    for kind, want in zip(("Outplace_Complex", "Outplace_Real"),
                          TPU_PICKS[mix, ext, batch]):
        problem = Problem(extents, kind, "float", batch=batch)
        assert estimate_choice(problem).key() == want, (kind, want)


def test_tpu_dense_dft_cap(on_tpu):
    """The dense DFT is offered up to DFT_MAX_N = 512 and charged less than
    the four-step kernel there, so the four-chip cell's local length 512
    runs it; 513 is refused."""
    assert DFT_MAX_N == 512
    assert backend_supports("dft", Problem((512,)))
    assert not backend_supports("dft", Problem((513,)))
    for n in (361, 375, 384, 400, 512):
        assert hbm_passes("dft", n) < hbm_passes("fourstep_pallas", n)
    assert dist_local_engine(512) == "dft"


class FakeMesh:
    """Enough mesh for ESTIMATE: candidate enumeration reads ``.size``."""
    def __init__(self, size: int):
        self.size = size


def test_tpu_estimate_accfft_512_over_four_chips(on_tpu):
    """The four-chip cell's problem, 512^3 C2C over a flat mesh of four:
    the distributed client's ESTIMATE picks slab[4], and every local axis
    of that plan runs the dense DFT."""
    from repro.core.client import Context
    from repro.core.clients.dist_fft import DistFFTNDClient
    from repro.core.candidates import dist_local_lengths

    problem = Problem((512, 512, 512), "Outplace_Complex", "float", batch=1)
    client = DistFFTNDClient(problem, Context())
    client._base_mesh = FakeMesh(4)
    cand = client._make_plan().candidate
    assert cand.key() == "slab[4]"
    lengths = [n for n, _ in dist_local_lengths(problem, cand)]
    assert lengths == [512, 512, 512]
    assert [dist_local_engine(n) for n in lengths] == ["dft"] * 3
