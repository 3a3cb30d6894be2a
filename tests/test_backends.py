"""The backend table, pinned per backend: per-axis feasibility off and on
the TPU, the default model's HBM passes, and the family, whole-transform and
TPU-withdrawn flags, on a grid of lengths that holds every cap on both
sides.  Any drift here is a change of what the planner offers or charges."""

import pytest

import repro.core.device as device
from repro.core.backends import BACKENDS, TABLE
from repro.core.candidates import axis_feasible
from repro.core.costmodel import DEFAULT_MODEL

INF = float("inf")

GRID = (1, 2, 3, 4, 5, 128, 361, 384, 385, 512, 513, 4096, 6859, 16384,
        16385, 18432, 1 << 15, (1 << 15) + 1, 1 << 20, (1 << 20) + 1,
        1 << 23, (1 << 23) + 1, 1 << 24, (1 << 24) + 1)

#: backend: (feasible off the TPU, feasible on it — one character per GRID
#: length, "1" feasible — the default model's passes per GRID length,
#: family, whole-transform, withdrawn on the TPU)
PINNED = {
    "xla": (
        "111111111111111111111111",
        "111111111111111111111111",
        [2.0, 2.0, 2.0, 2.0, 2.0, 2.0, 17.019390581717452, 2.0,
         15.958441558441558, 2.0, 23.953216374269005, 2.0,
         14.332118384604168, 2.0, 23.99853524565151, 2.0, 2.0,
         23.99926760047606, 2.0, 23.999977111838234, 2.0,
         23.999997138977392, 2.0, 23.99999856948861],
        "xla", True, False),
    "stockham": (
        "11.1.1...1.1.1..1.1.1.1.",
        "11.1.1...1.1.1..1.1.1.1.",
        [1.0, 1.0, INF, 2.0, INF, 7.0, INF, INF, INF, 9.0, INF, 12.0, INF,
         14.0, INF, INF, 15.0, INF, 20.0, INF, 23.0, INF, 24.0, INF],
        "jnp", False, False),
    "fourstep": (
        "11111111111111111.1.1.1.",
        "11111111111111111.1.1.1.",
        [2.0] * 10 + [4.0, 4.0, 4.0, 4.0, 6.0, 4.0, 4.0, INF, 6.0, INF,
                      6.0, INF, 8.0, INF],
        "jnp", False, False),
    "dft": (
        "1111111111..............",
        "1111111111..............",
        [1.0] * 10 + [INF] * 14,
        "pallas", False, False),
    "fourstep_pallas": (
        "111111111111.1..........",
        "111111111111.1..........",
        [5.0] * 10 + [1.0, 1.0, INF, 1.0] + [INF] * 10,
        "pallas", False, False),
    "stockham_pallas": (
        "111111.1.1.1.1.11.1.....",
        "........................",
        [1.0, 1.0, 1.0, 1.0, 1.0, 1.0, INF, 1.0, INF, 1.0, INF, 1.0, INF,
         1.0, INF, 1.0, 1.0, INF, INF, INF, INF, INF, INF, INF],
        "pallas", False, True),
    "sixstep": (
        ".1.1.1...1.1.1..1.1.1.1.",
        "........................",
        [INF, INF, INF, 5.0, INF, 5.0, INF, INF, INF, 5.0, INF, 5.0, INF,
         5.0, INF, INF, 5.0, INF, 5.0, INF, 5.0, INF, 5.0, INF],
        "pallas", False, True),
    "fft2_pallas": (
        "........................",
        "........................",
        [INF] * 24,
        "pallas", True, True),
    "chirpz_pallas": (
        "111111111111111111111...",
        "........................",
        [5.0, 7.5, 8.333333333333334, 8.75, 9.0, 10.0, 10.096952908587259,
         10.0, 10.18181818181818, 10.0, 10.029239766081872, 10.0,
         10.001457938474996, 10.0, 51.99682636557827, 46.22222222222222,
         26.0, 51.998413134364796, 26.0, 51.99995040898284, 26.0, INF, INF,
         INF],
        "pallas", False, True),
    "bluestein": (
        "111111111111111111111111",
        "111111111111111111111111",
        [5.0, 16.0, 29.333333333333332, 22.0, 44.800000000000004, 52.0,
         90.77008310249307, 85.33333333333333, 85.11168831168831, 64.0,
         139.7270955165692, 82.0, 105.10220148709725, 94.0,
         199.98779371376258, 177.77777777777777, 100.0, 211.99353047087186,
         130.0, 271.9997406008333, 148.0, 307.9999632835432, 154.0,
         319.9999809265148],
        "jnp", False, False),
}


def _feasible(backend: str) -> str:
    return "".join("1" if axis_feasible(backend, n) else "." for n in GRID)


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_record(monkeypatch, backend):
    assert tuple(PINNED) == BACKENDS   # the enumeration order, too
    off_tpu, on_tpu, passes, family, whole, withdrawn = PINNED[backend]
    assert _feasible(backend) == off_tpu
    assert [DEFAULT_MODEL.hbm_passes(backend, n) for n in GRID] == passes
    rec = TABLE[backend]
    assert (rec.family, not rec.separable, rec.tpu_withdrawn) \
        == (family, whole, withdrawn)
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    assert _feasible(backend) == on_tpu
    # hbm_passes ignores the platform: the same passes on the TPU
    assert [DEFAULT_MODEL.hbm_passes(backend, n) for n in GRID] == passes
