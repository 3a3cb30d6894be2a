"""Extents handling: parsing, classification and the paper's extent classes.

gearshifft names its extent classes powerof2 / radix357 / oddshape (Fig. 7);
we reproduce the same taxonomy and the '-e 128x128 1024' CLI syntax.
"""

from __future__ import annotations

import math
from typing import Iterator, Sequence


def parse_extents(spec: str) -> tuple[int, ...]:
    """'128x128x128' -> (128, 128, 128); '1024' -> (1024,)."""
    try:
        ext = tuple(int(p) for p in spec.lower().split("x"))
    except ValueError as e:
        raise ValueError(f"bad extents spec {spec!r}") from e
    if not ext or any(v < 1 for v in ext) or len(ext) > 3:
        raise ValueError(f"bad extents spec {spec!r} (rank 1..3, positive)")
    return ext


def format_extents(ext: Sequence[int]) -> str:
    return "x".join(str(v) for v in ext)


def total_elems(ext: Sequence[int]) -> int:
    return math.prod(ext)


def _factors_only(n: int, primes: Sequence[int]) -> bool:
    for p in primes:
        while n % p == 0:
            n //= p
    return n == 1


def next_pow2(v: int) -> int:
    """Smallest power of two >= v (shared by the planner's cost model and
    the pow2-padded convolution engines)."""
    m = 1
    while m < v:
        m *= 2
    return m


def next_smooth(v: int, primes: Sequence[int] = (2, 3, 5, 7)) -> int:
    """Smallest integer >= v whose prime factors all lie in ``primes`` —
    the padding helper for engines that accept any smooth length (the
    mixed-radix Stockham kernel): a chirp-Z convolution at 7-smooth m
    instead of next_pow2 can shrink the padded work by nearly 2x."""
    v = max(1, v)
    while not _factors_only(v, primes):
        v += 1
    return v


#: The jnp four-step engine's factorisation (:mod:`repro.fft.fourstep`),
#: here so that the planner reads it without importing JAX: the largest
#: column radix (the MXU tile edge) and the longest row the engine runs as
#: one dense DFT matmul.
FOURSTEP_MAX_RADIX = 128
FOURSTEP_DENSE_MAX_N = 512


def fourstep_split(n: int) -> tuple[int, int]:
    """n = n1 * n2 for one four-step level: n1 the largest power of two up
    to FOURSTEP_MAX_RADIX that divides n, else n's smallest odd prime."""
    for cand in (128, 64, 32, 16, 8, 4, 2):
        if n % cand == 0:
            return cand, n // cand
    for cand in range(3, FOURSTEP_MAX_RADIX + 1, 2):
        if n % cand == 0:
            return cand, n // cand
    raise ValueError(
        f"fourstep cannot factor n={n} with radices <= {FOURSTEP_MAX_RADIX}; "
        "use the bluestein backend for large-prime lengths")


def fourstep_levels(n: int) -> int:
    """The dense DFT matmul passes the four-step runs at length n."""
    if n <= FOURSTEP_DENSE_MAX_N:
        return 1
    return 1 + fourstep_levels(fourstep_split(n)[1])


def classify(ext: Sequence[int]) -> str:
    """Paper extent classes: powerof2 | radix357 | oddshape."""
    if all(v & (v - 1) == 0 for v in ext):
        return "powerof2"
    if all(_factors_only(v, (2, 3, 5, 7)) for v in ext):
        return "radix357"
    return "oddshape"


def powerof2_extents(rank: int, min_exp: int, max_exp: int) -> Iterator[tuple[int, ...]]:
    for e in range(min_exp, max_exp + 1):
        yield (2 ** e,) * rank


def radix357_extents(rank: int, count: int = 8, start: int = 3) -> Iterator[tuple[int, ...]]:
    """Sizes of the form 2^a * 3^b * 5^c * 7^d that are not powers of two.

    Scans upward one at a time: powers of 3 alone make the sequence
    infinite, so this always terminates.  (The previous ``v // 8`` skip for
    v >= 32 could step over every remaining smooth number and loop forever,
    e.g. ``start=96``.)
    """
    emitted, v = 0, start
    while emitted < count:
        if _factors_only(v, (2, 3, 5, 7)) and (v & (v - 1)):
            yield (v,) * rank
            emitted += 1
        v += 1


def oddshape_extents(rank: int, count: int = 6) -> Iterator[tuple[int, ...]]:
    """Powers of 19 and friends (the paper's power-of-19 oddshape runs)."""
    base = [19, 19 * 19, 19 ** 3, 11 ** 3, 13 ** 3, 17 ** 3, 23 ** 3, 19 ** 4]
    for v in base[:count]:
        yield (v,) * rank


#: Generator-backed sweep classes a SuiteSpec can name instead of listing
#: extents explicitly — the paper's three extent classes (Fig. 7).
SWEEP_CLASSES = ("powerof2", "radix357", "oddshape")

_SWEEP_PARAMS = {
    "powerof2": {"min_exp", "max_exp"},
    "radix357": {"count", "start"},
    "oddshape": {"count"},
}


def sweep_extents(extent_class: str, rank: int, **params) -> list[tuple[int, ...]]:
    """Expand a named sweep class into concrete extents.

    ``powerof2`` requires ``min_exp``/``max_exp``; ``radix357`` accepts
    ``count``/``start``; ``oddshape`` accepts ``count``.  Unknown classes and
    unknown/missing parameters raise ``ValueError`` so a bad spec file fails
    before any benchmark runs.
    """
    if extent_class not in SWEEP_CLASSES:
        raise ValueError(f"unknown sweep class {extent_class!r}; "
                         f"known: {', '.join(SWEEP_CLASSES)}")
    if rank < 1 or rank > 3:
        raise ValueError(f"sweep rank must be 1..3, got {rank}")
    extra = set(params) - _SWEEP_PARAMS[extent_class]
    if extra:
        raise ValueError(f"sweep class {extent_class!r} does not accept "
                         f"{sorted(extra)}; allowed: "
                         f"{sorted(_SWEEP_PARAMS[extent_class])}")
    if extent_class == "powerof2":
        missing = {"min_exp", "max_exp"} - set(params)
        if missing:
            raise ValueError(f"powerof2 sweep requires {sorted(missing)}")
        return list(powerof2_extents(rank, params["min_exp"], params["max_exp"]))
    if extent_class == "radix357":
        return list(radix357_extents(rank, **params))
    return list(oddshape_extents(rank, **params))
