"""The single-device FFT backends: one record per backend.

A :class:`Backend` states everything the planner and the clients know of a
backend: the engine lengths it takes (``fits``), any rule over the whole
problem, its modeled HBM passes and the cost coefficients they read, its
engine, its PATIENT knobs, its family (the ``fft_<family>_`` of every
executable name), whether one engine call covers every axis, and whether
the TPU withdraws it.  The planner (:mod:`repro.core.candidates`,
:mod:`repro.core.costmodel`) and the clients read this table; the length
caps are compared here only, and defined here but for the jnp four-step's
factorisation, which :mod:`repro.core.extents` states for its engine and
this table both.

:data:`TABLE` is in enumeration order: ESTIMATE's ties and the conformance
matrix follow it.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from functools import partial
from typing import TYPE_CHECKING, Any, Callable

from .extents import (FOURSTEP_MAX_RADIX, _factors_only, fourstep_levels,
                      next_pow2 as _next_pow2, next_smooth)

if TYPE_CHECKING:
    from .client import Problem

INF = float("inf")

#: Feasibility caps for the fused kernel paths (see the kernel modules).
FOURSTEP_PALLAS_MAX_N = 128 * 128        # one fused four-step kernel pass
#: Largest direct (dense-matrix) DFT.  At n <= 512 every split n = n1*n2
#: leaves a factor of 22 or less, so the four-step kernel's per-signal
#: matmuls fill a sliver of the 128x128 MXU, while the dense DFT's one
#: (TILE_B, n) @ (n, n) matmul is up to four lane tiles wide.  VMEM at
#: n = 512, double-buffered: W planes 4 MiB, x/y planes 4 MiB at TILE_B
#: 256, plus HIGHEST's bf16 operand splits: 16.2 MiB, over v5e's 16 MiB
#: scoped VMEM, so above 384 the kernel takes TILE_B 128
#: (``dft_matmul.default_tile_b``).
DFT_MAX_N = 512
STOCKHAM_PALLAS_MAX_N = 1 << 20          # ops.MAX_N: single-kernel hard cap
STOCKHAM_PALLAS_VMEM_N = 1 << 15         # fits a useful batch tile in VMEM
SIXSTEP_MIN_N, SIXSTEP_MAX_N = 4, 1 << 24
FFT2_PALLAS_MAX_ELEMS = 1 << 18          # fft2 ops.MAX_ELEMS: hard cap
FFT2_PALLAS_VMEM_ELEMS = 1 << 16         # n1*n2 tile fits the VMEM budget
#: Largest chirp-Z length whose padded transform (next_pow2(2n-1)) still
#: fits the six-step composition's SIXSTEP_MAX_N = 2^24.
CHIRPZ_PALLAS_MAX_N = 1 << 23


@dataclass(frozen=True)
class Infeasible:
    """Typed infeasibility verdict from the cost model.

    Falsy, and ``float()`` of it is ``inf`` — so numeric callers keep their
    sentinel while reporting callers (bench_compare's roofline) can tell
    *why* a row had no modeled traffic instead of silently papering over it.
    """

    reason: str = ""

    def __bool__(self) -> bool:
        return False

    def __float__(self) -> float:
        return float("inf")


# ---------------------------------------------------------------------------
# Length classes
# ---------------------------------------------------------------------------
def _pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def _smooth7(n: int) -> bool:
    """2^a*3^b*5^c*7^d — the extents the mixed-radix Stockham kernel
    factors (paper's powerof2 + radix357 classes; shares the extent
    classifier's ``_factors_only``)."""
    return n >= 1 and _factors_only(n, (2, 3, 5, 7))


def _kernel_factorable(n: int) -> bool:
    """n = n1*n2 with both <= 128 (single fused fft4step kernel pass)."""
    if n > FOURSTEP_PALLAS_MAX_N:
        return False
    for n1 in range(min(128, n), 0, -1):
        if n % n1 == 0 and n // n1 <= 128:
            return True
    return False


def axis_engine_n(problem: "Problem", axis: int) -> int:
    """Extent the 1-D engine actually transforms along ``axis``.

    Real kinds take the packed half-length path on the innermost axis (the
    cfft runs at n//2 for even n; odd lengths pay the full complex
    transform), so feasibility and the cost model must look at that length,
    not the nominal extent.  The chirp backends are the any-length
    catch-all, so odd-length real kinds route to the full-complex chirp path
    rather than a meaningless packed half."""
    n = problem.extents[axis]
    if problem.complex_input or axis < problem.rank - 1:
        return n
    return n // 2 if n % 2 == 0 and n > 1 else n


def fft2_feasible(problem: "Problem") -> bool:
    """The fused rank-2 kernel holds the whole n1 x n2 tile in VMEM."""
    exts = problem.extents
    return (len(exts) == 2 and all(_pow2(v) for v in exts)
            and exts[0] * exts[1] <= FFT2_PALLAS_MAX_ELEMS
            and (problem.complex_input or exts[-1] % 2 == 0))


def _sixstep_splits(n: int) -> list[int]:
    """Alternative n = n1*n2 residual splits for the PATIENT sweep: the
    balanced split and a residual-heavy one, besides the default.  Both
    sixstep.choose_split constraints apply — n1 <= 2^10 (the residual
    VMEM cap) and n2 <= 2^14 — so every emitted knob is one the engine
    actually honors rather than silently replacing with the default."""
    if not _pow2(n) or n < SIXSTEP_MIN_N:
        return []
    k = n.bit_length() - 1
    default_k1 = k - min(14, k - 1)
    opts = {max(1, k // 2), max(1, min(10, k - 1))} - {default_k1}
    return sorted(1 << k1 for k1 in opts
                  if 1 <= k1 <= 10 and k - k1 <= 14)


# ---------------------------------------------------------------------------
# The record
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Backend:
    """One backend.  ``passes(coeffs, n)`` is asked only at lengths that
    ``fits``; ``coeffs`` names the ``CostCoefficients`` fields it reads,
    which a fitted per-backend scale multiplies together."""

    name: str
    family: str                   # 'xla' | 'pallas' | 'jnp'
    fits: Callable[[int], bool] = lambda n: False
    passes: Callable[[Any, int], float] = lambda c, n: INF
    coeffs: tuple[str, ...] = ()
    #: ``engine(opts, interpret)`` -> ``cfft(x, inverse=False)``.
    engine: Callable[[dict, bool], Callable] | None = None
    #: PATIENT knobs: the option tuples of its extra candidates.
    knobs: Callable[["Problem"], list] = lambda problem: []
    #: A rule over the whole problem, on top of ``fits`` at every axis.
    rule: Callable[["Problem"], bool] | None = None
    #: A whole-transform backend's passes over the whole problem (or an
    #: :class:`Infeasible`); None for a separable one.
    whole_passes: Callable[[Any, "Problem"], "float | Infeasible"] | None = None
    #: Withdrawn on the TPU.  The mixed-radix Stockham stage chain
    #: (``stockham_pallas.apply_stages``) splits the lane axis with
    #: ``x.reshape(*lead, r, m, s)`` and slices twiddles at unaligned static
    #: offsets, which Mosaic refuses ("infer-vector-layout: unsupported
    #: shape cast"); sixstep, fft2_pallas and chirpz_pallas run that chain
    #: too.  Until a Mosaic-friendly Stockham exists the planner never
    #: offers them there, so no fallback chain has to hide a failed compile.
    tpu_withdrawn: bool = False

    @property
    def separable(self) -> bool:
        """Applied axis by axis (``nd.fftn``), not once over the whole."""
        return self.whole_passes is None


# --- xla: XLA's native FFT over every axis at once ------------------------
def _xla_passes(c, n: int) -> float:
    if _smooth7(n):
        return c.xla_smooth_passes  # vendor path: heavily fused
    # non-smooth lengths send the vendor library down its own chirp
    # fallback: ~3 fused transforms at the padded pow2 length
    return c.xla_chirp_passes * (_next_pow2(2 * n - 1) / n)


def _xla_whole(c, problem: "Problem") -> float:
    # a non-smooth axis drags the whole transform into its chirp fallback
    return max(_xla_passes(c, axis_engine_n(problem, i))
               for i in range(problem.rank))


# --- stockham / fourstep / bluestein: staged jnp engines -------------------
def _jnp_engine(module: str) -> Callable[[dict, bool], Callable]:
    """``repro.fft.<module>.fft``, which has no knobs."""
    return lambda opts, interpret: importlib.import_module(
        f"repro.fft.{module}").fft


def _stockham_passes(c, n: int) -> float:
    # one pass per radix-2 stage
    return c.stockham_stage_passes * float(max(1, n.bit_length() - 1))


def _fourstep_fits(n: int) -> bool:
    # every prime factor <= the largest radix (composites never divide
    # once their primes are out)
    return n >= 1 and _factors_only(n, range(2, FOURSTEP_MAX_RADIX + 1))


def _fourstep_passes(c, n: int) -> float:
    return c.fourstep_level_passes * fourstep_levels(n)


def _bluestein_passes(c, n: int) -> float:
    m = 1
    while m < 2 * n - 1:
        m *= 2
    # 3 staged Stockham transforms of padded length m, + chirp setup
    return (c.bluestein_stage_passes * max(1, m.bit_length() - 1)
            + c.bluestein_setup_passes) * (m / n)


# --- Pallas kernels ---------------------------------------------------------
def _dft_engine(opts: dict, interpret: bool) -> Callable:
    from repro.kernels.dft_matmul import ops
    return partial(ops.dft, interpret=interpret)


def _fourstep_pallas_engine(opts: dict, interpret: bool) -> Callable:
    from repro.kernels.fft4step import ops
    return partial(ops.fft, tile_b=opts.get("tile_b", 8), interpret=interpret)


def _stockham_pallas_engine(opts: dict, interpret: bool) -> Callable:
    from repro.kernels.stockham_pallas import ops
    return partial(ops.fft, tile_b=opts.get("tile_b"),
                   radix=opts.get("radix", 8), interpret=interpret)


def _sixstep_engine(opts: dict, interpret: bool) -> Callable:
    from repro.fft import sixstep
    return partial(sixstep.fft, n1=opts.get("split_n1"),
                   tile_b=opts.get("tile_b"), interpret=interpret)


def _sixstep_knobs(problem: "Problem") -> list:
    return ([(("split_n1", n1),) for n1 in _sixstep_splits(problem.extents[-1])]
            + [(("tile_b", 16),)])


def _sixstep_rule(problem: "Problem") -> bool:
    # offered only where the six-step composition is the real algorithm
    return all(_pow2(v) and SIXSTEP_MIN_N <= v <= SIXSTEP_MAX_N
               for v in problem.extents)


def _fft2_whole(c, problem: "Problem") -> "float | Infeasible":
    # one read + one write of the tile; the VMEM budget binds the tile the
    # kernel actually holds: real kinds run packed, so the inner extent
    # halves (even n)
    tile_elems = problem.extents[0] * axis_engine_n(problem, problem.rank - 1)
    if not (fft2_feasible(problem) and tile_elems <= FFT2_PALLAS_VMEM_ELEMS):
        return Infeasible(
            f"fft2_pallas tile of {tile_elems} elems exceeds "
            f"the VMEM budget for {problem.signature()}")
    return 1.0


def _fft2_engine(opts: dict, interpret: bool) -> Callable:
    """``cfft2(x, inverse=False)`` over the LAST TWO axes."""
    from repro.kernels.fft2_pallas import ops
    return partial(ops.fft2, tile_b=opts.get("tile_b"),
                   radix=opts.get("radix", 8), interpret=interpret)


def _chirpz_passes(c, n: int) -> float:
    # two fused padded transforms + chirp mul, filter mul, final chirp; the
    # filter spectrum is host-cached so no third transform runs.  The
    # mixed-radix kernel convolves at the smallest 7-SMOOTH m >= 2n-1
    # (often ~2x tighter than pow2); sixstep needs pow2.
    ms = next_smooth(2 * n - 1)
    if ms <= STOCKHAM_PALLAS_VMEM_N:
        return c.chirpz_smooth_passes * (ms / n)
    return c.chirpz_pow2_passes * (_next_pow2(2 * n - 1) / n)


def _chirpz_engine(opts: dict, interpret: bool) -> Callable:
    from repro.fft import bluestein
    return partial(bluestein.fft, engine=opts.get("engine", "auto"),
                   tile_b=opts.get("tile_b"), interpret=interpret)


def _chirpz_knobs(problem: "Problem") -> list:
    # a forced engine applies to EVERY axis the separable path transforms,
    # so gate each knob on every axis's engine length (only emit knobs the
    # engine actually honors, never ones that raise at build time)
    eng_ns = [axis_engine_n(problem, i) for i in range(problem.rank)]
    engines = []
    if all(next_smooth(2 * v - 1) <= STOCKHAM_PALLAS_MAX_N for v in eng_ns):
        engines.append("stockham_pallas")  # smooth-m padding
    if all(SIXSTEP_MIN_N <= _next_pow2(2 * v - 1) <= SIXSTEP_MAX_N
           for v in eng_ns):
        engines.append("sixstep")
    return ([(("engine", eng),) for eng in engines]
            + [(("tile_b", 16),)])


def _tile_radix_knobs(tiles: tuple[int, ...]) -> Callable[["Problem"], list]:
    return lambda problem: [(("radix", radix), ("tile_b", tb))
                            for tb in tiles for radix in (4, 8)]


#: Every backend the planner knows, in enumeration (preference-tie) order.
TABLE: dict[str, Backend] = {b.name: b for b in (
    Backend("xla", "xla", fits=lambda n: True, passes=_xla_passes,
            coeffs=("xla_smooth_passes", "xla_chirp_passes"),
            whole_passes=_xla_whole),
    Backend("stockham", "jnp", fits=_pow2, passes=_stockham_passes,
            coeffs=("stockham_stage_passes",), engine=_jnp_engine("stockham")),
    Backend("fourstep", "jnp", fits=_fourstep_fits, passes=_fourstep_passes,
            coeffs=("fourstep_level_passes",), engine=_jnp_engine("fourstep")),
    Backend("dft", "pallas", fits=lambda n: n <= DFT_MAX_N,
            passes=lambda c, n: c.dft_passes, coeffs=("dft_passes",),
            engine=_dft_engine),
    Backend("fourstep_pallas", "pallas", fits=_kernel_factorable,
            # a factor of 22 or less at n <= DFT_MAX_N: the narrow charge
            passes=lambda c, n: (c.fourstep_pallas_narrow_passes
                                 if n <= DFT_MAX_N
                                 else c.fourstep_pallas_passes),
            coeffs=("fourstep_pallas_passes",
                    "fourstep_pallas_narrow_passes"),
            engine=_fourstep_pallas_engine,
            knobs=lambda p: [(("tile_b", tb),) for tb in (4, 8, 16)]),
    Backend("stockham_pallas", "pallas",
            fits=lambda n: _smooth7(n) and n <= STOCKHAM_PALLAS_MAX_N,
            # it runs up to the hard cap, but beyond the VMEM budget it
            # holds no useful batch tile: never chosen on cost there
            passes=lambda c, n: (c.stockham_pallas_passes
                                 if n <= STOCKHAM_PALLAS_VMEM_N else INF),
            coeffs=("stockham_pallas_passes",),
            engine=_stockham_pallas_engine,
            knobs=_tile_radix_knobs((4, 16)), tpu_withdrawn=True),
    Backend("sixstep", "pallas",
            # the engine falls back to the fused Stockham kernel below
            # SIXSTEP_MIN_N (packed-real halves can land there)
            fits=lambda n: _pow2(n) and 2 <= n <= SIXSTEP_MAX_N,
            # 2 fused kernel passes + 3 transposes; below SIXSTEP_MIN_N it
            # is no six-step, so it is never chosen on cost there
            passes=lambda c, n: c.sixstep_passes if n >= SIXSTEP_MIN_N
            else INF,
            coeffs=("sixstep_passes",), engine=_sixstep_engine,
            knobs=_sixstep_knobs, rule=_sixstep_rule, tpu_withdrawn=True),
    Backend("fft2_pallas", "pallas", engine=_fft2_engine,
            knobs=_tile_radix_knobs((2, 8)), rule=fft2_feasible,
            whole_passes=_fft2_whole, tpu_withdrawn=True),
    Backend("chirpz_pallas", "pallas",
            # any length whose padded pow2 transform the fused engines cover
            fits=lambda n: 1 <= n <= CHIRPZ_PALLAS_MAX_N,
            passes=_chirpz_passes,
            coeffs=("chirpz_smooth_passes", "chirpz_pow2_passes"),
            engine=_chirpz_engine, knobs=_chirpz_knobs, tpu_withdrawn=True),
    Backend("bluestein", "jnp", fits=lambda n: True, passes=_bluestein_passes,
            coeffs=("bluestein_stage_passes", "bluestein_setup_passes"),
            engine=_jnp_engine("bluestein")),  # staged jnp chirp-Z
)}

#: The backends' names, in :data:`TABLE` order.
BACKENDS = tuple(TABLE)


def record(name: str) -> Backend:
    try:
        return TABLE[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}") from None


def engine(cand) -> Callable:
    """The candidate's engine: ``cfft(x, inverse=False)`` over the LAST axis
    (the LAST TWO for ``fft2_pallas``).  Pallas engines get ``interpret``
    from :func:`repro.core.device.interpret_mode` (the one platform
    decision), passed as a concrete value so jit caches never share a trace
    between interpreted and compiled kernels."""
    from .device import interpret_mode

    make = record(cand.backend).engine
    if make is None:
        raise ValueError(f"{cand.backend} has no engine of its own")
    return make(cand.opts(), interpret_mode())
