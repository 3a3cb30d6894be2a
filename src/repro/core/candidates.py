"""The planner's candidate space: backends, feasibility, and enumeration.

Split out of ``plan.py`` (which re-exports everything here).  This module
holds the *structural* half of planning — what a backend can run, which
(backend, knob) combinations exist for a problem — while the *quantitative*
half (how many HBM passes each choice costs) lives in
:mod:`repro.core.costmodel`.  The two layers meet only where enumeration
prunes by modeled cost: those call sites import the **active** cost model
lazily, so a fitted per-device coefficient table installed via
``costmodel.set_active_model`` steers candidate pruning and ranking without
any caller changing.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Sequence

from .client import Problem
from .extents import (_factors_only, next_pow2 as _next_pow2, next_smooth)


@dataclass(frozen=True)
class Candidate:
    """One point in the planner's search space.

    A candidate is either *homogeneous* (one backend applied per axis, or a
    whole-transform backend from :data:`FUSED_ND`) or — when ``axes`` is
    non-empty — a **per-axis assignment**: ``axes[i]`` transforms
    ``extents[i]`` (outermost first), each with its own backend and knobs.
    Per-axis candidates carry the placeholder backend ``'nd'``.

    Distributed candidates (:data:`DIST_BACKENDS`) additionally carry the
    **mesh shape** they decompose over — ``('slab', mesh=(4,))`` renders as
    ``slab[4]``, ``('pencil', mesh=(2, 4))`` as ``pencil[2x4]`` — because a
    selection tuned for one device count is meaningless for another, in
    plan-cache keys and in wisdom alike.
    """

    backend: str          # 'xla' | 'stockham' | ... | 'slab' | 'nd'
    options: tuple[tuple[str, Any], ...] = ()
    axes: tuple["Candidate", ...] = ()   # per-axis assignment (ND-native)
    mesh: tuple[int, ...] = ()           # device-mesh shape (distributed)

    def opts(self) -> dict[str, Any]:
        return dict(self.options)

    def per_axis(self, rank: int) -> tuple["Candidate", ...]:
        """The axis-by-axis assignment this candidate denotes: its explicit
        ``axes``, or the same (backend, knobs) replicated across ``rank``."""
        if self.axes:
            if len(self.axes) != rank:
                raise ValueError(
                    f"candidate assigns {len(self.axes)} axes to a rank-"
                    f"{rank} problem: {self.key()}")
            return self.axes
        return (Candidate(self.backend, self.options),) * rank

    def key(self) -> str:
        if self.axes:
            return "nd[" + ";".join(a.key() for a in self.axes) + "]"
        base = self.backend
        if self.mesh:
            base += "[" + "x".join(str(s) for s in self.mesh) + "]"
        o = ",".join(f"{k}={v}" for k, v in self.options)
        return f"{base}({o})" if o else base


def _pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def _smooth(n: int) -> bool:
    return n >= 1 and _factors_only(n, (2, 3, 5, 7, 11, 13))


def _smooth7(n: int) -> bool:
    """2^a*3^b*5^c*7^d — the extents the mixed-radix Stockham kernel
    factors (paper's powerof2 + radix357 classes; shares the extent
    classifier's ``_factors_only``)."""
    return n >= 1 and _factors_only(n, (2, 3, 5, 7))


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

#: Whole-transform backends: one engine call covers every axis, so the
#: separable path's swapaxes traffic never happens.
FUSED_ND = ("xla", "fft2_pallas")

#: Every backend the planner knows, in enumeration (preference-tie) order.
BACKENDS = ("xla", "stockham", "fourstep", "dft", "fourstep_pallas",
            "stockham_pallas", "sixstep", "fft2_pallas", "chirpz_pallas",
            "bluestein")

#: Withdrawn on the TPU.  The mixed-radix Stockham stage chain
#: (``stockham_pallas.apply_stages``) splits the lane axis with
#: ``x.reshape(*lead, r, m, s)`` and slices twiddles at unaligned static
#: offsets, which Mosaic refuses ("infer-vector-layout: unsupported shape
#: cast"); sixstep, fft2_pallas and chirpz_pallas run that chain too.
#: Until a Mosaic-friendly Stockham exists the planner never offers them
#: there, so no fallback chain has to hide a failed compile.
TPU_WITHDRAWN = frozenset({"stockham_pallas", "sixstep", "fft2_pallas",
                           "chirpz_pallas"})


def platform_allows(backend: str, precision: str = "float") -> bool:
    """Can ``backend`` run on this platform at ``precision``?  Everything
    runs off the TPU (Pallas kernels interpreted).  On the TPU the
    :data:`TPU_WITHDRAWN` kernels never run, and nothing runs in double:
    XLA's TPU FFT rejects c128 operands, the TPU compiler aborts on f64
    matmuls, and Mosaic lowers no f64 planes."""
    from .device import on_tpu

    if not on_tpu():
        return True
    return precision != "double" and backend not in TPU_WITHDRAWN


#: Mesh-sharded decompositions (fft/distributed.py) — enumerated only when
#: an active mesh is installed (launch.mesh.set_active_mesh), and kept out
#: of :data:`BACKENDS` so single-device planning and the conformance
#: support matrix are byte-identical without one.
DIST_BACKENDS = ("dist1d", "slab", "pencil")

#: all_to_alls per decomposition in the default TRANSPOSED-output layout.
DIST_A2A_COUNT = {"dist1d": 2, "slab": 1, "pencil": 2}
#: extra all_to_alls for natural-order output.
DIST_NATURAL_EXTRA = {"dist1d": 1, "slab": 1, "pencil": 2}


def axis_feasible(backend: str, n: int, precision: str = "float") -> bool:
    """Can ``backend`` transform one batched axis of extent ``n``?  This is
    the engine-level contract: the length the cfft actually receives — n//2
    for the packed r2c innermost axis of an EVEN real extent, the full
    length for an odd one, see ``axis_engine_n``.  The chirp backends are
    the any-length catch-all, so odd-length real kinds explicitly route to
    the full-complex chirp path rather than a meaningless packed half.
    Backends the platform withdraws (:func:`platform_allows`) are never
    feasible."""
    if not platform_allows(backend, precision):
        return False
    if backend in ("xla", "bluestein"):
        return True
    if backend == "stockham":
        return _pow2(n)
    if backend == "fourstep":
        return _smooth(n)
    if backend == "dft":
        return n <= DFT_MAX_N
    if backend == "fourstep_pallas":
        return _kernel_factorable(n)
    if backend == "stockham_pallas":
        return _smooth7(n) and n <= STOCKHAM_PALLAS_MAX_N
    if backend == "chirpz_pallas":
        # any length whose padded pow2 transform the fused engines cover
        return 1 <= n <= CHIRPZ_PALLAS_MAX_N
    if backend == "sixstep":
        # the engine falls back to the fused Stockham kernel below
        # SIXSTEP_MIN_N (packed-real halves can land there)
        return _pow2(n) and n <= SIXSTEP_MAX_N and n >= 2
    return False


def axis_engine_n(problem: Problem, axis: int) -> int:
    """Extent the 1-D engine actually transforms along ``axis``.

    Real kinds take the packed half-length path on the innermost axis (the
    cfft runs at n//2 for even n; odd lengths pay the full complex
    transform), so feasibility and the cost model must look at that length,
    not the nominal extent."""
    n = problem.extents[axis]
    if problem.complex_input or axis < problem.rank - 1:
        return n
    return n // 2 if n % 2 == 0 and n > 1 else n


def fft2_feasible(problem: Problem) -> bool:
    """The fused rank-2 kernel holds the whole n1 x n2 tile in VMEM."""
    exts = problem.extents
    return (len(exts) == 2 and all(_pow2(v) for v in exts)
            and exts[0] * exts[1] <= FFT2_PALLAS_MAX_ELEMS
            and (problem.complex_input or exts[-1] % 2 == 0))


def backend_supports(backend: str, problem: Problem) -> bool:
    """Single source of truth for the support matrix: candidates(), the
    conformance matrix, and the README table all consult this."""
    if not platform_allows(backend, problem.precision):
        return False
    if backend == "fft2_pallas":
        return fft2_feasible(problem)
    if backend == "xla":
        return True
    if backend == "sixstep":
        # offered only where the six-step composition is the real algorithm
        if not all(_pow2(v) and SIXSTEP_MIN_N <= v <= SIXSTEP_MAX_N
                   for v in problem.extents):
            return False
    return all(axis_feasible(backend, axis_engine_n(problem, i),
                             problem.precision)
               for i in range(problem.rank))


# ---------------------------------------------------------------------------
# Distributed candidates: slab / pencil / dist1d over the active mesh
# ---------------------------------------------------------------------------
def _mesh_devices(mesh) -> int:
    """Device count of a mesh (or mesh-shaped stand-in with ``.size``)."""
    return int(mesh.size)


def dist_supports(backend: str, problem: Problem,
                  mesh_shape: Sequence[int]) -> bool:
    """Can ``backend`` decompose ``problem`` over a mesh of ``mesh_shape``?

    Distribution is complex-kinds-only: the packed r2c half-spectrum extents
    (n//2, n//2+1) break the tiled all_to_all divisibility that every
    rotation depends on.  ``dist1d`` additionally needs batch == 1 — its
    matrix view consumes the whole axis.
    """
    if not problem.complex_input \
            or not platform_allows(backend, problem.precision):
        return False
    from repro.fft import distributed as dist

    shape = tuple(int(s) for s in mesh_shape)
    p = 1
    for s in shape:
        p *= s
    if p < 2:
        return False   # one device: decomposition is pure overhead
    if backend == "dist1d":
        return (problem.rank == 1 and problem.batch == 1
                and dist.can_shard_1d(problem.extents[0], p))
    if backend == "slab":
        return (len(shape) == 1 and problem.rank in (2, 3)
                and dist.slab_divisible(problem.extents, p))
    if backend == "pencil":
        return (len(shape) == 2 and problem.rank == 3
                and dist.pencil_divisible(problem.extents, *shape))
    return False


def _pencil_mesh_shapes(p: int, patient: bool = False) -> list[tuple[int, int]]:
    """(Pr, Pc) factorizations of ``p``: the most balanced one by default,
    widened to (at most four) alternates under PATIENT."""
    shapes = [(pr, p // pr) for pr in range(2, int(p ** 0.5) + 1)
              if p % pr == 0]
    shapes.sort(key=lambda s: s[1] - s[0])
    if not patient:
        return shapes[:1]
    out = list(shapes)
    out += [(pc, pr) for pr, pc in shapes if pr != pc]
    return out[:4]


def dist_local_lengths(problem: Problem, cand: Candidate
                       ) -> list[tuple[int, float]]:
    """The local sub-transform lengths a distributed candidate runs per
    shard, each with the swapaxes passes its position costs (+2 when the
    transform axis is not innermost in the local block, like the separable
    single-device path; 0 for the innermost axis)."""
    p = 1
    for s in cand.mesh:
        p *= s
    if cand.backend == "dist1d":
        from repro.fft.distributed import _choose_1d_factors

        n1, n2 = _choose_1d_factors(problem.extents[0], p)
        return [(n1, 2.0), (n2, 0.0)]
    # slab / pencil transform every global axis at its full extent locally
    return [(n, 0.0 if i == problem.rank - 1 else 2.0)
            for i, n in enumerate(problem.extents)]


def _dist_candidates(problem: Problem, mesh, patient: bool
                     ) -> list[Candidate]:
    """Sharded decompositions feasible for ``problem`` over ``mesh``.

    PATIENT widens with the decomposition x local-engine cross: alternate
    pencil mesh factorizations, and each feasible local engine forced via
    the ``local`` knob (the distributed analogue of the kernel tile
    sweeps)."""
    from .costmodel import dist_local_engine, hbm_passes

    p = _mesh_devices(mesh)
    if p < 2:
        return []
    out: list[Candidate] = []
    if dist_supports("dist1d", problem, (p,)):
        out.append(Candidate("dist1d", mesh=(p,)))
    if dist_supports("slab", problem, (p,)):
        out.append(Candidate("slab", mesh=(p,)))
    for shape in _pencil_mesh_shapes(p, patient):
        if dist_supports("pencil", problem, shape):
            out.append(Candidate("pencil", mesh=shape))
    if patient:
        extra = []
        for c in out:
            lengths = [n for n, _ in dist_local_lengths(problem, c)]
            default = {dist_local_engine(n, problem.precision)
                       for n in lengths}
            locals_ = [b for b in BACKENDS
                       if b not in FUSED_ND and b not in default
                       and all(axis_feasible(b, n, problem.precision)
                               for n in lengths)
                       and all(hbm_passes(b, n) != float("inf")
                               for n in lengths)]
            locals_.sort(key=lambda b: sum(hbm_passes(b, n) for n in lengths))
            extra += [Candidate(c.backend, (("local", b),), mesh=c.mesh)
                      for b in locals_[:2]]
        out += extra
    return out


def candidates(problem: Problem, patient: bool = False,
               mesh=None) -> list[Candidate]:
    """Enumerate feasible (backend, knob) combinations for a problem.

    The space is ND-native: besides homogeneous candidates (one backend for
    every axis) it holds the whole-transform backends (``xla``, and the
    fused rank-2 ``fft2_pallas`` kernel) and **per-axis assignments**
    (``Candidate.axes``) mixing backends across axes, pruned by the
    bytes-moved model.  ``patient=True`` widens the space with the fused
    kernels' tunable knobs — batch tiles, the (mixed-)radix schedule, the
    six-step n1*n2 split, the fft2 radix, the chirp-Z padded-engine choice
    — the FFTW_PATIENT analogue of searching algorithm *and* implementation
    parameters.

    ``mesh`` gates the distributed decompositions: ``None`` consults the
    active mesh (``launch.mesh.get_active_mesh``), which is itself None
    unless a launcher installed one — so single-process planning never
    offers a multi-device plan.
    """
    exts = problem.extents
    # every backend — the chirp catch-alls included — goes through
    # backend_supports, which evaluates feasibility at the ENGINE length:
    # odd-length real kinds route to the full-complex chirp path (engine
    # length n, not the even-only packed n//2) and caps apply there
    out = [Candidate(b) for b in BACKENDS if backend_supports(b, problem)]
    if not out:
        from .device import platform

        raise ValueError(f"no backend runs {problem.signature()} on the "
                         f"{platform()} platform")
    if problem.rank >= 2:
        out += _mixed_candidates(problem, limit=12 if patient else 6)
    if mesh is None:
        from repro.launch.mesh import get_active_mesh

        mesh = get_active_mesh()
    if mesh is not None:
        out += _dist_candidates(problem, mesh, patient)
    if patient:
        extra = []
        for c in out:
            if c.options or c.axes:
                continue
            if c.backend == "fourstep_pallas":
                for tb in (4, 8, 16):
                    extra.append(Candidate("fourstep_pallas", (("tile_b", tb),)))
            elif c.backend == "stockham_pallas":
                for tb in (4, 16):
                    for radix in (4, 8):
                        extra.append(Candidate(
                            "stockham_pallas",
                            (("radix", radix), ("tile_b", tb))))
            elif c.backend == "sixstep":
                for n1 in _sixstep_splits(exts[-1]):
                    extra.append(Candidate("sixstep", (("split_n1", n1),)))
                extra.append(Candidate("sixstep", (("tile_b", 16),)))
            elif c.backend == "chirpz_pallas":
                # a forced engine applies to EVERY axis the separable path
                # transforms, so gate each knob on every axis's engine
                # length (_sixstep_splits rule: only emit knobs the engine
                # actually honors, never ones that raise at build time)
                eng_ns = [axis_engine_n(problem, i)
                          for i in range(problem.rank)]
                engines = []
                if all(next_smooth(2 * v - 1) <= STOCKHAM_PALLAS_MAX_N
                       for v in eng_ns):
                    engines.append("stockham_pallas")  # smooth-m padding
                if all(SIXSTEP_MIN_N <= _next_pow2(2 * v - 1)
                       <= SIXSTEP_MAX_N for v in eng_ns):
                    engines.append("sixstep")
                for eng in engines:
                    extra.append(Candidate("chirpz_pallas",
                                           (("engine", eng),)))
                extra.append(Candidate("chirpz_pallas", (("tile_b", 16),)))
            elif c.backend == "fft2_pallas":
                for tb in (2, 8):
                    for radix in (4, 8):
                        extra.append(Candidate(
                            "fft2_pallas",
                            (("radix", radix), ("tile_b", tb))))
        out += extra
    return out


def _mixed_candidates(problem: Problem, limit: int) -> list[Candidate]:
    """Per-axis backend assignments, pruned by the bytes-moved model.

    For each axis, rank the separable backends by modeled engine passes at
    that axis's (packed) extent and keep the best two; the cross product —
    minus homogeneous assignments, which are already enumerated — is then
    re-ranked by the full ND model and truncated to ``limit``.  This is how
    the planner expresses e.g. 'dft on the tiny outer axis, fused Stockham
    on the long inner one' without sweeping every combination."""
    import itertools

    from .costmodel import estimate_bytes_moved, hbm_passes

    per_axis: list[list[str]] = []
    for i in range(problem.rank):
        n_eng = axis_engine_n(problem, i)
        feas = [b for b in BACKENDS
                if b not in FUSED_ND
                and axis_feasible(b, n_eng, problem.precision)]
        feas.sort(key=lambda b: hbm_passes(b, n_eng))
        per_axis.append(feas[:2])
    scored = []
    for combo in itertools.product(*per_axis):
        if len(set(combo)) == 1:
            continue  # homogeneous: already in the candidate list
        cand = Candidate("nd", axes=tuple(Candidate(b) for b in combo))
        cost = estimate_bytes_moved(problem, cand)
        if cost != float("inf"):
            scored.append((cost, cand))
    scored.sort(key=lambda t: t[0])
    return [cand for _, cand in scored[:limit]]


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


def _kernel_factorable(n: int) -> bool:
    """n = n1*n2 with both <= 128 (single fused fft4step kernel pass)."""
    if n > FOURSTEP_PALLAS_MAX_N:
        return False
    for n1 in range(min(128, n), 0, -1):
        if n % n1 == 0 and n // n1 <= 128:
            return True
    return False
