"""The planner's candidate space: feasibility on this platform, and
enumeration.

This module holds the *structural* half of planning — what a backend can
run here, which (backend, knob) combinations exist for a problem — while
the *quantitative* half (how many HBM passes each choice costs) lives in
:mod:`repro.core.costmodel`.  What each backend is — its lengths, knobs
and cost formula — is its record in :mod:`repro.core.backends`.  The two
layers meet only where enumeration prunes by modeled cost: those call sites
import the **active** cost model lazily, so a fitted per-device coefficient
table installed via ``costmodel.set_active_model`` steers candidate pruning
and ranking without any caller changing.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Sequence

from .backends import BACKENDS, TABLE, axis_engine_n
from .client import Problem


@dataclass(frozen=True)
class Candidate:
    """One point in the planner's search space.

    A candidate is either *homogeneous* (one backend applied per axis, or a
    whole-transform backend) or — when ``axes`` is non-empty — a **per-axis
    assignment**: ``axes[i]`` transforms
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


def platform_allows(backend: str, precision: str = "float") -> bool:
    """Can ``backend`` run on this platform at ``precision``?  Everything
    runs off the TPU (Pallas kernels interpreted).  On the TPU the kernels
    withdrawn there (``Backend.tpu_withdrawn``) never run, and nothing runs
    in double: XLA's TPU FFT rejects c128 operands, the TPU compiler aborts
    on f64 matmuls, and Mosaic lowers no f64 planes."""
    from .device import on_tpu

    if not on_tpu():
        return True
    return precision != "double" and not (
        backend in TABLE and TABLE[backend].tpu_withdrawn)


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
    length for an odd one, see ``axis_engine_n``.  Backends the platform
    withdraws (:func:`platform_allows`) are never feasible."""
    rec = TABLE.get(backend)
    return (rec is not None and platform_allows(backend, precision)
            and rec.fits(n))


def backend_supports(backend: str, problem: Problem) -> bool:
    """Single source of truth for the support matrix: candidates(), the
    conformance matrix, and the README table all consult this.  A
    whole-transform backend answers for the whole problem; a separable one
    must also fit every axis's engine length."""
    rec = TABLE.get(backend)
    if rec is None or not platform_allows(backend, problem.precision):
        return False
    if rec.rule is not None and not rec.rule(problem):
        return False
    return not rec.separable or all(
        rec.fits(axis_engine_n(problem, i)) for i in range(problem.rank))


# ---------------------------------------------------------------------------
# Distributed candidates: slab / pencil / dist1d over the active mesh
# ---------------------------------------------------------------------------
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
    p = math.prod(shape)
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
    p = math.prod(cand.mesh)
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

    p = int(mesh.size)   # a mesh, or a stand-in with .size
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
                       if TABLE[b].separable and b not in default
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
    every axis) it holds the whole-transform backends and **per-axis
    assignments** (``Candidate.axes``) mixing backends across axes, pruned
    by the bytes-moved model.  ``patient=True`` widens the space with the fused
    kernels' tunable knobs — batch tiles, the (mixed-)radix schedule, the
    six-step n1*n2 split, the fft2 radix, the chirp-Z padded-engine choice
    — the FFTW_PATIENT analogue of searching algorithm *and* implementation
    parameters.

    ``mesh`` gates the distributed decompositions: ``None`` consults the
    active mesh (``launch.mesh.get_active_mesh``), which is itself None
    unless a launcher installed one — so single-process planning never
    offers a multi-device plan.
    """
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
        # each plain candidate's knobs, as its record gives them
        out += [Candidate(c.backend, opts) for c in out
                if not (c.options or c.axes) and c.backend in TABLE
                for opts in TABLE[c.backend].knobs(problem)]
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
                if TABLE[b].separable
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
