"""The planner's cost model as a first-class, *fittable* layer.

ESTIMATE ranks candidates by modeled HBM traffic (bytes moved).  The model's
constants — per-backend pass counts, the chirp padding overheads, the
interconnect link cost — live in a :class:`CostCoefficients` table,
versioned and loadable per **device kind**, so ``tools/fit_costmodel.py``
can regress them from measured BENCH_*.json + wisdom data and a Session can
install the fitted table for its device.

Layering:

* :data:`DEFAULT_COEFFICIENTS` reproduces the historical hand-written
  values **bit-for-bit** — with it installed (the default), every golden
  ESTIMATE pick and dist-cost crossover is byte-identical to the
  pre-refactor planner.
* Each backend's pass formula is its record's ``passes`` in
  :mod:`repro.core.backends`; this module sums them over a problem.
* A module-level *active* model (:func:`get_active_model` /
  :func:`set_active_model` / :func:`use_model`) is what the module-level
  functions ``hbm_passes`` / ``estimate_bytes_moved`` / ``estimate_choice``
  delegate to; ``plan.fallback_chain`` and the serve engine's chain
  memoization therefore consult fitted rankings the moment a fitted table
  is installed, with no caller changes.
* Infeasible assignments get a typed :class:`Infeasible` verdict from
  :meth:`CostModel.estimate` (``float()`` of it is still ``inf``, so the
  numeric ``estimate_bytes_moved`` contract is unchanged).
"""

from __future__ import annotations

import json
import math
import warnings
from contextlib import contextmanager
from dataclasses import dataclass, fields, replace
from typing import Optional

from .backends import BACKENDS, TABLE, Infeasible, axis_engine_n
from .candidates import (Candidate, DIST_A2A_COUNT, DIST_BACKENDS,
                         DIST_NATURAL_EXTRA, axis_feasible, candidates,
                         dist_local_lengths, dist_supports)
from .client import Problem

#: Schema stamped into coefficient-table files; loaders reject newer ones.
COSTMODEL_SCHEMA_VERSION = 1

#: Interconnect cost of one all-to-all'd byte relative to one HBM byte —
#: ICI/NVLink-class fabrics move bytes at a small single-digit multiple of
#: HBM cost; this single coefficient is what lets ESTIMATE rank "one
#: device, one HBM touch" against "P devices, two all-to-alls" honestly.
DIST_LINK_COST = 4.0
#: Fixed per-collective charge (latency, layout fix-ups) expressed in
#: equivalent HBM bytes — keeps tiny transforms from sharding: below ~1 MiB
#: the collective's constant cost dwarfs any compute win.
DIST_A2A_LATENCY_BYTES = float(1 << 20)


@dataclass(frozen=True)
class CostCoefficients:
    """Every fittable constant of the bytes-moved model, with the
    historical hand-written values as defaults.

    Pass counts are HBM round-trips of the live signal; the chirp/bluestein
    entries are multiplied by their padding ratio (m/n) at evaluation time,
    so fitting them rescales the *overhead*, not the structure.
    """

    # vendor path: multi-stage but heavily fused on smooth extents; a
    # non-smooth length takes the library's own chirp fallback
    xla_smooth_passes: float = 2.0
    xla_chirp_passes: float = 6.0
    # one staged jnp pass per radix-2 stage
    stockham_stage_passes: float = 1.0
    # per recursion level of the cache-blocked four-step
    fourstep_level_passes: float = 2.0
    # single-matmul DFT: one fused touch
    dft_passes: float = 1.0
    # fused kernels: read + write the signal exactly once
    fourstep_pallas_passes: float = 1.0
    # fourstep_pallas at n <= DFT_MAX_N, where one factor is 22 or less: on
    # a v5e its factor-19 kernel ran ~43x one HBM pass's time against ~8x
    # for the 64x64 kernel charged 1.0 above (361 vs 4096), about 5x
    fourstep_pallas_narrow_passes: float = 5.0
    stockham_pallas_passes: float = 1.0
    # 2 fused kernel passes + 3 transpose passes
    sixstep_passes: float = 5.0
    # chirp-Z: 2 padded engine passes + chirp/filter/final muls, charged at
    # the padded length (x m/n) — smooth-m kernel vs pow2 six-step engine
    chirpz_smooth_passes: float = 5.0
    chirpz_pow2_passes: float = 13.0
    # staged-Stockham Bluestein: 3 padded transforms + chirp setup
    bluestein_stage_passes: float = 3.0
    bluestein_setup_passes: float = 2.0
    # swapaxes in + out around every non-innermost separable engine call
    transpose_passes: float = 2.0
    # interconnect: per-byte link cost + per-collective latency floor
    dist_link_cost: float = DIST_LINK_COST
    dist_a2a_latency_bytes: float = DIST_A2A_LATENCY_BYTES
    # dist1d's extra per-shard twiddle multiply
    dist1d_twiddle_passes: float = 1.0
    # latency-floor heuristic: rank-1 problems at or below this inner
    # engine length go straight to the single-matmul dft kernel
    dft_pin_max_n: int = 128

    def to_dict(self) -> dict:
        return {f.name: getattr(self, f.name) for f in fields(self)}

    @classmethod
    def from_dict(cls, d: dict) -> "CostCoefficients":
        known = {f.name for f in fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            warnings.warn(f"ignoring unknown cost coefficients: {unknown}")
        kw = {k: v for k, v in d.items() if k in known}
        if "dft_pin_max_n" in kw:
            kw["dft_pin_max_n"] = int(kw["dft_pin_max_n"])
        return cls(**{k: (float(v) if k != "dft_pin_max_n" else v)
                      for k, v in kw.items()})


DEFAULT_COEFFICIENTS = CostCoefficients()

class CostModel:
    """Bytes-moved model over one :class:`CostCoefficients` table.

    ``device_kind`` labels which device the coefficients were fitted for
    (``"default"`` = the hand-written table); ``source`` records provenance
    for reports.
    """

    def __init__(self, coeffs: CostCoefficients = DEFAULT_COEFFICIENTS,
                 device_kind: str = "default",
                 source: str = "hand-written defaults"):
        self.coeffs = coeffs
        self.device_kind = device_kind
        self.source = source

    def __repr__(self) -> str:
        return f"CostModel({self.device_kind!r}, source={self.source!r})"

    def scaled(self, backend_scales: dict[str, float],
               device_kind: str = "", source: str = "") -> "CostModel":
        """A new model with each backend's coefficients (its record's
        ``coeffs``, scaled together so the structural ratios inside a
        backend are kept) multiplied by its fitted scale."""
        updates: dict[str, float] = {}
        for backend, scale in backend_scales.items():
            rec = TABLE.get(backend)
            for name in rec.coeffs if rec else ():
                updates[name] = getattr(self.coeffs, name) * float(scale)
        return CostModel(replace(self.coeffs, **updates),
                         device_kind or self.device_kind,
                         source or self.source)

    # --- per-axis engine passes -------------------------------------------
    def hbm_passes(self, backend: str, n: int) -> float:
        """Modeled HBM round-trips of the whole signal for one length-n
        transform (the quantity that dominates above the paper's ~1 MiB
        boundary).  ``inf`` marks an infeasible / VMEM-overflowing choice.

        The fused kernels are the reason this model exists: stockham_pallas
        and fourstep_pallas read and write the signal exactly once, the
        six-step composition a small constant (2 kernel passes + 3
        transposes), while the staged jnp Stockham pays one pass per
        radix-2 stage.  Each backend's formula is its record's ``passes``,
        asked only at lengths the record ``fits``.
        """
        rec = TABLE.get(backend)
        if rec is None or not rec.fits(n):
            return float("inf")
        return rec.passes(self.coeffs, n)

    # --- live elements per axis -------------------------------------------
    @staticmethod
    def axis_elems(problem: Problem, axis: int) -> int:
        """Complex elements the transform carries while working on ``axis``.

        Complex kinds move the whole signal on every axis.  Real kinds run
        the innermost axis packed at half the elements (even n) and every
        outer axis on the half-spectrum — n_last//2 + 1 bins along the last
        axis — which is the traffic halving the paper's Fig. 8a measures."""
        if problem.complex_input:
            return problem.n_elems
        n_last = problem.extents[-1]
        rows = problem.n_elems // n_last
        if axis == problem.rank - 1:
            return rows * (n_last // 2) if n_last % 2 == 0 else problem.n_elems
        return rows * (n_last // 2 + 1)

    # --- full-transform estimate ------------------------------------------
    def estimate(self, problem: Problem,
                 cand: Candidate) -> "float | Infeasible":
        """Modeled HBM bytes for the full nd transform under ``cand``, or a
        typed :class:`Infeasible` verdict.

        Whole-transform backends move the signal their record's
        ``whole_passes`` with **no** transpose traffic.  Separable
        assignments charge, per axis: the engine's :meth:`hbm_passes` at the
        extent the engine actually sees (packed half-length on a real
        innermost axis), *plus* the two swapaxes passes ``nd._apply_last``
        really performs for every non-innermost axis — zero for the
        innermost one.  Each pass reads and writes the live elements once
        (see :meth:`axis_elems` for the r2c half-spectrum sizes).

        Distributed candidates (``DIST_BACKENDS``) model the **per-device**
        cost — what bounds wall time when every device works in parallel:
        the local per-axis engine passes on the 1/P-sized shard, plus the
        interconnect term — each all_to_all moves the device's whole block
        once, charged at ``dist_link_cost`` HBM-equivalent bytes per byte
        plus the fixed ``dist_a2a_latency_bytes`` per collective.  That
        latency floor is why small transforms never shard and the
        single-/multi-device crossover sits where it does.
        """
        c = self.coeffs
        complex_itemsize = 16 if problem.precision == "double" else 8
        if cand.backend in DIST_BACKENDS:
            p = math.prod(cand.mesh)
            if not dist_supports(cand.backend, problem, cand.mesh):
                return Infeasible(
                    f"{cand.key()} cannot decompose "
                    f"{problem.signature()} over mesh {cand.mesh}")
            opts = cand.opts()
            forced = opts.get("local")
            passes = 0.0
            for n_g, swaps in dist_local_lengths(problem, cand):
                b = forced or self.dist_local_engine(n_g, problem.precision)
                hp = self.hbm_passes(b, n_g)
                if hp == float("inf") or not axis_feasible(
                        b, n_g, problem.precision):
                    return Infeasible(
                        f"local engine {b} infeasible at n={n_g}")
                passes += hp + swaps
            if cand.backend == "dist1d":
                passes += c.dist1d_twiddle_passes  # per-shard twiddle mul
            dev_bytes = (problem.n_elems / p) * complex_itemsize
            n_a2a = DIST_A2A_COUNT[cand.backend]
            if opts.get("natural"):
                n_a2a += DIST_NATURAL_EXTRA[cand.backend]
            return (passes * 2.0 * dev_bytes
                    + n_a2a * (dev_bytes * c.dist_link_cost
                               + c.dist_a2a_latency_bytes))
        rec = TABLE.get(cand.backend)
        if rec is not None and not rec.separable:
            passes = rec.whole_passes(c, problem)
            if isinstance(passes, Infeasible):
                return passes
            elems = self.axis_elems(problem, problem.rank - 1)
            return passes * 2.0 * elems * complex_itemsize
        total = 0.0
        for axis, ax_cand in enumerate(cand.per_axis(problem.rank)):
            n_eng = axis_engine_n(problem, axis)
            passes = self.hbm_passes(ax_cand.backend, n_eng)
            if passes == float("inf"):
                return Infeasible(
                    f"{ax_cand.backend} infeasible at engine length "
                    f"{n_eng} (axis {axis} of {problem.signature()})")
            if axis != problem.rank - 1:
                passes += c.transpose_passes  # swapaxes in + out
            total += (passes * 2.0 * self.axis_elems(problem, axis)
                      * complex_itemsize)
        return total

    def estimate_bytes_moved(self, problem: Problem,
                             cand: Candidate) -> float:
        """Numeric view of :meth:`estimate` — infeasible is ``inf``."""
        return float(self.estimate(problem, cand))

    # --- rankings ---------------------------------------------------------
    def dist_local_engine(self, n: int, precision: str = "float") -> str:
        """The separable backend a distributed plan runs locally at length
        ``n`` when no explicit ``local`` knob forces one: fewest modeled
        HBM passes, ties to the earlier (more conservative) BACKENDS
        entry."""
        best, best_p = "fourstep", float("inf")
        for b in BACKENDS:
            if TABLE[b].separable and axis_feasible(b, n, precision):
                passes = self.hbm_passes(b, n)
                if passes < best_p:
                    best, best_p = b, passes
        return best

    def estimate_choice(self, problem: Problem) -> Candidate:
        """The ESTIMATE heuristic: a static bytes-moved cost model.

        Mirrors fftw's 'probably sub-optimal but instant' behavior: tiny
        rank-1 problems go straight to the single-matmul dft kernel (launch
        overhead dominates traffic there); everything else takes the
        feasible candidate that moves the fewest modeled HBM bytes (ties
        keep the earlier, more conservative entry — the vendor path is
        enumerated first, per-axis mixed assignments last).
        """
        cands = candidates(problem)
        by_backend = {c.backend: c for c in cands}
        n_inner = problem.extents[-1]
        if "dft" in by_backend and n_inner <= self.coeffs.dft_pin_max_n \
                and problem.rank == 1:
            return by_backend["dft"]
        best, best_cost = None, float("inf")
        for c in cands:
            cost = self.estimate_bytes_moved(problem, c)
            if cost < best_cost:
                best, best_cost = c, cost
        if best is not None:
            return best
        return by_backend.get("xla", by_backend["bluestein"])


#: The golden hand-written model: installed by default, pinned by the
#: planner's golden ESTIMATE tests.
DEFAULT_MODEL = CostModel()

_active_model: CostModel = DEFAULT_MODEL


def get_active_model() -> CostModel:
    """The model every compatibility function (and therefore the planner,
    ``fallback_chain``, and the serve engine's chain memoization) consults."""
    return _active_model


def set_active_model(model: Optional[CostModel]) -> CostModel:
    """Install ``model`` (None restores the default); returns the previous
    active model so callers can restore it."""
    global _active_model
    prev = _active_model
    _active_model = model if model is not None else DEFAULT_MODEL
    return prev


@contextmanager
def use_model(model: Optional[CostModel]):
    """Scoped :func:`set_active_model` — a Session installs its fitted
    per-device table for the duration of a run and restores on exit."""
    prev = set_active_model(model)
    try:
        yield get_active_model()
    finally:
        set_active_model(prev)


# --- the active model's rankings, as module functions -----------------------
def hbm_passes(backend: str, n: int) -> float:
    return get_active_model().hbm_passes(backend, n)


def estimate_bytes_moved(problem: Problem, cand: Candidate) -> float:
    return get_active_model().estimate_bytes_moved(problem, cand)


def estimate_choice(problem: Problem) -> Candidate:
    return get_active_model().estimate_choice(problem)


def dist_local_engine(n: int, precision: str = "float") -> str:
    return get_active_model().dist_local_engine(n, precision)


# ---------------------------------------------------------------------------
# Versioned per-device-kind coefficient tables
# ---------------------------------------------------------------------------
def load_tables(path: str) -> dict[str, CostModel]:
    """Load a fitted coefficient-table file: ``{"schema": 1, "tables":
    {device_kind: {coeff: value}}, ...meta}``.  Raises on a newer schema —
    a stale reader must not silently misinterpret fitted numbers."""
    with open(path) as f:
        doc = json.load(f)
    schema = doc.get("schema")
    if schema != COSTMODEL_SCHEMA_VERSION:
        raise ValueError(
            f"cost-model table {path} has schema {schema!r}; this reader "
            f"understands v{COSTMODEL_SCHEMA_VERSION}")
    source = doc.get("generated_by", path)
    return {kind: CostModel(CostCoefficients.from_dict(tbl), kind,
                            source=f"{source} [{kind}]")
            for kind, tbl in doc.get("tables", {}).items()}


def save_tables(path: str, models: dict[str, CostModel],
                meta: Optional[dict] = None) -> None:
    doc = {"schema": COSTMODEL_SCHEMA_VERSION, **(meta or {}),
           "tables": {kind: m.coeffs.to_dict()
                      for kind, m in sorted(models.items())}}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")


def model_for_device(device_kind: str,
                     tables: "dict[str, CostModel] | str") -> CostModel:
    """Pick the table for ``device_kind`` — exact match first, then a
    case-insensitive prefix match (``"NVIDIA H100"`` finds a ``"nvidia"``
    table), then ``"default"``, else the hand-written model."""
    if isinstance(tables, str):
        tables = load_tables(tables)
    if device_kind in tables:
        return tables[device_kind]
    dk = device_kind.lower()
    for kind, model in sorted(tables.items()):
        k = kind.lower()
        if k != "default" and (dk.startswith(k) or k.startswith(dk)):
            return model
    return tables.get("default", DEFAULT_MODEL)


# ---------------------------------------------------------------------------
# Rank-correlation metric shared by the fitter, CI, and tests
# ---------------------------------------------------------------------------
def spearman(xs, ys) -> float:
    """Spearman rank correlation (ties get average ranks); nan for < 2
    points or zero variance.  Stdlib-only on purpose — the fitter must run
    in a bare CI container."""
    n = len(xs)
    if n != len(ys):
        raise ValueError(f"length mismatch: {n} vs {len(ys)}")
    if n < 2:
        return float("nan")

    def ranks(vals):
        order = sorted(range(n), key=lambda i: vals[i])
        r = [0.0] * n
        i = 0
        while i < n:
            j = i
            while j + 1 < n and vals[order[j + 1]] == vals[order[i]]:
                j += 1
            avg = (i + j) / 2.0 + 1.0
            for k in range(i, j + 1):
                r[order[k]] = avg
            i = j + 1
        return r

    rx, ry = ranks(list(xs)), ranks(list(ys))
    mx = sum(rx) / n
    my = sum(ry) / n
    cov = sum((a - mx) * (b - my) for a, b in zip(rx, ry))
    vx = sum((a - mx) ** 2 for a in rx)
    vy = sum((b - my) ** 2 for b in ry)
    if vx == 0.0 or vy == 0.0:
        return float("nan")
    return cov / (vx * vy) ** 0.5
