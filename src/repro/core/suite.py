"""Declarative suite descriptions + the Session facade (engine layer 4).

gearshifft drives every library binary from one configuration surface
(extents files + CLI flags) so cross-library comparisons stay reproducible.
This module is that surface for the whole engine:

* :class:`SuiteSpec` — a frozen, serializable description of one benchmark
  run: which clients, which extents (explicit lists *and* generator-backed
  sweep classes ``powerof2``/``radix357``/``oddshape``), kinds, precisions,
  batch, planner rigor, warmups/repetitions, plan-cache policy, wisdom path,
  and the result sink.  Round-trips to TOML (the ``-f extents_file``
  analogue) and JSON, so any run can be saved, replayed, and diffed.
* :class:`Session` — owns the Context lifecycle, device discovery, wisdom
  loading, the (shareable) plan cache, and result sinks.
  ``Session.run(spec)`` returns a :class:`ResultSet`.
* :class:`ResultSet` — the materialized rows of a run plus the
  aggregation/query helpers the table scripts consume.

The CLI (:mod:`repro.core.cli`) is a thin argparse→SuiteSpec adapter, every
``benchmarks/table_*.py`` is a spec run through ``run_suite``, and
programmatic users construct specs directly — one run description behind all
three surfaces.
"""

from __future__ import annotations

import importlib
import json
import os
import statistics
from contextlib import nullcontext
from dataclasses import dataclass, fields
from typing import Any, Iterable, Iterator, Optional, Sequence

from .benchmark import BenchmarkConfig, run_nodes
from .client import KINDS, PRECISIONS, Context
from .extents import SWEEP_CLASSES, format_extents, parse_extents, sweep_extents
from .plan import PlanCache, PlanCacheStats, PlanRigor
from .registry import get_client
from .results import (ResultSink, Row, aggregate_rows, columns_for,
                      open_sink, percentile_summary, rows_to_csv, save_csv)
from .tree import BenchNode, build_tree, select
from .wisdom import Wisdom


# ---------------------------------------------------------------------------
# sweep specs — generator-backed extent classes
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class SweepSpec:
    """One generator-backed extent sweep (paper Fig. 7 extent classes).

    ``extent_class`` is one of ``powerof2`` (requires ``min_exp``/``max_exp``),
    ``radix357`` (optional ``count``/``start``) or ``oddshape`` (optional
    ``count``); ``rank`` repeats the size along 1..3 dimensions.
    """

    extent_class: str
    rank: int = 1
    min_exp: Optional[int] = None
    max_exp: Optional[int] = None
    count: Optional[int] = None
    start: Optional[int] = None

    def __post_init__(self):
        # validate eagerly: a bad sweep must fail at spec-build time
        self.extents()

    def extents(self) -> list[tuple[int, ...]]:
        params = {k: getattr(self, k)
                  for k in ("min_exp", "max_exp", "count", "start")
                  if getattr(self, k) is not None}
        return sweep_extents(self.extent_class, self.rank, **params)

    def to_dict(self) -> dict:
        d = {"class": self.extent_class, "rank": self.rank}
        for k in ("min_exp", "max_exp", "count", "start"):
            v = getattr(self, k)
            if v is not None:
                d[k] = v
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "SweepSpec":
        d = dict(d)
        extent_class = d.pop("class", None) or d.pop("extent_class", None)
        if extent_class is None:
            raise ValueError(f"sweep entry missing 'class': {d}")
        known = {"rank", "min_exp", "max_exp", "count", "start"}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown sweep key(s) {sorted(unknown)}; "
                             f"allowed: class, {', '.join(sorted(known))}")
        return cls(extent_class=extent_class, **d)


def _as_extent(v) -> tuple[int, ...]:
    if isinstance(v, str):
        return parse_extents(v)
    if isinstance(v, int):
        return (v,)
    return parse_extents(format_extents(tuple(int(x) for x in v)))


# ---------------------------------------------------------------------------
# the suite spec
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class SuiteSpec:
    """A complete, serializable description of one benchmark run.

    Every field has a TOML/JSON representation; :meth:`to_toml` /
    :meth:`from_toml` (and the JSON twins) round-trip to an equal spec, so
    ``--dump-config`` → ``--config`` replays any CLI invocation exactly.
    """

    clients: tuple[str, ...] = ("XlaFFT",)
    load: tuple[str, ...] = ()                  # extra client modules
    extents: tuple[tuple[int, ...], ...] = ()   # explicit extents
    sweeps: tuple[SweepSpec, ...] = ()          # generator-backed extents
    kinds: tuple[str, ...] = KINDS
    precisions: tuple[str, ...] = ("float",)
    batch: int = 1
    device_counts: tuple[int, ...] = ()         # multi-device scaling axis
    select: Optional[str] = None                # '-r' wildcard pattern
    rigor: str = "estimate"
    warmups: int = 1
    repetitions: int = 3
    error_bound: float = 1e-5
    seed: int = 2017
    plan_cache: bool = True
    wisdom: Optional[str] = None                # wisdom JSON path
    costmodel: Optional[str] = None             # fitted coefficient table path
    output: Optional[str] = "result.csv"        # None = in-memory only
    format: Optional[str] = None                # 'csv' | 'jsonl' | by extension
    verbose: bool = False

    def __post_init__(self):
        norm = object.__setattr__
        norm(self, "clients", tuple(str(c) for c in self.clients))
        norm(self, "load", tuple(str(m) for m in self.load))
        norm(self, "extents", tuple(_as_extent(e) for e in self.extents))
        norm(self, "sweeps", tuple(
            s if isinstance(s, SweepSpec) else SweepSpec.from_dict(s)
            for s in self.sweeps))
        norm(self, "kinds", tuple(self.kinds))
        norm(self, "precisions", tuple(self.precisions))
        norm(self, "device_counts", tuple(int(n) for n in self.device_counts))
        if any(n < 1 for n in self.device_counts):
            raise ValueError(f"device_counts must be >= 1, "
                             f"got {self.device_counts}")
        if isinstance(self.rigor, PlanRigor):
            norm(self, "rigor", self.rigor.value)
        bad = set(self.kinds) - set(KINDS)
        if bad:
            raise ValueError(f"unknown kind(s) {sorted(bad)}; known: {KINDS}")
        bad = set(self.precisions) - set(PRECISIONS)
        if bad:
            raise ValueError(
                f"unknown precision(s) {sorted(bad)}; known: {PRECISIONS}")
        if self.rigor not in {r.value for r in PlanRigor}:
            raise ValueError(f"unknown rigor {self.rigor!r}; known: "
                             f"{[r.value for r in PlanRigor]}")
        if self.batch < 1:
            raise ValueError(f"batch must be >= 1, got {self.batch}")
        if self.warmups < 0 or self.repetitions < 0:
            raise ValueError("warmups/repetitions must be >= 0")
        if self.format is not None and self.format not in ("csv", "jsonl"):
            raise ValueError(f"unknown format {self.format!r}")

    # --- node tree ---------------------------------------------------------
    def resolved_extents(self) -> tuple[tuple[int, ...], ...]:
        """Explicit extents followed by every sweep's expansion, in order."""
        out = list(self.extents)
        for sweep in self.sweeps:
            out.extend(sweep.extents())
        return tuple(out)

    def load_modules(self) -> None:
        """Import the spec's extra client modules (registry side effects)."""
        for mod in self.load:
            importlib.import_module(mod)

    def build_nodes(self) -> list[BenchNode]:
        """Materialize the benchmark tree this spec describes."""
        # built-in clients self-register on import (deferred: spec
        # serialization must work without pulling in jax)
        from .clients import jax_fft, dist_fft, serve_fft  # noqa: F401
        self.load_modules()
        exts = self.resolved_extents()
        if not exts:
            raise ValueError(
                "spec resolves no extents: give 'extents' and/or 'sweeps'")
        nodes = build_tree([get_client(c) for c in self.clients], exts,
                           kinds=self.kinds, precisions=self.precisions,
                           batch=self.batch)
        return select(nodes, self.select)

    def benchmark_config(self) -> BenchmarkConfig:
        return BenchmarkConfig(
            warmups=self.warmups, repetitions=self.repetitions,
            error_bound=self.error_bound, rigor=PlanRigor(self.rigor),
            output=self.output or "result.csv", seed=self.seed)

    # --- serialization -----------------------------------------------------
    def to_dict(self) -> dict:
        """Plain-data form: extents as '128x128' strings (the CLI syntax),
        sweeps as a list of tables, ``None`` fields omitted."""
        d: dict[str, Any] = {
            "clients": list(self.clients),
            "extents": [format_extents(e) for e in self.extents],
            "kinds": list(self.kinds),
            "precisions": list(self.precisions),
            "batch": self.batch,
            "rigor": self.rigor,
            "warmups": self.warmups,
            "repetitions": self.repetitions,
            "error_bound": self.error_bound,
            "seed": self.seed,
            "plan_cache": self.plan_cache,
            "verbose": self.verbose,
        }
        if self.load:
            d["load"] = list(self.load)
        if self.device_counts:
            # the scaling axis a driver (tools/bench_compare.py --devices)
            # fans out over — one subprocess per count, since a process's
            # XLA device count is fixed at first jax init.  Omitted when
            # empty so legacy specs round-trip byte-identically.
            d["device_counts"] = list(self.device_counts)
        for k in ("select", "wisdom", "costmodel", "output", "format"):
            v = getattr(self, k)
            if v is not None:
                d[k] = v
        if self.sweeps:
            d["sweep"] = [s.to_dict() for s in self.sweeps]
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "SuiteSpec":
        d = dict(d)
        sweeps = d.pop("sweep", None) or d.pop("sweeps", None) or ()
        known = {f.name for f in fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown SuiteSpec key(s) {sorted(unknown)}; "
                             f"known: {', '.join(sorted(known | {'sweep'}))}")
        return cls(sweeps=tuple(SweepSpec.from_dict(s) if isinstance(s, dict)
                                else s for s in sweeps), **d)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2) + "\n"

    @classmethod
    def from_json(cls, text: str) -> "SuiteSpec":
        return cls.from_dict(json.loads(text))

    def to_toml(self) -> str:
        """Emit the spec as TOML (scalar/array keys, then ``[[sweep]]``
        tables).  Hand-rolled writer: the container has no TOML emitter."""
        d = self.to_dict()
        sweeps = d.pop("sweep", [])
        lines = [f"{k} = {_toml_value(v)}" for k, v in d.items()]
        for s in sweeps:
            lines += ["", "[[sweep]]"]
            lines += [f"{k} = {_toml_value(v)}" for k, v in s.items()]
        return "\n".join(lines) + "\n"

    @classmethod
    def from_toml(cls, text: str) -> "SuiteSpec":
        return cls.from_dict(_toml_loads(text))

    def save(self, path: str) -> str:
        """Write the spec to ``path`` (TOML, or JSON for ``.json``)."""
        text = (self.to_json() if path.endswith(".json") else self.to_toml())
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
        return path

    @classmethod
    def from_file(cls, path: str) -> "SuiteSpec":
        with open(path) as f:
            text = f.read()
        if path.endswith(".json"):
            return cls.from_json(text)
        return cls.from_toml(text)


def _toml_value(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return repr(v)
    if isinstance(v, str):
        return json.dumps(v)   # JSON string escaping is valid TOML
    if isinstance(v, (list, tuple)):
        return "[" + ", ".join(_toml_value(x) for x in v) + "]"
    raise TypeError(f"cannot serialize {type(v).__name__} to TOML: {v!r}")


def _toml_loads(text: str) -> dict:
    try:
        import tomllib
    except ImportError:                           # Python 3.10: use tomli
        try:
            import tomli as tomllib
        except ImportError as e:
            raise RuntimeError(
                "reading TOML specs needs Python >= 3.11 (tomllib) or the "
                "tomli package; use a .json spec instead") from e
    return tomllib.loads(text)


# ---------------------------------------------------------------------------
# result sets
# ---------------------------------------------------------------------------
class ResultSet:
    """The materialized rows of one suite run + query/aggregation helpers
    (moved here from ``ResultWriter``, which remains a plain sink)."""

    def __init__(self, rows: Iterable[Row], columns: Sequence[str],
                 path: Optional[str] = None,
                 plan_stats: Optional[PlanCacheStats] = None):
        self.rows: list[Row] = list(rows)
        self.columns = list(columns)
        self.path = path              # file the run streamed to, if any
        self.plan_stats = plan_stats  # PlanCacheStats when caching was on

    # --- container protocol ------------------------------------------------
    def __len__(self) -> int:
        return len(self.rows)

    def __iter__(self) -> Iterator[Row]:
        return iter(self.rows)

    @property
    def n_rows(self) -> int:
        return len(self.rows)

    @property
    def n_failures(self) -> int:
        return sum(1 for r in self.rows if not r.success)

    # --- queries -----------------------------------------------------------
    def query(self, **eq) -> list[Row]:
        """Rows whose attributes equal every given keyword, e.g.
        ``rs.query(op='execute_forward', library='XlaFFT')``."""
        return [r for r in self.rows
                if all(getattr(r, k) == v for k, v in eq.items())]

    def failures(self) -> list[Row]:
        return [r for r in self.rows if not r.success]

    def aggregate(self, op: Optional[str] = None, percentiles: bool = False):
        """mean/stdev per (library, extents, precision, kind, rigor, op);
        ``percentiles=True`` adds p50/p95/p99 columns (see
        :func:`repro.core.results.aggregate_rows`)."""
        return aggregate_rows(self.rows, op, percentiles=percentiles)

    def aggregate_named(self, op: Optional[str] = None,
                        percentiles: bool = False):
        """Same grouping as :meth:`aggregate` but through the shared
        comparison core directly: a list of
        :class:`repro.core.compare.AggRow` with *named* fields
        (``a.library``, ``a.mean``, ``a.p99``, ...) — what the
        ``benchmarks/table_*`` reporters consume instead of unpacking
        positional tuples."""
        from .compare import aggregate_result_rows
        return aggregate_result_rows(self.rows, op, percentiles=percentiles)

    def summary(self, latency_op: str = "execute_forward") -> dict:
        """Planner-cost overview (paper Figs. 4-5) without grepping CSV rows:
        row/failure counts, aggregate planning time (the init ops carry
        planning + compilation), its cold-compile share, and the plan-cache
        hit/miss totals — per-row markers plus the session-level stats.

        When any successful ``latency_op`` rows exist (``execute_forward``
        by default; pass ``"serve_request"`` for service replays) the
        summary also carries their tail-latency view — mean + p50/p95/p99
        over every matching row."""
        init_ops = ("init_forward", "init_inverse")
        plan_rows = [r for r in self.rows if r.op in init_ops]
        events = [r.plan_cache for r in plan_rows if r.plan_cache]
        total = sum(r.time_ms for r in plan_rows)
        if events:
            cold = sum(r.time_ms for r in plan_rows if r.plan_cache == "miss")
        else:
            # no hit/miss markers = plan cache off: every init op re-plans
            # and re-compiles, so the whole planning time is cold
            cold = total
        out = {
            "rows": self.n_rows,
            "failures": self.n_failures,
            "plan_time_ms": total,
            "plan_time_cold_ms": cold,
            "plan_cache_hits": sum(1 for e in events if e == "hit"),
            "plan_cache_misses": sum(1 for e in events if e == "miss"),
        }
        lat = [r.time_ms for r in self.rows
               if r.success and r.op == latency_op]
        if lat:
            out["latency_ms"] = {"op": latency_op, "n": len(lat),
                                 "mean": statistics.fmean(lat),
                                 **percentile_summary(lat)}
        if self.plan_stats is not None:
            out["plan_cache"] = self.plan_stats.as_dict()
        return out

    # --- export ------------------------------------------------------------
    def to_csv_string(self) -> str:
        return rows_to_csv(self.rows, self.columns)

    def save(self, path: str) -> str:
        save_csv(path, self.rows, self.columns)
        self.path = path
        return path

    @classmethod
    def concat(cls, results: Sequence["ResultSet"]) -> "ResultSet":
        """Merge runs that share a schema into one result set."""
        if not results:
            return cls([], columns_for(False))
        cols = results[0].columns
        for r in results[1:]:
            if r.columns != cols:
                raise ValueError("cannot concat ResultSets with different "
                                 f"columns: {cols} vs {r.columns}")
        return cls([row for r in results for row in r.rows], cols,
                   path=results[0].path)


class _CollectorSink(ResultSink):
    """In-memory sink feeding a ResultSet."""

    def __init__(self, columns):
        super().__init__(path="", columns=columns)
        self.rows: list[Row] = []

    def _write(self, row: Row) -> None:
        self.rows.append(row)


class _TeeSink(ResultSink):
    """Forward every row to several sinks (memory + streaming file)."""

    def __init__(self, sinks: Sequence[ResultSink]):
        super().__init__(path="", columns=sinks[0].columns)
        self.sinks = list(sinks)

    def add(self, row: Row) -> None:
        self.n_rows += 1
        if not row.success:
            self.n_failures += 1
        for s in self.sinks:
            s.add(row)

    def save(self) -> str:
        for s in self.sinks:
            s.save()
        return self.path


# ---------------------------------------------------------------------------
# the session facade
# ---------------------------------------------------------------------------
class Session:
    """Owns everything a run needs besides its description: the Context
    lifecycle, device discovery, wisdom, the plan/executable cache, and the
    result sinks.  Reusing one Session across several ``run`` calls shares
    the plan cache, so repeated specs dispatch warm executables.
    """

    def __init__(self, context: Optional[Context] = None,
                 plan_cache: Optional[PlanCache] = None,
                 wisdom: Optional[Wisdom] = None):
        self.context = context if context is not None else Context()
        self._plan_cache = plan_cache
        self._wisdom = wisdom
        self._device_kind: Optional[str] = None

    @property
    def device_kind(self) -> str:
        """Discovered JAX device kind — the key wisdom stores are written
        under by ``python -m repro.core.wisdom``."""
        if self._device_kind is None:
            import jax
            self._device_kind = jax.devices()[0].device_kind
        return self._device_kind

    @property
    def plan_cache(self) -> PlanCache:
        """The session-lifetime plan cache (created on first use)."""
        if self._plan_cache is None:
            self._plan_cache = PlanCache()
        return self._plan_cache

    def _resolve_wisdom(self, spec: SuiteSpec) -> Optional[Wisdom]:
        if self._wisdom is not None:
            return self._wisdom
        if spec.wisdom:
            return Wisdom(spec.wisdom, device_kind=self.device_kind)
        return None

    def run(self, spec: SuiteSpec,
            nodes: Optional[Sequence[BenchNode]] = None) -> ResultSet:
        """Execute the spec; returns the materialized :class:`ResultSet`.

        ``nodes`` overrides the spec's own tree (the CLI pre-builds it to
        report empty selections before any device work happens).
        """
        if nodes is None:
            nodes = spec.build_nodes()
        else:
            spec.load_modules()
        cache = self.plan_cache if spec.plan_cache else None
        wisdom = self._resolve_wisdom(spec)
        columns = columns_for(cache is not None,
                              plan_source=wisdom is not None)
        collector = _CollectorSink(columns)
        sinks: list[ResultSink] = [collector]
        if spec.output:
            sinks.append(open_sink(spec.output, fmt=spec.format,
                                   columns=columns))
        writer = _TeeSink(sinks)
        # a fitted coefficient table, when the spec names one, becomes the
        # active cost model for the whole run: ESTIMATE picks, MEASURE
        # candidate orderings, and fallback chains all re-rank under it
        if spec.costmodel:
            from .costmodel import model_for_device, use_model
            model_cm = use_model(model_for_device(self.device_kind,
                                                  spec.costmodel))
        else:
            model_cm = nullcontext()
        with model_cm:
            run_nodes(nodes, context=self.context,
                      config=spec.benchmark_config(), writer=writer,
                      plan_cache=cache, wisdom=wisdom, verbose=spec.verbose)
        writer.save()
        if wisdom is not None and spec.rigor in (PlanRigor.MEASURE.value,
                                                 PlanRigor.PATIENT.value):
            # persist tuned selections: a warm Session (or a later process
            # pointing at the same wisdom file) skips the candidate sweep
            wisdom.save()
        return ResultSet(collector.rows, columns,
                         path=spec.output if spec.output else None,
                         plan_stats=cache.stats if cache else None)


def run_suite(spec: SuiteSpec, session: Optional[Session] = None) -> ResultSet:
    """One-shot convenience: run ``spec`` in a fresh (or given) Session."""
    return (session if session is not None else Session()).run(spec)


# ---------------------------------------------------------------------------
# support matrix
# ---------------------------------------------------------------------------
#: Power-of-two probe extents per rank used to answer "does this backend
#: support rank r at all?" — pow2 so every pow2-only backend registers its
#: ranks; extent-dependent caps (VMEM budgets, smoothness) still apply to
#: individual problems via ``plan.backend_supports``.
SUPPORT_PROBE_EXTENTS = {1: (16,), 2: (8, 16), 3: (4, 4, 8)}


def support_matrix(kinds: Sequence[str] = KINDS,
                   precisions: Sequence[str] = PRECISIONS,
                   probes: Optional[dict] = None) -> list[dict]:
    """The backend x kind x rank x precision feasibility table.

    One row per cell, ``{"backend", "kind", "precision", "rank", "extents",
    "supported"}`` — the single source of truth behind the README's
    support-matrix section and the conformance matrix's cell enumeration
    (``tests/test_conformance.py`` sweeps exactly the supported cells).
    """
    from .client import Problem
    from .backends import BACKENDS
    from .candidates import backend_supports

    probes = dict(SUPPORT_PROBE_EXTENTS if probes is None else probes)
    rows = []
    for backend in BACKENDS:
        for rank, extents in sorted(probes.items()):
            for kind in kinds:
                for precision in precisions:
                    problem = Problem(tuple(extents), kind, precision)
                    rows.append({
                        "backend": backend, "kind": kind,
                        "precision": precision, "rank": rank,
                        "extents": tuple(extents),
                        "supported": backend_supports(backend, problem),
                    })
    return rows


def dist_support_matrix(device_counts: Sequence[int] = (2, 4, 8),
                        kinds: Sequence[str] = KINDS,
                        probes: Optional[dict] = None) -> list[dict]:
    """The distributed-decomposition x kind x rank x device-count table —
    the device-count column of the README support matrix.

    Mesh shapes per backend follow the planner's enumeration: ``dist1d`` and
    ``slab`` flatten the P devices, ``pencil`` uses the most balanced
    (Pr, Pc) factorization.
    """
    from .client import Problem
    from .candidates import DIST_BACKENDS, _pencil_mesh_shapes, dist_supports

    probes = dict(SUPPORT_PROBE_EXTENTS if probes is None else probes)
    rows = []
    for backend in DIST_BACKENDS:
        for devices in device_counts:
            for rank, extents in sorted(probes.items()):
                for kind in kinds:
                    if backend == "pencil":
                        shapes = _pencil_mesh_shapes(devices) or [(devices,)]
                        mesh_shape = shapes[0]
                    else:
                        mesh_shape = (devices,)
                    problem = Problem(tuple(extents), kind, "float")
                    rows.append({
                        "backend": backend, "kind": kind, "rank": rank,
                        "devices": devices, "extents": tuple(extents),
                        "supported": dist_supports(backend, problem,
                                                   mesh_shape),
                    })
    return rows


__all__ = ["SweepSpec", "SuiteSpec", "ResultSet", "Session", "run_suite",
           "SWEEP_CLASSES", "SUPPORT_PROBE_EXTENTS", "support_matrix",
           "dist_support_matrix"]
