"""Wisdom: persisted planner decisions (fftw's wisdom files, §2.1).

A wisdom store maps a problem signature (extents/precision/kind/batch +
device kind) to the winning Candidate from a MEASURE/PATIENT run.  Stored as
JSON next to the results so WISDOM_ONLY runs are reproducible.

Schema v3 grows each record with the *provenance the cost-model fitter
consumes* — the winner's measured time and the rigor that produced the
knobs — and the store with **nearest-neighbor interpolation**
(:meth:`Wisdom.lookup_near`): an exact miss falls back to the selection
tuned for the closest shape in the same backend-feasibility class, so
unseen shapes get a MEASURE-grade warm start instead of a cold PATIENT
sweep.  v1 (pre-versioning) and v2 files still load unchanged.

Offline pre-generation lives in ``tools/pregen_wisdom.py`` (the
``fftwf-wisdom`` analogue, paper §3.3); the :func:`generate`/:func:`main`
entry points here are deprecated shims kept for callers of the old
``python -m repro.core.wisdom`` interface.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import threading
import warnings
from typing import Optional

from .backends import BACKENDS
from .candidates import Candidate, backend_supports
from .client import Problem
from .costmodel import estimate_bytes_moved
from .extents import classify, parse_extents
from .breaker import problem_class

# re-exported for compatibility: historical imports got these via wisdom
from .plan import PlanRigor  # noqa: F401


DEFAULT_PATH = os.path.expanduser("~/.cache/repro/wisdom.json")

#: Schema version stamped into every record this writer produces.  Loaders
#: keep records at or below their own version (missing ``v`` = version 1,
#: the pre-versioning layout) and skip-and-warn on anything newer or
#: malformed — a future writer sharing the file must never crash this one.
#: v2 added per-axis/mesh candidate fields; v3 adds ``measured_ms`` +
#: ``rigor`` provenance (consumed by tools/fit_costmodel.py) and the
#: nearest-neighbor ``lookup_near`` read path.
WISDOM_SCHEMA_VERSION = 3

#: Store key holding backend demotions (known-bad picks), not a selection:
#: ``{f"{device_kind}|{problem_class}": [backend, ...]}``.
_DEMOTED_KEY = "__demoted__"

#: Candidate knobs that encode a *shape-specific* tuning decision — a
#: nearest-neighbor warm start must drop them when the extents differ
#: (``split_n1`` names an n1*n2 factorization of the neighbor's length;
#: ``engine`` is gated on the neighbor's padded chirp length).  Batch
#: tiles and radix schedules transfer across nearby shapes.
_SHAPE_KNOBS = frozenset({"split_n1", "engine"})


def _candidate_to_record(cand: Candidate) -> dict:
    rec = {"v": WISDOM_SCHEMA_VERSION, "backend": cand.backend,
           "options": [list(kv) for kv in cand.options]}
    if cand.axes:   # per-axis ND assignment: recurse (old records omit it)
        rec["axes"] = [_candidate_to_record(a) for a in cand.axes]
    if cand.mesh:   # distributed: mesh shape is part of the selection
        rec["mesh"] = list(cand.mesh)
    return rec


def _candidate_from_record(rec: dict) -> Candidate:
    # .get defaults keep every legacy record (no axes/mesh field) loading
    return Candidate(rec["backend"],
                     tuple((k, v) for k, v in rec["options"]),
                     tuple(_candidate_from_record(a)
                           for a in rec.get("axes", ())),
                     tuple(int(s) for s in rec.get("mesh", ())))


def _strip_shape_knobs(cand: Candidate) -> Candidate:
    """A copy of ``cand`` without the shape-specific knobs (recursively for
    per-axis assignments) — what a neighbor's tuning legitimately transfers."""
    opts = tuple(kv for kv in cand.options if kv[0] not in _SHAPE_KNOBS)
    axes = tuple(_strip_shape_knobs(a) for a in cand.axes)
    return Candidate(cand.backend, opts, axes, cand.mesh)


def _feasibility_class(problem: Problem) -> frozenset:
    """The set of backends that support ``problem`` — interpolation never
    crosses this boundary: a neighbor whose support set differs (a cap or
    packing rule flips somewhere between the two shapes) is no neighbor."""
    return frozenset(b for b in BACKENDS if backend_supports(b, problem))


class Wisdom:
    """In-memory map is guarded by a lock so serving workers can look up,
    record, and save concurrently; the *file* side was already safe (atomic
    mkstemp + os.replace writes with merge-on-save, below)."""

    def __init__(self, path: str = DEFAULT_PATH, device_kind: str = ""):
        self.path = path
        self.device_kind = device_kind
        self._lock = threading.RLock()
        self._store: dict[str, dict] = self._read_disk()

    def _read_disk(self) -> dict:
        """Best-effort load: a missing file is an empty store, and so is a
        corrupt/truncated one (warn, don't crash) — a concurrent session
        must never take the whole benchmark down.  Individual entries are
        validated too: an unparseable record or one written by a future
        schema version is skipped with a warning rather than poisoning the
        load (see :data:`WISDOM_SCHEMA_VERSION`)."""
        try:
            with open(self.path) as f:
                store = json.load(f)
            if not isinstance(store, dict):
                raise ValueError(f"wisdom root is {type(store).__name__}")
        except FileNotFoundError:
            return {}
        except (json.JSONDecodeError, OSError, ValueError) as e:
            warnings.warn(f"ignoring unreadable wisdom at {self.path}: {e}")
            return {}
        clean: dict[str, dict] = {}
        for key, rec in store.items():
            why = self._invalid_reason(key, rec)
            if why is None:
                clean[key] = rec
            else:
                warnings.warn(
                    f"skipping wisdom entry {key!r} in {self.path}: {why}")
        return clean

    @staticmethod
    def _invalid_reason(key: str, rec) -> Optional[str]:
        """None for a loadable entry, else a human-readable skip reason."""
        if key == _DEMOTED_KEY:
            if isinstance(rec, dict) and all(
                    isinstance(v, list) and all(isinstance(b, str) for b in v)
                    for v in rec.values()):
                return None
            return "malformed demotion table"
        if not isinstance(rec, dict):
            return f"record is {type(rec).__name__}, not an object"
        v = rec.get("v", 1)
        if not isinstance(v, int) or v < 1:
            return f"bad schema version {v!r}"
        if v > WISDOM_SCHEMA_VERSION:
            return (f"schema version {v} is newer than this reader "
                    f"(v{WISDOM_SCHEMA_VERSION})")
        if not isinstance(rec.get("backend"), str) \
                or not isinstance(rec.get("options"), list):
            return "missing/malformed backend or options"
        ms = rec.get("measured_ms")
        if ms is not None and not isinstance(ms, (int, float)):
            return f"malformed measured_ms {ms!r}"
        try:
            _candidate_from_record(rec)
        except Exception as e:
            return f"unparseable candidate ({type(e).__name__}: {e})"
        return None

    def _key(self, problem: Problem, scope: str = "") -> str:
        """Unscoped keys hold the open planner's (Planned client) choices —
        the original store layout, so existing wisdom files stay valid.  A
        ``scope`` (the pinned client's backend) namespaces per-library
        tuning, mirroring gearshifft's one-wisdom-file-per-binary: a knob
        sweep won by StockhamPallas must not overwrite the open planner's
        cross-backend winner for the same problem."""
        base = f"{self.device_kind}|{problem.signature()}"
        return f"{base}|{scope}" if scope else base

    def _parse_key(self, key: str, scope: str = "") -> Optional[Problem]:
        """Invert :meth:`_key` for entries in this store's device kind and
        ``scope`` namespace; None for any other (or unparseable) key."""
        prefix = f"{self.device_kind}|"
        if not key.startswith(prefix):
            return None
        rest = key[len(prefix):]
        if scope:
            suffix = f"|{scope}"
            if not rest.endswith(suffix):
                return None
            rest = rest[:-len(suffix)]
        if "|" in rest:     # a differently-scoped (or demotion) entry
            return None
        parts = rest.split("/")
        if len(parts) != 4 or not parts[3].startswith("b"):
            return None
        try:
            return Problem(parse_extents(parts[0]), parts[2], parts[1],
                           batch=int(parts[3][1:]))
        except Exception:
            return None

    def lookup(self, problem: Problem, scope: str = "") -> Optional[Candidate]:
        with self._lock:
            rec = self._store.get(self._key(problem, scope))
        if rec is None:
            return None
        return _candidate_from_record(rec)

    def lookup_near(self, problem: Problem, scope: str = ""
                    ) -> Optional[tuple[Candidate, str]]:
        """Nearest-neighbor interpolation over (extent, batch, rank): the
        selection persisted for the closest shape in the same
        backend-feasibility class, with shape-specific knobs stripped.

        Returns ``(candidate, neighbor_key)`` or None.  'Closest' is
        Euclidean distance in log2 space over the per-axis extents and
        batch — the resolution at which transform behavior actually
        changes.  A neighbor never crosses a feasibility boundary: it must
        share the query's rank, extent class, and full backend-support set
        (see :func:`_feasibility_class`), its candidate must itself be
        feasible for the query, and mesh-shaped (distributed) selections
        never transfer — a decomposition tuned for one device count is
        meaningless for another shape on another mesh.
        """
        exts_q = problem.extents
        class_q = classify(exts_q)
        feas_q = None       # computed lazily: most stores miss outright
        best: Optional[tuple[float, str, Candidate]] = None
        with self._lock:
            items = [(k, rec) for k, rec in self._store.items()
                     if k != _DEMOTED_KEY]
        for key, rec in items:
            neighbor = self._parse_key(key, scope)
            if neighbor is None or (neighbor.extents == exts_q
                                    and neighbor.batch == problem.batch):
                continue    # foreign namespace, or the exact key (a miss
                            # here means the caller already tried it)
            if (neighbor.rank != problem.rank
                    or neighbor.kind != problem.kind
                    or neighbor.precision != problem.precision
                    or classify(neighbor.extents) != class_q):
                continue
            if feas_q is None:
                feas_q = _feasibility_class(problem)
            if _feasibility_class(neighbor) != feas_q:
                continue
            try:
                cand = _candidate_from_record(rec)
            except Exception:
                continue
            if cand.mesh:
                continue
            if neighbor.extents != exts_q:
                cand = _strip_shape_knobs(cand)
            if cand.backend != "nd" and cand.backend not in feas_q:
                continue
            if estimate_bytes_moved(problem, cand) == float("inf"):
                continue    # per-axis assignment infeasible at these extents
            d = sum((math.log2(a) - math.log2(b)) ** 2
                    for a, b in zip(exts_q, neighbor.extents))
            d += (math.log2(problem.batch) - math.log2(neighbor.batch)) ** 2
            if best is None or (d, key) < (best[0], best[1]):
                best = (d, key, cand)
        if best is None:
            return None
        return best[2], best[1]

    def record(self, problem: Problem, cand: Candidate, scope: str = "",
               measured_ms: Optional[float] = None,
               rigor: Optional[str] = None) -> None:
        """Persist a selection; ``measured_ms`` (the winner's timed
        best-of-reps) and ``rigor`` record the provenance the cost-model
        fitter trains on.  Both are optional so legacy call sites — and
        selections that were never timed — keep writing valid records."""
        rec = _candidate_to_record(cand)
        if measured_ms is not None and measured_ms == measured_ms:
            rec["measured_ms"] = float(measured_ms)
        if rigor is not None:
            rec["rigor"] = str(rigor)
        with self._lock:
            self._store[self._key(problem, scope)] = rec

    # --- demotions: known-bad (backend, problem-class) pairs --------------
    def _demote_key(self, problem: Problem) -> str:
        return f"{self.device_kind}|{problem_class(problem)}"

    def record_demotion(self, problem: Problem, backend: str) -> None:
        """Persistably quarantine ``backend`` for this problem-class: warm
        sessions (and the planner's ESTIMATE path) skip it outright."""
        with self._lock:
            table = self._store.setdefault(_DEMOTED_KEY, {})
            row = table.setdefault(self._demote_key(problem), [])
            if backend not in row:
                row.append(backend)

    def demoted(self, problem: Problem) -> frozenset:
        with self._lock:
            table = self._store.get(_DEMOTED_KEY, {})
            return frozenset(table.get(self._demote_key(problem), ()))

    def measurements(self) -> list[tuple[Problem, Candidate, float]]:
        """Every v3 record carrying a measured time, parsed — the fitter's
        training rows from this store's device kind (any scope)."""
        out = []
        with self._lock:
            items = list(self._store.items())
        for key, rec in items:
            if key == _DEMOTED_KEY or not isinstance(rec, dict):
                continue
            ms = rec.get("measured_ms")
            if not isinstance(ms, (int, float)):
                continue
            # accept scoped keys too: strip a trailing |scope namespace
            problem = self._parse_key(key)
            if problem is None and key.count("|") >= 2:
                problem = self._parse_key(key[:key.rfind("|")])
            if problem is None:
                continue
            try:
                out.append((problem, _candidate_from_record(rec), float(ms)))
            except Exception:
                continue
        return out

    def save(self) -> None:
        """Atomic, concurrent-tolerant write.

        Merge-on-save: entries another session persisted since our load are
        re-read and kept.  Conflicting selections keep ours (they're newer),
        but **field-wise**: v3 provenance fields (``measured_ms``/``rigor``)
        another session attached to the same key survive a save by a writer
        that didn't set them — concurrent saves union-merge v3 fields.
        The temp file is uniquely named (mkstemp, not a fixed ``.tmp`` two
        racing sessions would clobber), fsync'd, then os.replace'd — readers
        always see a complete JSON document, never a torn write.
        """
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        with self._lock:
            merged = self._read_disk()
            # demotions union across sessions: a pair one session proved bad
            # stays quarantined even when another session saves concurrently
            disk_dem = merged.get(_DEMOTED_KEY, {})
            ours_dem = self._store.get(_DEMOTED_KEY, {})
            union = {k: list(v) for k, v in disk_dem.items()}
            for k, backends in ours_dem.items():
                row = union.setdefault(k, [])
                row += [b for b in backends if b not in row]
            for k, rec in self._store.items():
                if k == _DEMOTED_KEY:
                    continue
                disk_rec = merged.get(k)
                if isinstance(disk_rec, dict) and isinstance(rec, dict) \
                        and disk_rec.get("backend") == rec.get("backend") \
                        and disk_rec.get("options") == rec.get("options"):
                    # same selection: union the provenance fields
                    merged[k] = {**disk_rec, **rec}
                else:
                    merged[k] = rec
            if union:
                merged[_DEMOTED_KEY] = union
            self._store = merged
            snapshot = dict(merged)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".wisdom-", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(snapshot, f, indent=1, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def __len__(self) -> int:
        with self._lock:
            return len(self._store) - (_DEMOTED_KEY in self._store)


def generate(sizes, path: str = DEFAULT_PATH, rigor: PlanRigor = PlanRigor.PATIENT,
             kinds=("Outplace_Real", "Outplace_Complex"), precisions=("float",)) -> Wisdom:
    """Deprecated: use ``tools/pregen_wisdom.py`` (it sweeps the full
    support matrix, records v3 provenance, and ships checked-in packs)."""
    warnings.warn(
        "repro.core.wisdom.generate is deprecated; use tools/pregen_wisdom.py",
        DeprecationWarning, stacklevel=2)
    import jax
    from .plan import make_plan
    from .clients.jax_fft import build_forward

    wisdom = Wisdom(path, device_kind=jax.devices()[0].device_kind)
    for ext in sizes:
        for kind in kinds:
            for prec in precisions:
                problem = Problem(tuple(ext), kind, prec)
                # near=False: every swept shape gets a real sweep — a
                # pregeneration run must not inherit its neighbor's pick
                make_plan(problem, rigor, build=lambda c: build_forward(problem, c),
                          wisdom=wisdom, near=False)
    wisdom.save()
    return wisdom


def main(argv=None) -> None:
    """Deprecated CLI shim: forwards to ``tools/pregen_wisdom.py``."""
    warnings.warn(
        "python -m repro.core.wisdom is deprecated; "
        "use tools/pregen_wisdom.py", DeprecationWarning, stacklevel=2)
    import argparse

    p = argparse.ArgumentParser(description="pre-generate repro FFT wisdom "
                                "(deprecated: use tools/pregen_wisdom.py)")
    p.add_argument("-o", "--output", default=DEFAULT_PATH)
    p.add_argument("--max-exp", type=int, default=12,
                   help="powers of two up to 2^max_exp (1D) / 2^(max_exp//3*3) (3D)")
    args = p.parse_args(argv)
    sizes = [(2 ** e,) for e in range(1, args.max_exp + 1)]
    sizes += [(2 ** e,) * 3 for e in range(1, args.max_exp // 3 + 1)]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        w = generate(sizes, args.output)
    print(f"wrote {len(w)} wisdom entries to {args.output}")


if __name__ == "__main__":
    main()
