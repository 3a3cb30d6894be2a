"""Differential conformance matrix: every registered backend x all four
kinds x both precisions x rank-1/2/3 extents against ``numpy.fft``, plus
gearshifft-style roundtrip checks (``ifft(fft(x)) ~= x``, rel-L2 <= 1e-3
float / 1e-8 double — see ``helpers.accuracy``).

The cell set is derived from ``plan.backend_supports`` via
``suite.support_matrix`` — the same source of truth the planner and the
README table use — so a backend that silently drops a rank/kind it claims
breaks this module, and a backend that grows support is swept automatically.

Two tiers:
* fast subset (default, tier-1): every backend x kind pair once, ranks
  rotated so all three ranks are exercised per backend, float precision.
  Inplace/Outplace share the transform math, so each distinct
  (backend, extents, complex?, precision) computation is verified once and
  memoized across kinds.
* full matrix: every supported cell, both precisions — run by the dedicated
  CI job step via ``CONFORMANCE_FULL=1`` under the ``slow`` marker.
"""

from __future__ import annotations

import os
import zlib

import numpy as np
import pytest
import jax.numpy as jnp

from helpers.accuracy import assert_rel_l2, numpy_forward, rand_input
from repro.core.client import KINDS, PRECISIONS, Problem
from repro.core.backends import BACKENDS
from repro.core.candidates import Candidate, backend_supports
from repro.core.suite import SUPPORT_PROBE_EXTENTS, support_matrix
from repro.core.clients.jax_fft import build_forward, build_inverse

RANKS = sorted(SUPPORT_PROBE_EXTENTS)

#: One small probe per non-pow2 extent class (paper Fig. 7): 12 = 2^2*3 is
#: the radix357 canary (its packed real half, 6, is still 7-smooth), 19 the
#: oddshape one.  Every backend claiming support at these extents gets at
#: least one tier-1 cell per class, and the full matrix sweeps them across
#: kinds x precisions.
CLASS_PROBE_EXTENTS = {"radix357": (12,), "oddshape": (19,)}


def check_cell(backend: str, problem: Problem,
               _verified: dict = {}) -> None:
    """Differential + roundtrip check of one matrix cell.  Memoized on the
    computation actually performed — Inplace/Outplace kinds build identical
    transforms, so each is verified once per (extents, complex?, precision).
    """
    key = (backend, problem.extents, problem.complex_input, problem.precision)
    if key in _verified:
        return
    # stable per-cell seed (hash() varies with PYTHONHASHSEED; a failing
    # cell must reproduce with the same data on rerun)
    x = rand_input(problem, seed=zlib.crc32(repr(key).encode()))
    fwd = build_forward(problem, Candidate(backend))
    spec = np.asarray(fwd(jnp.asarray(x)))
    want = numpy_forward(problem, x)
    assert spec.shape == want.shape, \
        f"{backend} {problem.signature()}: shape {spec.shape} != {want.shape}"
    assert_rel_l2(spec, want, problem.precision,
                  f"{backend} {problem.signature()} forward")
    inv = build_inverse(problem, Candidate(backend))
    back = np.asarray(inv(jnp.asarray(spec)))
    assert_rel_l2(back, x, problem.precision,
                  f"{backend} {problem.signature()} roundtrip")
    _verified[key] = True


# ---------------------------------------------------------------------------
# fast subset (tier-1)
# ---------------------------------------------------------------------------
def _fast_cells() -> list[tuple[str, int, str]]:
    """Every backend x kind once, rank rotating with the cell index so all
    supported ranks get exercised per backend."""
    cells = []
    for bi, backend in enumerate(BACKENDS):
        for ki, kind in enumerate(KINDS):
            for off in range(len(RANKS)):
                rank = RANKS[(bi + ki + off) % len(RANKS)]
                problem = Problem(SUPPORT_PROBE_EXTENTS[rank], kind, "float")
                if backend_supports(backend, problem):
                    cells.append((backend, rank, kind))
                    break
    return cells


def test_fast_subset_covers_every_backend_kind_pair():
    assert len(_fast_cells()) == len(BACKENDS) * len(KINDS)


@pytest.mark.parametrize("backend,rank,kind", _fast_cells(),
                         ids=lambda v: str(v))
def test_conformance(backend, rank, kind):
    check_cell(backend, Problem(SUPPORT_PROBE_EXTENTS[rank], kind, "float"))


# ---------------------------------------------------------------------------
# fast non-pow2 extent classes (tier-1): radix357 + oddshape per backend
# ---------------------------------------------------------------------------
def _class_cells() -> list[tuple[str, str, str]]:
    """For every backend and every non-pow2 extent class it claims support
    for, one cell — kinds rotated with the backend index so real and
    complex paths (and the odd-length full-complex fallback) all run."""
    cells = []
    for bi, backend in enumerate(BACKENDS):
        for ci, (cls, ext) in enumerate(sorted(CLASS_PROBE_EXTENTS.items())):
            for off in range(len(KINDS)):
                kind = KINDS[(bi + ci + off) % len(KINDS)]
                if backend_supports(backend, Problem(ext, kind, "float")):
                    cells.append((backend, cls, kind))
                    break
    return cells


def test_class_cells_cover_the_fused_nonpow2_paths():
    """The new fast paths must claim (and therefore test) their classes:
    the mixed-radix kernel on radix357, the fused chirp on both."""
    covered = {(b, c) for b, c, _ in _class_cells()}
    assert ("stockham_pallas", "radix357") in covered
    assert ("chirpz_pallas", "radix357") in covered
    assert ("chirpz_pallas", "oddshape") in covered
    assert ("bluestein", "oddshape") in covered
    assert ("xla", "oddshape") in covered


@pytest.mark.parametrize("backend,cls,kind", _class_cells(),
                         ids=lambda v: str(v))
def test_conformance_extent_classes(backend, cls, kind):
    check_cell(backend, Problem(CLASS_PROBE_EXTENTS[cls], kind, "float"))


# ---------------------------------------------------------------------------
# full matrix (CI conformance job: CONFORMANCE_FULL=1, slow marker)
# ---------------------------------------------------------------------------
def _full_cells() -> list[tuple[str, tuple, str, str]]:
    """Every supported (backend, extents, kind, precision) cell: the pow2
    probes per rank plus one radix357 and one oddshape probe."""
    rows = list(support_matrix())
    for ext in CLASS_PROBE_EXTENTS.values():
        rows += support_matrix(probes={len(ext): ext})
    return [(r["backend"], r["extents"], r["kind"], r["precision"])
            for r in rows if r["supported"]]


@pytest.mark.slow
def test_conformance_full_matrix():
    if os.environ.get("CONFORMANCE_FULL", "") in ("", "0"):
        pytest.skip("full backend x kind x precision x rank matrix: set "
                    "CONFORMANCE_FULL=1 (the dedicated CI job step runs it)")
    failures = []
    cells = _full_cells()
    for backend, extents, kind, precision in cells:
        problem = Problem(extents, kind, precision)
        try:
            check_cell(backend, problem)
        except Exception as e:  # a raising cell must not abort the sweep:
            # the whole point is the aggregated N/M failure report
            failures.append(f"{backend}/{problem.signature()}: "
                            f"{type(e).__name__}: {e}")
    assert not failures, \
        f"{len(failures)}/{len(cells)} cells failed:\n" + "\n".join(failures)


# ---------------------------------------------------------------------------
# distributed cells: decomposition x kind x rank on a forced 4-device mesh
# ---------------------------------------------------------------------------
def test_conformance_distributed_cells():
    """The distributed extension of the matrix — slab/pencil/dist1d cells
    with planned local engines, natural order, differential + roundtrip.
    Runs in a subprocess: a process's XLA device count is fixed at first
    jax init, and the in-process smoke tests must keep seeing 1 device."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(root, "src")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(root, "tests", "helpers", "dist_fft_check.py"),
         "conformance"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "DISTRIBUTED CONFORMANCE CELLS PASSED" in proc.stdout


# ---------------------------------------------------------------------------
# the support matrix itself is part of the contract
# ---------------------------------------------------------------------------
def test_support_matrix_declares_expected_ranks():
    rows = support_matrix()
    by_backend: dict[str, set] = {}
    for r in rows:
        if r["supported"]:
            by_backend.setdefault(r["backend"], set()).add(r["rank"])
    for backend in BACKENDS:
        want = {2} if backend == "fft2_pallas" else set(RANKS)
        assert by_backend.get(backend, set()) == want, backend


def test_support_matrix_is_kind_and_precision_blind_at_pow2_probes():
    """Real kinds plan through the packed path on any complex backend, so at
    the pow2 probe extents no backend's support may depend on kind or
    precision."""
    rows = support_matrix()
    seen: dict[tuple, set] = {}
    for r in rows:
        seen.setdefault((r["backend"], r["rank"]), set()).add(r["supported"])
    assert all(len(v) == 1 for v in seen.values()), \
        {k: v for k, v in seen.items() if len(v) > 1}


def test_full_matrix_spans_all_dimensions():
    cells = _full_cells()
    assert {c[0] for c in cells} == set(BACKENDS)
    assert {len(c[1]) for c in cells} == set(RANKS)
    assert {c[2] for c in cells} == set(KINDS)
    assert {c[3] for c in cells} == set(PRECISIONS)
    # both non-pow2 class probes contribute supported cells
    exts = {c[1] for c in cells}
    assert set(CLASS_PROBE_EXTENTS.values()) <= exts
