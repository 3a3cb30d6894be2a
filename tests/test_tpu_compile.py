"""Compile the planned FFT path for a TPU v5e that is described, not attached.

The TPU's compiler is installed with jaxlib and compiles for a topology
that ``jax.experimental.topologies`` describes, so what Mosaic or XLA would
refuse on the chip fails here, at real sizes, without a chip.  Nothing
runs: these tests say nothing about results or times.

The program decides interpret mode and backend feasibility from
:func:`repro.core.device.platform`; each test that builds through the
program steers it to ``"tpu"``, so the Pallas engines lower through Mosaic
and the planner withdraws what the TPU cannot run.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

import repro.core.device as device
from repro.core.backends import TABLE
from repro.core.candidates import Candidate, candidates
from repro.core.client import Problem
from repro.core.clients.dist_fft import dist_engines
from repro.core.clients.jax_fft import forward_fn
from repro.core.costmodel import estimate_choice
from repro.core.plan import fallback_chain
from repro.fft import distributed as dist
from repro.roofline.hlo_parse import count_source_collectives


TPU_WITHDRAWN = frozenset(b for b, rec in TABLE.items() if rec.tpu_withdrawn)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the program's platform decision to the TPU, and trace as the
    program runs there: without x64 (the suite's conftest turns it on for
    its f64 oracles; the TPU compiler aborts on f64 work)."""
    monkeypatch.setattr(device, "platform", lambda: "tpu")
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", x64)


def _compile_forward(problem: Problem, cand: Candidate, sharding):
    spec = jax.ShapeDtypeStruct((problem.batch, *problem.extents),
                                problem.input_dtype.name, sharding=sharding)
    return jax.jit(forward_fn(problem, cand)).lower(spec).compile()


def _backends(cand: Candidate) -> set[str]:
    return {a.backend for a in cand.axes} | {cand.backend}


@pytest.mark.parametrize("extents,batch", [
    ((1 << 20,), 64), ((4096, 4096), 4), ((256, 256, 256), 2),
], ids=["1d", "2d", "3d"])
def test_xla_forward_compiles(one_chip, on_tpu, extents, batch):
    problem = Problem(extents, "Outplace_Complex", "float", batch=batch)
    _compile_forward(problem, Candidate("xla"), one_chip)


def test_xla_rejects_c128_on_tpu(one_chip):
    """Why double is infeasible on the TPU: XLA's TPU FFT refuses c128."""
    problem = Problem((4096,), "Outplace_Complex", "double", batch=4)
    with pytest.raises(Exception, match="FFT"):
        _compile_forward(problem, Candidate("xla"), one_chip)


@pytest.mark.parametrize("kind,extents,batch,backend", [
    ("Outplace_Complex", (128,), 262144, "dft"),
    ("Outplace_Complex", (12,), 4, "dft"),
    ("Outplace_Complex", (16384,), 64, "fourstep_pallas"),    # 128 x 128
    ("Outplace_Complex", (4096,), 16384, "fourstep_pallas"),  # 64 x 64
    ("Outplace_Complex", (3072,), 4, "fourstep_pallas"),      # 48 x 64
    ("Outplace_Real", (18432,), 4096, "fourstep_pallas"),     # packed 96 x 96
    ("Outplace_Complex", (361, 361, 361), 1, "dft"),          # 361 lanes
    ("Outplace_Real", (361, 361), 384, "dft"),
    ("Outplace_Complex", (512,), 65536, "dft"),               # 128-row tile
])
def test_pallas_kernel_compiles(one_chip, on_tpu, kind, extents, batch,
                                backend):
    """The Pallas engines the planner offers on the TPU lower through
    Mosaic (a ``tpu_custom_call`` in the compiled module), fourstep_pallas
    at factors below the 128-lane tile included."""
    problem = Problem(extents, kind, "float", batch=batch)
    compiled = _compile_forward(problem, Candidate(backend), one_chip)
    assert "tpu_custom_call" in compiled.as_text()


#: chip_smoke.SUITE problems whose picks the xla tests above do not
#: already cover (6859, an XLA chirp, takes ~40 s to compile and is left
#: to the chip run).
PLANNED = [((4096,), 16384), ((256, 256), 1024), ((18432,), 4096),
           ((128,), 262144)]


@pytest.mark.parametrize("extents,batch", PLANNED,
                         ids=["x".join(map(str, e)) for e, _ in PLANNED])
def test_planned_suite_problem_compiles(one_chip, on_tpu, extents, batch):
    """ESTIMATE's pick for chip-smoke problems, both kinds, compiles for
    the chip — and is never a withdrawn backend."""
    for kind in ("Outplace_Complex", "Outplace_Real"):
        problem = Problem(extents, kind, "float", batch=batch)
        cand = estimate_choice(problem)
        assert not _backends(cand) & TPU_WITHDRAWN, cand.key()
        _compile_forward(problem, cand, one_chip)


def test_withdrawn_backends_never_offered_on_tpu(monkeypatch):
    """On the TPU neither the candidate space nor any fallback chain holds
    a withdrawn kernel, and double precision has no backend at all (so
    planning refuses it instead of compiling what the TPU compiler rejects
    or aborts on); off it the same problems still offer them (the rules
    are the platform's, not the problems')."""
    problems = [Problem(e, k, p, batch=4)
                for e in ((1024,), (18432,), (6859,), (256, 256),
                          (4096,), (64, 64, 64), (1 << 20,))
                for k in ("Outplace_Complex", "Outplace_Real")
                for p in ("float", "double")]

    def offered(problem):
        out = set()
        for c in (candidates(problem, patient=True)
                  + fallback_chain(problem, patient=True)):
            out |= _backends(c)
        return out

    off_tpu = set().union(*(offered(p) for p in problems))
    assert TPU_WITHDRAWN <= off_tpu
    monkeypatch.setattr(device, "platform", lambda: "tpu")
    for problem in problems:
        if problem.precision == "double":
            for enumerate_ in (candidates, fallback_chain, estimate_choice):
                with pytest.raises(ValueError, match="no backend"):
                    enumerate_(problem)
            continue
        got = offered(problem)
        assert not got & TPU_WITHDRAWN, (problem.signature(), got)
        assert "xla" in got


def test_pallas_interpret_refused_on_tpu(on_tpu):
    with pytest.raises(ValueError, match="interpret"):
        device.interpret_mode(True)
    assert device.interpret_mode() is False


@pytest.mark.parametrize("backend,mesh_shape,a2a", [
    ("slab", (4,), 1), ("pencil", (2, 2), 2)])
def test_dist_decomposition_compiles_on_four_chips(topo, on_tpu, backend,
                                                   mesh_shape, a2a):
    """The four-chip phase of chip_smoke.py at full size: 512^3 c64 over a
    mesh of the four described chips, its local engines as planned on the
    TPU, with the expected all-to-alls in the compiled module."""
    shape = (512, 512, 512)
    problem = Problem(shape, "Outplace_Complex", "float", batch=1)
    cand = Candidate(backend, mesh=mesh_shape)
    names = ("d0", "d1")[:len(mesh_shape)]
    mesh = Mesh(np.array(topo.devices[:4]).reshape(mesh_shape), names)
    engines = dist_engines(problem, cand)
    if backend == "slab":
        fn, in_spec, _ = dist.make_slab_fftnd(mesh, "d0", shape,
                                              engines=engines)
    else:
        fn, in_spec, _ = dist.make_pencil_fftnd(mesh, "d0", "d1", shape,
                                                engines=engines)
    spec = jax.ShapeDtypeStruct((1, *shape), jnp.complex64,
                                sharding=NamedSharding(mesh, in_spec))
    text = fn.lower(spec).compile().as_text()
    # the TPU compiler splits each c64 all-to-all into one per f32 plane
    assert count_source_collectives(text) == a2a
    assert text.count(" all-to-all(") == 2 * a2a
