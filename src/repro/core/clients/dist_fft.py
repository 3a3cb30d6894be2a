"""Distributed-FFT clients: the mesh-parallel transforms
(repro.fft.distributed) driven through the SAME Table-1 timed path as the
single-device libraries — the FFTW-MPI / cuFFTMp "binaries" of the suite.

``DistFFT1D`` runs the distributed four-step; ``DistFFTND`` runs the
planned slab/pencil decompositions, selecting among them (and their local
per-axis engines) with the interconnect-aware cost model in ``costmodel.py``.

Forward transforms emit the FFTW_MPI_TRANSPOSED_OUT spectrum layout and the
inverse consumes it directly (TRANSPOSED_IN), so the measured round trip is
the production layout-aware path with no reordering pass; pass the context
option ``dist_natural=True`` to buy natural-order spectra for one extra
all_to_all per direction instead.  On a single-device host the mesh
degenerates to P=1 and the collectives are identity — the same code path
the pod runs.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..backends import engine
from ..candidates import Candidate, dist_local_lengths, dist_supports
from ..client import Context, FFTClient, Problem
from ..costmodel import dist_local_engine, estimate_bytes_moved
from ..plan import (Plan, PlanCache, PlanRigor, cached_build,
                    executable_bytes)
from ..registry import register_client
from ..trace import dispatch_and_sync, span
from ..wisdom import Wisdom
from repro.fft import distributed as dist
from repro.launch.mesh import flat_mesh, get_active_mesh, reshaped_mesh


def dist_engines(problem: Problem, cand: Candidate) -> list:
    """One local engine per sub-transform of a distributed candidate: the
    ``local`` knob when the sweep forced one, else the cost model's best
    separable backend at each local length — resolved to callables through
    the same backend table every single-device plan uses."""
    forced = cand.opts().get("local")
    out = []
    for n, _ in dist_local_lengths(problem, cand):
        b = forced or dist_local_engine(n, problem.precision)
        out.append(engine(Candidate(b)))
    return out


@register_client()
class DistFFT1DClient(FFTClient):
    """1D distributed four-step FFT over all visible devices.

    Constraints (recorded as node failures, not suite aborts): rank-1
    complex transforms, batch 1, and n must factor as n1*n2 with the device
    count dividing n1.
    """

    title = "DistFFT1D"

    def __init__(self, problem: Problem, context: Context,
                 rigor: PlanRigor | None = None, wisdom: Wisdom | None = None,
                 plan_cache: PlanCache | None = None):
        super().__init__(problem, context)
        if problem.rank != 1:
            raise ValueError("DistFFT1D supports rank-1 transforms only")
        if not problem.complex_input:
            raise ValueError("DistFFT1D supports complex kinds only")
        if problem.batch != 1:
            raise ValueError("DistFFT1D supports batch=1 only")
        self.plan_cache = plan_cache
        self.cache_events: dict[str, str] = {}
        self._n = problem.extents[0]
        # natural-order spectra (one extra all_to_all per direction) —
        # both directions honor it, so round trips stay layout-consistent
        self._natural = bool(context.options.get("dist_natural", False))
        self._mesh = None
        self._sharding = None
        self._buf = None
        self._spec = None
        self._local = None   # the local engines, once planned
        self._fwd_compiled = self._inv_compiled = None
        self._fwd_name = self._inv_name = ""
        self._seq = 0     # ties a transform's dispatch and sync spans
        self._plan_bytes = 0

    # --- memory -----------------------------------------------------------
    def allocate(self) -> None:
        devices = jax.devices()
        self._mesh = Mesh(np.array(devices), ("data",))
        self._sharding = NamedSharding(self._mesh, P("data"))
        x = jnp.zeros((self._n,), dtype=self.problem.input_dtype.name)
        self._buf = jax.device_put(x, self._sharding)
        self._buf.block_until_ready()

    def destroy(self) -> None:
        for b in (self._buf, self._spec):
            if b is not None:
                try:
                    b.delete()
                except Exception:
                    pass
        self._buf = self._spec = None
        self._fwd_compiled = self._inv_compiled = None

    def get_alloc_size(self) -> int:
        return 2 * self.problem.signal_bytes   # signal + spectrum buffers

    def get_plan_size(self) -> int:
        return self._plan_bytes

    # --- planning ---------------------------------------------------------
    def _n_devices(self) -> int:
        return len(jax.devices())

    def _name(self, direction: str) -> str:
        return dist.executable_name("dist1d", (self._n_devices(),),
                                    (self._n,), direction == "inverse",
                                    self._natural)

    def _compile(self, direction: str, name: str, build):
        nat = ",natural" if self._natural else ""
        key = PlanCache.executable_key(
            getattr(self.context, "device_kind", "?"), self.problem,
            f"dist_fourstep[p={self._n_devices()}{nat}]", direction)
        with span("fft.build", exe=name):
            return cached_build(self.plan_cache, self.cache_events,
                                f"init_{direction}", key, build)

    def _engines(self):
        """The local engines of the four-step's two passes, chosen once."""
        if self._local is None:
            with span("fft.plan"):
                cand = Candidate("dist1d", mesh=(self._n_devices(),))
                self._local = dist_engines(self.problem, cand)
        return self._local

    def init_forward(self) -> None:
        engines = self._engines()

        def build():
            fn, _ = dist.make_fft1d(self._mesh, "data", self._n,
                                    natural=self._natural, engines=engines)
            return fn.lower(self._buf).compile()

        self._fwd_name = self._name("forward")
        self._fwd_compiled = self._compile("forward", self._fwd_name, build)
        self._plan_bytes = executable_bytes(self._fwd_compiled)

    def init_inverse(self) -> None:
        engines = self._engines()

        def build():
            fn, _ = dist.make_ifft1d(self._mesh, "data", self._n,
                                     natural=self._natural, engines=engines)
            # the spectrum has the signal's shape/dtype/sharding
            return fn.lower(self._spec if self._spec is not None
                            else self._buf).compile()

        self._inv_name = self._name("inverse")
        self._inv_compiled = self._compile("inverse", self._inv_name, build)
        self._plan_bytes += executable_bytes(self._inv_compiled)

    # --- execution --------------------------------------------------------
    def execute_forward(self) -> None:
        self._seq += 1
        self._spec = dispatch_and_sync(self._fwd_name, self._seq,
                                       self._fwd_compiled, self._buf)

    def execute_inverse(self) -> None:
        self._seq += 1
        self._buf = dispatch_and_sync(self._inv_name, self._seq,
                                      self._inv_compiled, self._spec)

    # --- transfer ---------------------------------------------------------
    def upload(self, host_data: np.ndarray) -> None:
        flat = jnp.asarray(np.asarray(host_data).reshape(-1))
        self._buf = jax.device_put(flat, self._sharding)
        self._buf.block_until_ready()

    def download(self) -> np.ndarray:
        return np.asarray(self._buf)


@register_client()
class DistFFTNDClient(FFTClient):
    """Planned mesh-parallel ND FFT: slab or pencil decomposition.

    The planner side of the tentpole: candidates come from the distributed
    cost model (``plan.estimate_bytes_moved`` with the interconnect term)
    over the active mesh — or a flat mesh over every visible device when
    none is installed — and MEASURE/PATIENT time the decomposition x
    local-engine space, persisting winners to wisdom under the ``dist``
    scope with their mesh shape.  Constraints: rank-2/3 complex kinds whose
    extents satisfy the decomposition divisibility rules.
    """

    title = "DistFFTND"
    rigor = PlanRigor.ESTIMATE

    def __init__(self, problem: Problem, context: Context,
                 rigor: PlanRigor | None = None, wisdom: Wisdom | None = None,
                 plan_cache: PlanCache | None = None):
        super().__init__(problem, context)
        if problem.rank not in (2, 3):
            raise ValueError("DistFFTND supports rank-2/3 transforms only")
        if not problem.complex_input:
            raise ValueError("DistFFTND supports complex kinds only")
        if rigor is not None:
            self.rigor = rigor
        self.wisdom = wisdom
        self.plan_cache = plan_cache
        self.cache_events: dict[str, str] = {}
        self._natural = bool(context.options.get("dist_natural", False))
        self._forced = context.options.get("dist_backend")  # 'slab'|'pencil'
        self.plan: Plan | None = None
        self._base_mesh = None
        self._mesh = None
        self._in_sharding = None
        self._buf = None
        self._spec = None
        self._fwd_compiled = self._inv_compiled = None
        self._fwd_name = self._inv_name = ""
        self._seq = 0     # ties a transform's dispatch and sync spans
        self._plan_bytes = 0

    # --- planning ---------------------------------------------------------
    def _candidates(self) -> list[Candidate]:
        from ..candidates import _dist_candidates

        if self._base_mesh.size < 2:
            # degenerate P=1 mesh: the collectives are identity, the same
            # code path the pod runs — how tier-1 tests cover this client
            return [Candidate("slab", mesh=(1,))]
        patient = self.rigor is PlanRigor.PATIENT
        cands = [c for c in _dist_candidates(self.problem, self._base_mesh,
                                             patient)
                 if c.backend in ("slab", "pencil")]
        if self._forced:
            cands = [c for c in cands if c.backend == self._forced]
        if not cands:
            raise ValueError(
                f"no feasible slab/pencil decomposition of "
                f"{self.problem.extents} over {self._base_mesh.size} devices")
        return cands

    def _make_plan(self) -> Plan:
        import time as _time

        t0 = _time.perf_counter()
        measured = self.rigor in (PlanRigor.MEASURE, PlanRigor.PATIENT)
        if self.wisdom is not None and \
                self.rigor is not PlanRigor.ESTIMATE:
            cand = self.wisdom.lookup(self.problem, scope="dist")
            if cand is not None and cand.backend in ("slab", "pencil") \
                    and dist_supports(cand.backend, self.problem, cand.mesh) \
                    and _mesh_total(cand.mesh) == self._base_mesh.size:
                return Plan(self.problem, cand, self.rigor,
                            (_time.perf_counter() - t0) * 1e3)
        if self.rigor is PlanRigor.WISDOM_ONLY:
            raise RuntimeError("NULL plan (wisdom miss)")
        cands = self._candidates()
        timings: dict[str, float] = {}
        if measured and len(cands) > 1:
            from ..plan import measure_plan

            def build(c):
                fn, mesh, in_spec, _ = self._build_fn(c, "forward")
                sh = NamedSharding(mesh, in_spec)
                return lambda x: fn(jax.device_put(x, sh))

            cand, timings = measure_plan(self.problem, build, cands)
            if self.wisdom is not None:
                self.wisdom.record(self.problem, cand, scope="dist")
        else:
            cand = min(cands,
                       key=lambda c: estimate_bytes_moved(self.problem, c))
        return Plan(self.problem, cand, self.rigor,
                    (_time.perf_counter() - t0) * 1e3, timings)

    def _select(self) -> Candidate:
        if self.plan is not None:
            return self.plan.candidate
        with span("fft.plan"):
            if self.plan_cache is not None:
                pkey = PlanCache.plan_key(
                    getattr(self.context, "device_kind", "?"), self.problem,
                    self.rigor, scope=f"dist[{self._base_mesh.size}]")
                plan, _ = self.plan_cache.plan(pkey, self._make_plan)
            else:
                plan = self._make_plan()
        self.plan = plan
        return plan.candidate

    def _build_fn(self, cand: Candidate, direction: str):
        """The jit-able sharded transform for one candidate (used both by
        the MEASURE sweep and the final executable build)."""
        mesh = reshaped_mesh(self._base_mesh, cand.mesh)
        engines = dist_engines(self.problem, cand)
        inverse = direction == "inverse"
        if cand.backend == "slab":
            fn, in_spec, out_spec = dist.make_slab_fftnd(
                mesh, "d0", self.problem.extents, inverse=inverse,
                natural=self._natural, engines=engines)
        else:
            fn, in_spec, out_spec = dist.make_pencil_fftnd(
                mesh, "d0", "d1", self.problem.extents, inverse=inverse,
                natural=self._natural, engines=engines)
        return fn, mesh, in_spec, out_spec

    # --- memory -----------------------------------------------------------
    def allocate(self) -> None:
        active = get_active_mesh()
        self._base_mesh = active if active is not None else flat_mesh()
        cand = self._select()
        fn, mesh, in_spec, out_spec = self._build_fn(cand, "forward")
        self._mesh = mesh
        self._in_sharding = NamedSharding(mesh, in_spec)
        x = jnp.zeros((self.problem.batch, *self.problem.extents),
                      dtype=self.problem.input_dtype.name)
        self._buf = jax.device_put(x, self._in_sharding)
        self._buf.block_until_ready()

    def destroy(self) -> None:
        for b in (self._buf, self._spec):
            if b is not None:
                try:
                    b.delete()
                except Exception:
                    pass
        self._buf = self._spec = None
        self._fwd_compiled = self._inv_compiled = None

    def get_alloc_size(self) -> int:
        return 2 * self.problem.signal_bytes   # signal + spectrum buffers

    def get_plan_size(self) -> int:
        return self._plan_bytes

    # --- compile ----------------------------------------------------------
    def _name(self, direction: str) -> str:
        cand = self.plan.candidate
        return dist.executable_name(cand.backend, cand.mesh,
                                    self.problem.extents,
                                    direction == "inverse", self._natural)

    def _compile(self, direction: str, name: str, build):
        nat = ",natural" if self._natural else ""
        cand = self.plan.candidate
        key = PlanCache.executable_key(
            getattr(self.context, "device_kind", "?"), self.problem,
            f"{cand.key()}{nat}", direction)
        with span("fft.build", exe=name):
            return cached_build(self.plan_cache, self.cache_events,
                                f"init_{direction}", key, build)

    def init_forward(self) -> None:
        cand = self._select()

        def build():
            fn, _, _, _ = self._build_fn(cand, "forward")
            return fn.lower(self._buf).compile()

        self._fwd_name = self._name("forward")
        self._fwd_compiled = self._compile("forward", self._fwd_name, build)
        self._plan_bytes = executable_bytes(self._fwd_compiled)

    def init_inverse(self) -> None:
        cand = self.plan.candidate

        def build():
            fwd, mesh, _, out_spec = self._build_fn(cand, "forward")
            inv, _, in_spec, _ = self._build_fn(cand, "inverse")
            spec_shape = jax.ShapeDtypeStruct(
                (self.problem.batch, *self.problem.extents),
                self.problem.input_dtype.name,
                sharding=NamedSharding(mesh, out_spec))
            return inv.lower(spec_shape).compile()

        self._inv_name = self._name("inverse")
        self._inv_compiled = self._compile("inverse", self._inv_name, build)
        self._plan_bytes += executable_bytes(self._inv_compiled)

    # --- execution --------------------------------------------------------
    def execute_forward(self) -> None:
        self._seq += 1
        self._spec = dispatch_and_sync(self._fwd_name, self._seq,
                                       self._fwd_compiled, self._buf)

    def execute_inverse(self) -> None:
        self._seq += 1
        self._buf = dispatch_and_sync(self._inv_name, self._seq,
                                      self._inv_compiled, self._spec)

    # --- transfer ---------------------------------------------------------
    def upload(self, host_data: np.ndarray) -> None:
        x = jnp.asarray(np.asarray(host_data).reshape(
            (self.problem.batch, *self.problem.extents)))
        self._buf = jax.device_put(x, self._in_sharding)
        self._buf.block_until_ready()

    def download(self) -> np.ndarray:
        return np.asarray(self._buf)


def _mesh_total(shape) -> int:
    out = 1
    for s in shape:
        out *= s
    return out
