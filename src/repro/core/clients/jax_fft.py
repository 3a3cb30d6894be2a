"""The JAX FFT clients — the in-repo analogue of the paper's fftw/cuFFT/clFFT
client implementations, one per backend engine.

Each backend — its engine, the lengths it takes, its knobs — is a record
in :mod:`repro.core.backends`; one client class per backend pins it the way
a library binary would.  The mixed-radix stockham_pallas kernel covers the
paper's radix357 class (any 2^a*3^b*5^c*7^d length) in a single HBM touch;
chirpz_pallas covers oddshape, so all three Fig. 7 extent classes ride fused
kernels.

Plans are ND-native: a candidate may assign a different backend to every
axis (``Candidate.axes``); separable engines are applied per axis through
``nd.fftn``'s minimal-transpose path, while the whole-transform backends
(xla, fft2_pallas) take the fused route.  Real kinds run the packed
half-spectrum path on top of whichever complex backend the planner picked —
per-axis engines through ``nd.rfftn``, fused ones through
``rfft.rfftn_packed``.

A client owns device buffers + AOT-compiled executables for ONE Problem —
the jit-specialization equivalent of gearshifft's compile-time template
instantiation.  By default init_forward/init_inverse re-lower and re-compile
on every run so planning cost stays an honestly measured quantity (paper
Figs. 4/5); with a PlanCache attached, the first run pays the measured cold
compile and warm repetitions reuse the cached executable, with hit/miss
events surfaced per op for the result rows.
"""

from __future__ import annotations

from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from ..backends import engine
from ..candidates import Candidate
from ..client import Context, FFTClient, Problem
from ..plan import (Plan, PlanCache, PlanRigor, cached_build,
                    executable_bytes, make_plan)
from ..registry import register_client
from ..trace import dispatch_and_sync, executable_name, named, span
from ..wisdom import Wisdom
from repro.fft import nd
from repro.fft import rfft as rfft_mod


def _forward_fn(problem: Problem, cand: Candidate) -> Callable:
    axes = tuple(range(-problem.rank, 0))
    if cand.backend == "xla":
        if problem.complex_input:
            return lambda x: jnp.fft.fftn(x, axes=axes)
        return lambda x: jnp.fft.rfftn(x, axes=axes)
    if cand.backend == "fft2_pallas":
        if problem.rank != 2:   # fail loudly, like every other backend's
            raise ValueError(   # infeasible build — never silent wrong math
                f"fft2_pallas is rank-2 only, got rank {problem.rank}")
        eng2 = engine(cand)
        if problem.complex_input:
            return eng2
        return lambda x: rfft_mod.rfftn_packed(x, eng2, rank=2)
    engines = [engine(c) for c in cand.per_axis(problem.rank)]
    if problem.complex_input:
        return lambda x: nd.fftn(x, engines, axes=axes)
    return lambda x: nd.rfftn(x, engines, axes=axes)


def _inverse_fn(problem: Problem, cand: Candidate) -> Callable:
    axes = tuple(range(-problem.rank, 0))
    if cand.backend == "xla":
        if problem.complex_input:
            return lambda y: jnp.fft.ifftn(y, axes=axes)
        return lambda y: jnp.fft.irfftn(y, s=problem.extents, axes=axes)
    if cand.backend == "fft2_pallas":
        if problem.rank != 2:
            raise ValueError(
                f"fft2_pallas is rank-2 only, got rank {problem.rank}")
        eng2 = engine(cand)
        if problem.complex_input:
            return lambda y: eng2(y, inverse=True)
        return lambda y: rfft_mod.irfftn_packed(y, problem.extents, eng2)
    engines = [engine(c) for c in cand.per_axis(problem.rank)]
    if problem.complex_input:
        return lambda y: nd.fftn(y, engines, axes=axes, inverse=True)
    return lambda y: nd.irfftn(y, problem.extents, engines, axes=axes)


#: Public name for the un-jitted forward builder — the serving engine wraps
#: it with its own jit (donated staging buffer, AOT-compiled per batch
#: bucket) instead of taking build_forward's plain jit.
forward_fn = _forward_fn


def build_forward(problem: Problem, cand: Candidate) -> Callable:
    """jit-compiled forward for planner MEASURE timing."""
    return jax.jit(named(_forward_fn(problem, cand),
                         executable_name(problem, cand, "fwd")))


def build_inverse(problem: Problem, cand: Candidate) -> Callable:
    """jit-compiled inverse (the conformance matrix's roundtrip leg)."""
    return jax.jit(named(_inverse_fn(problem, cand),
                         executable_name(problem, cand, "inv")))


class JaxFFTClient(FFTClient):
    """Generic client; subclasses pin ``backend_filter`` to mimic having one
    binary per library (gearshifft_cufft, gearshifft_fftw, ...)."""

    title = "jaxfft"
    backend_filter: str | None = None   # force one backend, like a library binary
    rigor = PlanRigor.ESTIMATE

    def __init__(self, problem: Problem, context: Context,
                 rigor: PlanRigor | None = None, wisdom: Wisdom | None = None,
                 plan_cache: PlanCache | None = None):
        super().__init__(problem, context)
        if rigor is not None:
            self.rigor = rigor
        self.wisdom = wisdom
        self.plan_cache = plan_cache
        self.cache_events: dict[str, str] = {}
        self.plan: Plan | None = None
        self._buf = None
        self._spec = None
        self._fwd = self._inv = None
        self._fwd_compiled = self._inv_compiled = None
        self._fwd_name = self._inv_name = ""
        self._seq = 0     # ties a transform's dispatch and sync spans
        self._plan_bytes = 0

    # --- memory -----------------------------------------------------------
    def allocate(self) -> None:
        x = jnp.zeros((self.problem.batch, *self.problem.extents),
                      dtype=self.problem.input_dtype.name)
        self._buf = jax.device_put(x)
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
        n_in = self.problem.signal_bytes
        if self.problem.inplace:
            if self.problem.complex_input:
                return n_in
            # FFTW padded in-place r2c layout: the real array's last axis is
            # padded to 2*(n/2+1) reals so the n/2+1 complex half-spectrum
            # bins fit in place — the padding is part of the allocation
            return self._halfspec_bytes()
        # out-of-place: plus the spectrum buffer
        if self.problem.complex_input:
            return 2 * n_in
        return n_in + self._halfspec_bytes()

    def _halfspec_bytes(self) -> int:
        ext = self.problem.extents
        n_out = self.problem.batch
        for v in ext[:-1]:
            n_out *= v
        n_out *= ext[-1] // 2 + 1
        return n_out * self.problem.input_dtype.itemsize * (2 if not self.problem.complex_input else 1)

    def get_plan_size(self) -> int:
        return self._plan_bytes

    # --- planning ---------------------------------------------------------
    def _make_plan(self) -> Plan | None:
        from ..candidates import candidates
        from ..plan import measure_plan
        import time as _time

        build = lambda c: build_forward(self.problem, c)
        if self.backend_filter is None:
            return make_plan(self.problem, self.rigor, build=build,
                             wisdom=self.wisdom)
        # library-pinned client: planner searches only this backend's knobs.
        # Wisdom entries are scoped by the backend so per-library tuning
        # persists without clobbering the open planner's choices.
        t0 = _time.perf_counter()
        measured = self.rigor in (PlanRigor.MEASURE, PlanRigor.PATIENT)
        if (measured or self.rigor is PlanRigor.WISDOM_ONLY) \
                and self.wisdom is not None:
            cand = self.wisdom.lookup(self.problem, scope=self.backend_filter)
            if cand is not None and cand.backend == self.backend_filter:
                return Plan(self.problem, cand, self.rigor,
                            (_time.perf_counter() - t0) * 1e3,
                            source="wisdom")
        if self.rigor is PlanRigor.WISDOM_ONLY:
            return None   # fftw NULL plan: no persisted selection, no sweep
        cands = [c for c in candidates(self.problem,
                                       patient=(self.rigor is PlanRigor.PATIENT))
                 if c.backend == self.backend_filter] or [Candidate(self.backend_filter)]
        if measured and len(cands) > 1:
            cand, timings = measure_plan(self.problem, build, cands)
            if self.wisdom is not None:   # persist the tuned knobs
                self.wisdom.record(
                    self.problem, cand, scope=self.backend_filter,
                    measured_ms=timings.get(cand.key()),
                    rigor=self.rigor.value)
        else:
            cand, timings = cands[0], {}
        return Plan(self.problem, cand, self.rigor,
                    (_time.perf_counter() - t0) * 1e3, timings,
                    source=self.rigor.value if timings else "estimate")

    def _select(self) -> Candidate | None:
        if self.plan_cache is not None:
            # memoized selection: MEASURE/PATIENT candidate sweeps (which
            # compile every candidate) run at most once per problem
            pkey = PlanCache.plan_key(self._device_kind(), self.problem,
                                      self.rigor, scope=self.backend_filter or "*")
            plan, _ = self.plan_cache.plan(pkey, self._make_plan)
        else:
            plan = self._make_plan()
        if plan is None:
            return None
        self.plan = plan
        return plan.candidate

    def _device_kind(self) -> str:
        return getattr(self.context, "device_kind", "?")

    @property
    def plan_source(self) -> str:
        """Where this client's plan came from (``Plan.source``) — surfaced
        as the result rows' ``plan_source`` column when wisdom is attached,
        so exact-``wisdom`` hits, interpolated ``wisdom_near`` warm starts,
        and real sweeps stay distinguishable downstream."""
        return self.plan.source if self.plan is not None else ""

    def init_forward(self) -> None:
        with span("fft.plan"):
            cand = self._select()
        if cand is None:
            raise RuntimeError("NULL plan (wisdom miss)")  # fftw semantics
        name = self._fwd_name = executable_name(self.problem, cand, "fwd")

        def build():
            donate = (0,) if self.problem.inplace else ()
            fn = jax.jit(named(_forward_fn(self.problem, cand), name),
                         donate_argnums=donate)
            lowered = fn.lower(jax.ShapeDtypeStruct(self._buf.shape, self._buf.dtype))
            return lowered.compile()

        with span("fft.build", exe=name):
            self._fwd_compiled = cached_build(
                self.plan_cache, self.cache_events, "init_forward",
                PlanCache.executable_key(self._device_kind(), self.problem,
                                         cand, "forward"), build)
        self._plan_bytes = executable_bytes(self._fwd_compiled)

    def init_inverse(self) -> None:
        cand = self.plan.candidate
        name = self._inv_name = executable_name(self.problem, cand, "inv")

        def build():
            donate = (0,) if self.problem.inplace else ()
            fn = jax.jit(named(_inverse_fn(self.problem, cand), name),
                         donate_argnums=donate)
            spec_shape = jax.eval_shape(_forward_fn(self.problem, cand),
                                        jax.ShapeDtypeStruct((self.problem.batch, *self.problem.extents),
                                                             self.problem.input_dtype.name))
            return fn.lower(spec_shape).compile()

        with span("fft.build", exe=name):
            self._inv_compiled = cached_build(
                self.plan_cache, self.cache_events, "init_inverse",
                PlanCache.executable_key(self._device_kind(), self.problem,
                                         cand, "inverse"), build)
        self._plan_bytes += executable_bytes(self._inv_compiled)

    # --- execution --------------------------------------------------------
    def execute_forward(self) -> None:
        self._seq += 1
        self._spec = dispatch_and_sync(self._fwd_name, self._seq,
                                       self._fwd_compiled, self._buf)
        if self.problem.inplace:
            self._buf = None  # donated

    def execute_inverse(self) -> None:
        self._seq += 1
        self._buf = dispatch_and_sync(self._inv_name, self._seq,
                                      self._inv_compiled, self._spec)
        if self.problem.inplace:
            self._spec = None

    # --- transfer ---------------------------------------------------------
    def upload(self, host_data: np.ndarray) -> None:
        self._buf = jax.device_put(jnp.asarray(host_data))
        self._buf.block_until_ready()

    def download(self) -> np.ndarray:
        return np.asarray(self._buf)


# --- one "binary" per library, as in the paper ------------------------------
@register_client()
class XlaFFTClient(JaxFFTClient):
    title = "XlaFFT"
    backend_filter = "xla"


@register_client()
class StockhamClient(JaxFFTClient):
    title = "Stockham"
    backend_filter = "stockham"


@register_client()
class FourStepClient(JaxFFTClient):
    title = "FourStep"
    backend_filter = "fourstep"


@register_client()
class FourStepPallasClient(JaxFFTClient):
    title = "FourStepPallas"
    backend_filter = "fourstep_pallas"


@register_client()
class StockhamPallasClient(JaxFFTClient):
    title = "StockhamPallas"
    backend_filter = "stockham_pallas"


@register_client()
class SixStepClient(JaxFFTClient):
    title = "SixStep"
    backend_filter = "sixstep"


@register_client()
class Fft2PallasClient(JaxFFTClient):
    title = "Fft2Pallas"
    backend_filter = "fft2_pallas"


@register_client()
class ChirpZPallasClient(JaxFFTClient):
    title = "ChirpZPallas"
    backend_filter = "chirpz_pallas"


@register_client()
class BluesteinClient(JaxFFTClient):
    title = "Bluestein"
    backend_filter = "bluestein"


@register_client()
class PlannedClient(JaxFFTClient):
    """Planner-driven client (rigor decides the backend), fftw-style."""
    title = "Planned"
    backend_filter = None
