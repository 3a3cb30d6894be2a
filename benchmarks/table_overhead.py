"""Paper Fig. 2: framework measurement overhead.

Compares the gearshifft-framework-measured round-trip time against a
standalone single-timer loop over the same compiled executables
(standalone-tts) for two signal sizes. Paper claim: overhead < 2%,
shrinking with size.
"""

from __future__ import annotations

import time
from dataclasses import replace

import numpy as np
import jax

from repro.core.benchmark import make_input
from repro.core.client import Problem
from repro.core.clients.jax_fft import _forward_fn, _inverse_fn
from repro.core.candidates import Candidate
from repro.core.suite import SuiteSpec
from .common import emit, run_suite

SPEC = SuiteSpec(clients=("XlaFFT",), kinds=("Inplace_Real",),
                 precisions=("float",), warmups=2, plan_cache=False,
                 output=None)


def _standalone_tts(problem: Problem, reps: int) -> float:
    """One timer around the whole round trip (paper's standalone-tts)."""
    cand = Candidate("xla")
    fwd = jax.jit(_forward_fn(problem, cand))
    inv = jax.jit(_inverse_fn(problem, cand))
    x = jax.device_put(make_input(problem, 0))
    jax.block_until_ready(inv(fwd(x)))  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        y = inv(fwd(jax.device_put(np.asarray(x))))
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / reps * 1e6


def run(reps: int = 5) -> None:
    for ext in [(32, 32, 32), (64, 64, 64)]:
        name = "x".join(map(str, ext))
        results = run_suite(replace(SPEC, extents=(name,), repetitions=reps))
        # framework view: sum of measured per-op times (upload..download)
        per_run: dict[int, float] = {}
        for op in ("upload", "execute_forward", "execute_inverse", "download"):
            for r in results.query(op=op):
                per_run[r.run] = per_run.get(r.run, 0.0) + r.time_ms
        fw_us = 1e3 * np.mean(list(per_run.values()))
        sa_us = _standalone_tts(Problem(ext, "Inplace_Real", "float"), reps)
        emit(f"overhead/framework/{name}", fw_us, "per-op timers")
        emit(f"overhead/standalone_tts/{name}", sa_us, "single timer")
        emit(f"overhead/ratio/{name}", fw_us / sa_us * 100, "percent")
