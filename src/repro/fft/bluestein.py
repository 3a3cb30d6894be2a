"""Bluestein chirp-Z FFT for arbitrary (incl. large-prime) lengths.

The paper's "oddshape" extents (e.g. powers of 19) hit this path in fftw/cuFFT;
we implement it on top of our power-of-two engines so every extent class from
the paper's Fig. 7 is representable.

Identity: with jk = (j^2 + k^2 - (k-j)^2) / 2,

    X[k] = c[k] * sum_j (x[j] c[j]) * conj(c)[k - j],   c[j] = e^{-i pi j^2 / n}

i.e. a linear convolution of a[j] = x[j] c[j] with b[j] = conj(c)[j], which we
evaluate circularly at any padded size m >= 2n - 1: next_pow2(2n - 1) for
the pow2-only engines, the (often much closer) smallest 7-SMOOTH m for the
mixed-radix Pallas kernel — e.g. n = 18432 convolves at 36864 instead of
65536, nearly halving the padded work.

Engine selection (the planner's ``chirpz_pallas`` backend vs the staged
``bluestein`` baseline): the two per-call padded pow2 transforms run through
a selectable engine — the fused in-VMEM ``stockham_pallas`` kernel, the
``sixstep`` composition for padded lengths past the VMEM tile budget, or the
staged pure-jnp ``stockham`` engine.  ``engine="auto"`` picks by padded
length on hardware and the jnp engine in interpret mode.

Host-side setup is cached, not recomputed per call: the chirp c and the
padded filter spectrum FFT(b) depend only on (n, dtype, direction), so they
are built once in numpy float64 — the filter via an exact host DFT, making
the third internal transform of the classical formulation disappear from
the per-call path entirely — and memoized (mirroring the twiddle-pack
pattern in ``kernels/stockham_pallas/ops.py``).

Numerical care: j^2 / n is reduced mod 2 in *integer* arithmetic (pi j^2 / n
has period 2n in j^2) before the float conversion, so chirp phases stay
accurate for n in the millions even in float32.  Real inputs promote to the
complex dtype of matching width — float32 -> complex64, float64 ->
complex128 — so double-precision data never silently loses precision.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core.device import interpret_mode
from repro.core.extents import next_pow2 as _next_pow2, next_smooth

from . import stockham
from .reference import _canonical

#: Padded-length thresholds for ``engine="auto"``: the fused single-kernel
#: Stockham path up to its useful VMEM batch-tile budget, the six-step
#: composition beyond; past the six-step cap no fused engine applies.
PALLAS_SINGLE_MAX_M = 1 << 15
SIXSTEP_MAX_M = 1 << 24

#: Engines the ``engine`` knob accepts ("auto" resolves by padded length).
ENGINES = ("auto", "stockham", "stockham_pallas", "sixstep")

#: (n, m, dtype name, inverse) -> (chirp, padded filter spectrum) HOST pair.
#: Bounded: a near-cap c128 entry is ~400 MB of host arrays, so a long
#: oddshape sweep must evict (insertion order — oldest problems first)
#: instead of growing host RSS for the process lifetime.
_TABLES: dict = {}
_TABLES_MAX = 32


def resolve_engine(n: int, engine: str = "auto",
                   interpret: bool | None = None) -> tuple[str, int]:
    """Resolve the ``engine`` knob and the padded length m >= 2n - 1 it
    convolves at.  The mixed-radix kernel accepts any 7-smooth m, so it
    pads far tighter than the pow2-only engines.  ``interpret`` follows
    :func:`repro.core.device.interpret_mode` (``None``: off the TPU).  In
    interpret mode (off-TPU conformance runs) "auto" keeps the staged jnp
    engine, where the Pallas interpreter would be pure overhead; on
    hardware it takes the fused kernels and never falls back to the jnp
    engine — a padded length past the six-step cap raises.  An EXPLICIT
    engine choice forces that engine anywhere."""
    lo = 2 * n - 1
    if engine == "auto":
        if interpret_mode(interpret):
            engine = "stockham"
        elif next_smooth(lo) <= PALLAS_SINGLE_MAX_M:
            engine = "stockham_pallas"
        elif _next_pow2(lo) <= SIXSTEP_MAX_M:
            engine = "sixstep"
        else:
            raise ValueError(
                f"chirp-Z of n={n} pads to {_next_pow2(lo)}, past the fused "
                f"engines' cap {SIXSTEP_MAX_M}")
    if engine not in ENGINES:
        raise ValueError(f"chirp engine must be one of {ENGINES}, "
                         f"got {engine!r}")
    m = next_smooth(lo) if engine == "stockham_pallas" else _next_pow2(lo)
    return engine, m


def _complex_dtype(dtype) -> jnp.dtype:
    """f32 -> c64, f64 -> c128; complex dtypes pass through (the dtype
    mapping bugfix: real float64 input used to downcast to complex64)."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.complexfloating):
        return dtype
    wide = jnp.complex128 if dtype == jnp.float64 else jnp.complex64
    return jnp.dtype(_canonical(wide))


def _build_tables(n: int, m: int, dtype, inverse: bool):
    """Host-side float64 chirp + padded filter spectrum (exact numpy DFT)."""
    j = np.arange(n, dtype=np.int64)
    jsq_mod = (j * j) % (2 * n)  # exact integer reduction
    ang = np.pi * jsq_mod.astype(np.float64) / n
    c = np.exp((1j if inverse else -1j) * ang)
    # b[j] = conj(c)[|j|] placed circularly: b[0..n-1] and b[m-n+1..m-1]
    bc = np.conj(c)
    b = np.zeros(m, dtype=np.complex128)
    b[:n] = bc
    b[m - n + 1:] = bc[1:][::-1]
    fb = np.fft.fft(b)
    dt = np.dtype(jnp.dtype(dtype).name)
    return c.astype(dt), fb.astype(dt)


def chirp_tables(n: int, m: int, dtype, inverse: bool = False):
    """The (chirp, filter spectrum) pair for one (n, m, dtype, direction),
    memoized so repeated un-jitted calls do no host trig work.  The cache
    holds HOST numpy arrays — never traced values, so a table built while
    tracing one jit can safely serve every later call — and jnp folds them
    in as constants at the use site."""
    key = (n, m, jnp.dtype(dtype).name, bool(inverse))
    out = _TABLES.get(key)
    if out is None:
        while len(_TABLES) >= _TABLES_MAX:
            _TABLES.pop(next(iter(_TABLES)))
        out = _TABLES[key] = _build_tables(n, m, dtype, inverse)
    return out


def _padded_engine(engine: str, tile_b, interpret: bool | None):
    """cfft(x, inverse=False) used for the two padded length-m transforms
    (``engine`` already resolved by :func:`resolve_engine`)."""
    if engine == "stockham":
        return stockham.fft
    if engine == "stockham_pallas":
        from repro.kernels.stockham_pallas import ops as sp_ops
        return lambda v, inverse=False: sp_ops.fft(
            v, inverse=inverse, tile_b=tile_b, interpret=interpret)
    if engine == "sixstep":
        from . import sixstep
        return lambda v, inverse=False: sixstep.fft(
            v, inverse=inverse, tile_b=tile_b, interpret=interpret)
    raise ValueError(f"chirp engine must be one of {ENGINES}, got {engine!r}")


def fft(x: jnp.ndarray, inverse: bool = False, *, engine: str = "stockham",
        tile_b: int | None = None, interpret: bool | None = None) -> jnp.ndarray:
    """Chirp-Z DFT along the last axis; works for ANY length n.

    ``engine`` selects the padded pow2 engine ("stockham" keeps the staged
    jnp baseline; "auto"/"stockham_pallas"/"sixstep" are the fused-kernel
    chirp path the planner exposes as ``chirpz_pallas``).  ``engine`` and
    ``tile_b`` are the PATIENT-searchable knobs.
    """
    x = x.astype(_complex_dtype(x.dtype))
    n = x.shape[-1]
    if n == 1:
        return x
    engine, m = resolve_engine(n, engine, interpret)
    c, fb = chirp_tables(n, m, x.dtype, inverse)
    cfft = _padded_engine(engine, tile_b, interpret)

    a = jnp.zeros((*x.shape[:-1], m), dtype=x.dtype).at[..., :n].set(x * c)
    conv = cfft(cfft(a) * fb, inverse=True)
    y = conv[..., :n] * c
    if inverse:
        y = y / n
    return y


def ifft(x: jnp.ndarray) -> jnp.ndarray:
    return fft(x, inverse=True)
