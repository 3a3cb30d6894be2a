"""The plain reference every cell's answers are compared with, and the
control that has to fail that comparison.

* :func:`forward` — the forward transform in float64 with ``scipy.fft``
  (numpy's algorithm, threaded).  It imports nothing of the program.
* :func:`rel_l2_rows` — per-row relative L2 distance; a cell compares the
  worst row.
* :func:`control_forward` — the same transform computed in the precision
  just below the one the configurations state.  They state float32 at
  ``Precision.HIGHEST`` (the program's matmul DFTs run there), so the
  control is ``Precision.HIGH``: each float32 matmul operand split into a
  bfloat16 head and tail and three bfloat16 products summed in float32,
  which is what the MXU does at ``HIGH``.  It is written out here so that it
  reads the same on every platform; it runs on the device in ``jax.numpy``
  as a recursive four-step DFT (direct DFT matmuls up to
  :data:`DIRECT_MAX` points).
"""

from __future__ import annotations

import math
import os

import numpy as np

#: Longest axis the control transforms by one direct DFT matmul.
DIRECT_MAX = 256


def forward(x: np.ndarray, rank: int, real: bool) -> np.ndarray:
    """Forward transform of the last ``rank`` axes of ``x`` in float64."""
    import scipy.fft

    axes = tuple(range(-rank, 0))
    workers = os.cpu_count() or 1
    if real:
        return scipy.fft.rfftn(np.asarray(x, np.float64), axes=axes,
                               workers=workers)
    return scipy.fft.fftn(np.asarray(x, np.complex128), axes=axes,
                          workers=workers)


def rel_l2_rows(got, ref) -> np.ndarray:
    """Relative L2 distance of each leading-axis row of ``got`` to ``ref``."""
    got = np.asarray(got, np.complex128).reshape(len(ref), -1)
    ref = np.asarray(ref, np.complex128).reshape(len(ref), -1)
    num = np.linalg.norm(got - ref, axis=1)
    den = np.maximum(np.linalg.norm(ref, axis=1), 1e-300)
    out = num / den
    return np.where(np.isfinite(out), out, np.inf)   # NaN never passes


# --- the control: float32 matmuls at HIGH (three bfloat16 passes) ----------
def _split(a):
    """A float32 array as the sum of two bfloat16 arrays.  The rounding is
    ``reduce_precision``, which XLA keeps: a bare float32-bfloat16-float32
    round trip may be dropped by the TPU compiler (excess precision)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _mm_high(a, b):
    """``a @ b`` for float32 operands as ``Precision.HIGH`` computes it:
    hi*hi + hi*lo + lo*hi, each a bfloat16 product summed in float32."""
    import jax.numpy as jnp

    ah, al = _split(a)
    bh, bl = _split(b)
    mm = lambda p, q: jnp.matmul(p, q, preferred_element_type=jnp.float32)
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def _cmm_high(xr, xi, wr, wi):
    return (_mm_high(xr, wr) - _mm_high(xi, wi),
            _mm_high(xr, wi) + _mm_high(xi, wr))


def _factor(n: int) -> int:
    """The divisor of ``n`` nearest to its square root from below (1 for a
    prime)."""
    for d in range(int(math.isqrt(n)), 0, -1):
        if n % d == 0:
            return d
    return 1


def _dft_matrix(n: int):
    k = np.arange(n)
    w = np.exp(-2j * np.pi * np.outer(k, k) / n)
    return w.real.astype(np.float32), w.imag.astype(np.float32)


def _fft_last(xr, xi):
    """Forward DFT of the last axis of (xr + i xi), float32 planes."""
    import jax.numpy as jnp

    n = xr.shape[-1]
    n1 = _factor(n)
    if n <= DIRECT_MAX or n1 == 1:
        wr, wi = _dft_matrix(n)
        return _cmm_high(xr, xi, jnp.asarray(wr), jnp.asarray(wi))
    n2 = n // n1
    lead = xr.shape[:-1]
    # x[j1*n2 + j2]: transform over j1 for every j2, twiddle, then over j2
    ar = jnp.swapaxes(xr.reshape(*lead, n1, n2), -1, -2)
    ai = jnp.swapaxes(xi.reshape(*lead, n1, n2), -1, -2)
    br, bi = _fft_last(ar, ai)                       # (..., j2, k1)
    k1 = np.arange(n1)
    j2 = np.arange(n2)
    tw = np.exp(-2j * np.pi * np.outer(j2, k1) / n)
    twr, twi = jnp.asarray(tw.real, jnp.float32), jnp.asarray(tw.imag,
                                                              jnp.float32)
    cr = br * twr - bi * twi
    ci = br * twi + bi * twr
    cr, ci = jnp.swapaxes(cr, -1, -2), jnp.swapaxes(ci, -1, -2)  # (k1, j2)
    dr, di = _fft_last(cr, ci)                       # (..., k1, k2)
    # X[k1 + n1*k2]
    dr = jnp.swapaxes(dr, -1, -2).reshape(*lead, n)
    di = jnp.swapaxes(di, -1, -2).reshape(*lead, n)
    return dr, di


def _flip_index(a, ax: int):
    """``a[(-k) mod n]`` along axis ``ax``."""
    import jax.numpy as jnp

    return jnp.roll(jnp.flip(a, ax), 1, ax)


def _fft_axes(xr, xi, rank: int):
    import jax.numpy as jnp

    for ax in range(-rank, 0):
        xr, xi = jnp.moveaxis(xr, ax, -1), jnp.moveaxis(xi, ax, -1)
        xr, xi = _fft_last(xr, xi)
        xr, xi = jnp.moveaxis(xr, -1, ax), jnp.moveaxis(xi, -1, ax)
    return xr, xi


def control_forward(x, rank: int, real: bool):
    """The control's forward transform of the last ``rank`` axes of ``x``
    (a device array), at ``Precision.HIGH``; returns complex64."""
    import jax
    import jax.numpy as jnp

    def run(v):
        if real:
            xr, xi = v.astype(jnp.float32), jnp.zeros(v.shape, jnp.float32)
        else:
            xr, xi = jnp.real(v), jnp.imag(v)
        xr, xi = _fft_axes(xr, xi, rank)
        out = jax.lax.complex(xr, xi)
        return out[..., :v.shape[-1] // 2 + 1] if real else out

    return jax.jit(run)(x)


def control_inverse(y, extents, real: bool):
    """The control's normalized inverse of a spectrum ``y`` over the last
    ``len(extents)`` axes, as :func:`control_forward` computes: the
    conjugate of the forward transform of the conjugate, over ``n``.  A real
    kind's half spectrum is first completed by Hermitian symmetry."""
    import jax
    import jax.numpy as jnp

    rank = len(extents)
    n = math.prod(extents)

    def run(s):
        if real:
            last = extents[-1]
            tail = jnp.conj(s[..., 1:last - last // 2])
            for ax in range(-rank, -1):
                tail = _flip_index(tail, ax)
            s = jnp.concatenate([s, jnp.flip(tail, -1)], axis=-1)
        xr, xi = _fft_axes(jnp.real(s), -jnp.imag(s), rank)
        if real:
            return xr / n
        return jax.lax.complex(xr, -xi) / n

    return jax.jit(run)(y)
