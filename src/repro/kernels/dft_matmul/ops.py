"""jit'd public wrapper for the dft_matmul kernel: complex API, padding."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.fft.reference import dft_matrix
from .dft_matmul import default_tile_b, dft_matmul


def _pad_rows(a: jnp.ndarray, mult: int) -> jnp.ndarray:
    b = a.shape[0]
    rem = (-b) % mult
    if rem:
        a = jnp.pad(a, ((0, rem), (0, 0)))
    return a


@functools.partial(jax.jit, static_argnames=("inverse", "interpret", "tile_b"))
def dft(x: jnp.ndarray, inverse: bool = False, *, interpret: bool | None = None,
        tile_b: int | None = None) -> jnp.ndarray:
    """Direct DFT along the last axis via the Pallas MXU kernel.

    x: complex, any batch shape, last-axis length n <= 512 (the planner's
    ``DFT_MAX_N``: the VMEM budget of one batch tile and its matrix).
    ``tile_b`` defaults to :func:`default_tile_b` of n.
    Forward unnormalized, inverse 1/n (numpy semantics).
    """
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    n = x.shape[-1]
    batch_shape = x.shape[:-1]
    flat = x.reshape(-1, n)
    b = flat.shape[0]

    # planes carry the problem's real dtype (float64 for complex128), so
    # double-precision problems keep double-precision accumulation
    real_dtype = jnp.float64 if x.dtype == jnp.complex128 else jnp.float32
    w = dft_matrix(n, inverse=inverse, dtype=jnp.complex128)
    wr = jnp.real(w).astype(real_dtype)
    wi = jnp.imag(w).astype(real_dtype)

    tile = min(tile_b or default_tile_b(n), max(8, b))
    xr = _pad_rows(jnp.real(flat).astype(real_dtype), tile)
    xi = _pad_rows(jnp.imag(flat).astype(real_dtype), tile)
    yr, yi = dft_matmul(xr, xi, wr, wi, tile_b=tile, interpret=interpret)
    y = (yr[:b] + 1j * yi[:b]).reshape(*batch_shape, n).astype(x.dtype)
    if inverse:
        y = y / n
    return y
