"""jit'd public wrapper for the fft4step kernel: complex API, factor choice,
padding, normalization."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.fft.reference import dft_matrix, twiddles
from ..stockham_pallas.ops import batch_tile
from .fft4step import fft4step, DEFAULT_TILE_B


def choose_factors(n: int) -> tuple[int, int]:
    """Pick n = n1*n2 with both factors <= 128 and as square as possible
    (square split balances the two matmul shapes on the MXU)."""
    best = None
    for n1 in range(min(128, n), 0, -1):
        if n % n1 == 0 and n // n1 <= 128:
            n2 = n // n1
            score = abs(n1 - n2)
            if best is None or score < best[0]:
                best = (score, n1, n2)
    if best is None:
        raise ValueError(f"n={n} has no n1*n2 factorization with both <= 128 "
                         "(max single-kernel n is 16384); compose kernels or "
                         "use the fourstep jnp path")
    return best[1], best[2]


@functools.partial(jax.jit, static_argnames=("inverse", "interpret", "tile_b"))
def fft(x: jnp.ndarray, inverse: bool = False, *, interpret: bool | None = None,
        tile_b: int = DEFAULT_TILE_B) -> jnp.ndarray:
    """Four-step FFT along the last axis via the fused Pallas kernel.

    Supports any n with an n1*n2 (<=128 each) factorization, i.e. n <= 16384
    for powers of two. numpy semantics (inverse applies 1/n).
    """
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    n = x.shape[-1]
    n1, n2 = choose_factors(n)
    # planes carry the problem's real dtype (f64 for c128 inputs)
    rdt = jnp.float64 if x.dtype == jnp.complex128 else jnp.float32
    batch_shape = x.shape[:-1]
    flat = x.reshape(-1, n1, n2)
    b = flat.shape[0]
    tile = batch_tile(tile_b, b)
    pad = (-b) % tile

    xr = jnp.real(flat).astype(rdt)
    xi = jnp.imag(flat).astype(rdt)
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0), (0, 0)))
        xi = jnp.pad(xi, ((0, pad), (0, 0), (0, 0)))

    w1 = dft_matrix(n1, inverse=inverse, dtype=jnp.complex128)
    w2 = dft_matrix(n2, inverse=inverse, dtype=jnp.complex128)
    t = twiddles(n1, n2, inverse=inverse, dtype=jnp.complex128)
    planes = lambda z: (jnp.real(z).astype(rdt), jnp.imag(z).astype(rdt))
    w1r, w1i = planes(w1)
    w2r, w2i = planes(w2)
    tr, ti = planes(t)

    yr, yi = fft4step(xr, xi, w1r, w1i, w2r, w2i, tr, ti,
                      n1=n1, n2=n2, tile_b=tile, interpret=interpret)
    y = (yr[:b] + 1j * yi[:b]).reshape(*batch_shape, n).astype(x.dtype)
    if inverse:
        y = y / n
    return y
