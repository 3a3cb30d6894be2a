"""jit'd public wrapper for the fused fftconv kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.fft.reference import dft_matrix, twiddles
from .fftconv import fftconv_kernel, DEFAULT_TILE_B


def _next_square_pow2(v: int) -> int:
    """Smallest 4^m >= v (so n = k*k with k = 2^m <= 128)."""
    n = 1
    while n < v:
        n *= 4
    if n > 128 * 128:
        raise ValueError(f"fused fftconv supports n <= 16384, need {v}")
    return n


@functools.partial(jax.jit, static_argnames=("interpret", "tile_b"))
def fftconv(x: jnp.ndarray, h: jnp.ndarray, *, interpret: bool | None = None,
            tile_b: int = DEFAULT_TILE_B) -> jnp.ndarray:
    """Causal depthwise convolution via the fused Pallas kernel.

    x: (C, B, L) real activations (channel-major);  h: (C, K) real filters,
    K <= L.  Returns (C, B, L) = linear causal conv, f32.
    """
    c, b, L = x.shape
    K = h.shape[-1]
    n = _next_square_pow2(L + K - 1)
    k = int(round(n ** 0.5))

    # filter spectra (natural order), inverse normalization folded in
    hf = jnp.fft.fft(h.astype(jnp.float32), n=n, axis=-1) / n
    hfr = jnp.real(hf).astype(jnp.float32).reshape(c, k, k)
    hfi = jnp.imag(hf).astype(jnp.float32).reshape(c, k, k)

    f32 = lambda z: (jnp.real(z).astype(jnp.float32), jnp.imag(z).astype(jnp.float32))
    wfr, wfi = f32(dft_matrix(k, dtype=jnp.complex128))
    wir, wii = f32(dft_matrix(k, inverse=True, dtype=jnp.complex128))
    tfr, tfi = f32(twiddles(k, k, dtype=jnp.complex128))
    tir, tii = f32(twiddles(k, k, inverse=True, dtype=jnp.complex128))

    tile = min(tile_b, max(1, b))
    pad_b = (-b) % tile
    xp = jnp.zeros((c, b + pad_b, n), jnp.float32).at[:, :b, :L].set(x)
    xp = xp.reshape(c, b + pad_b, k, k)

    y = fftconv_kernel(xp, hfr, hfi, wfr, wfi, wir, wii, tfr, tfi, tir, tii,
                       k=k, tile_b=tile, interpret=interpret)
    return y.reshape(c, b + pad_b, n)[:, :b, :L].astype(x.dtype)
