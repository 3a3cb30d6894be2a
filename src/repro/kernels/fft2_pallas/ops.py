"""jit'd public wrapper for the fft2_pallas kernel: complex rank-2 API,
per-axis radix schedules + one shared twiddle pack (host-side float64),
batch tiling/padding, normalization."""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..stockham_pallas.stockham_pallas import radix_schedule
from ..stockham_pallas.ops import batch_tile, pack_twiddles
from ..stockham_pallas.ops import default_tile_b as _default_tile_b
from .fft2_pallas import DEFAULT_TILE_B, fft2_pallas

#: Largest n1*n2 tile a single kernel instance may hold: bounded by the
#: working planes of one (tile_b=1) tile in VMEM.  Larger rank-2 problems
#: go through the separable per-axis path.
MAX_ELEMS = 1 << 18


def pack_twiddles2(n1: int, n2: int, radices1, radices2, inverse: bool,
                   real_dtype):
    """Both axes' stage twiddles in one (1, L) pair: the n2 (row) pack
    first, then the n1 (column) pack with its offsets shifted past it.
    Each per-axis pack comes from the rank-1 kernel's ``pack_twiddles``
    (float64 angles, exact integer mod reduction, lane-aligned)."""
    twr2, twi2, off2 = pack_twiddles(n2, radices2, inverse, real_dtype)
    twr1, twi1, off1 = pack_twiddles(n1, radices1, inverse, real_dtype)
    shift = twr2.shape[1]
    off1 = tuple(tuple(o + shift for o in stage) for stage in off1)
    twr = np.concatenate([twr2, twr1], axis=1)
    twi = np.concatenate([twi2, twi1], axis=1)
    return twr, twi, off1, off2


def default_tile_b(n_elems: int, batch: int, itemsize: int) -> int:
    """The shared VMEM-budget heuristic at this kernel's plane count (~8:
    in/out/stage/transpose temporaries) and tile ceiling."""
    return _default_tile_b(n_elems, batch, itemsize, planes=8, cap=64)


@functools.partial(jax.jit,
                   static_argnames=("inverse", "tile_b", "radix", "interpret"))
def fft2(x: jnp.ndarray, inverse: bool = False, *, tile_b: int | None = None,
         radix: int = 8, interpret: bool | None = None) -> jnp.ndarray:
    """Fused rank-2 FFT over the last TWO axes via the Pallas kernel.

    Power-of-two extents with n1*n2 <= ``MAX_ELEMS``; row stages, in-VMEM
    transpose, and column stages all run on a VMEM-resident batch tile, so
    the signal touches HBM once each way.  numpy semantics (inverse applies
    1/(n1*n2)).  ``tile_b``/``radix`` are the PATIENT-searchable knobs;
    ``tile_b=None`` sizes the tile to VMEM.
    """
    if x.ndim < 2:
        raise ValueError(f"fft2 needs rank >= 2 input, got shape {x.shape}")
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    n1, n2 = x.shape[-2], x.shape[-1]
    if (n1 & (n1 - 1)) or (n2 & (n2 - 1)):
        raise ValueError(
            f"fft2_pallas requires power-of-two extents, got {n1}x{n2}")
    if n1 * n2 > MAX_ELEMS:
        raise ValueError(f"fft2_pallas caps at n1*n2={MAX_ELEMS}; "
                         "use the separable per-axis path beyond that")
    if n1 * n2 == 1:
        return x

    real_dtype = jnp.float64 if x.dtype == jnp.complex128 else jnp.float32
    batch_shape = x.shape[:-2]
    flat = x.reshape(-1, n1, n2)
    b = flat.shape[0]
    tile = batch_tile(tile_b if tile_b is not None else default_tile_b(
        n1 * n2, b, jnp.dtype(real_dtype).itemsize), b)
    pad = (-b) % tile

    xr = jnp.real(flat).astype(real_dtype)
    xi = jnp.imag(flat).astype(real_dtype)
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0), (0, 0)))
        xi = jnp.pad(xi, ((0, pad), (0, 0), (0, 0)))

    radices1 = radix_schedule(n1, radix)
    radices2 = radix_schedule(n2, radix)
    twr, twi, off1, off2 = pack_twiddles2(n1, n2, radices1, radices2,
                                          inverse, real_dtype)
    yr, yi = fft2_pallas(xr, xi, jnp.asarray(twr), jnp.asarray(twi),
                         n1=n1, n2=n2, radices1=radices1, radices2=radices2,
                         offsets1=off1, offsets2=off2, inverse=inverse,
                         tile_b=tile, interpret=interpret)
    y = (yr[:b] + 1j * yi[:b]).reshape(*batch_shape, n1, n2).astype(x.dtype)
    if inverse:
        y = y / (n1 * n2)
    return y
