"""Pallas TPU kernel: fused rank-2 FFT, the whole n1 x n2 tile in VMEM.

The separable path for a 2D transform runs the inner axis fused (one HBM
touch via stockham_pallas), but the outer axis still pays a swapaxes pass
in, its own transform, and a swapaxes pass out — 2*log2(n)+2 HBM touches on
the staged baseline, and never fewer than ~4 even with fused 1-D kernels.
This kernel does the classical small-2D trick instead: hold the full
n1 x n2 tile in VMEM, run the row (last-axis) Stockham stages, transpose
*in VMEM*, run the column stages, transpose back — so a small-extent 2D FFT
reads and writes HBM exactly once each way.

Layout (grid over batch tiles; all shapes static):
  x_re, x_im : (TILE_B, n1, n2) VMEM, block i -> batch tile i
  tw_re/im   : (1, L) VMEM broadcast — both axes' per-stage twiddles packed
               back to back (n2 stages first, then n1 stages at shifted
               offsets), precomputed host-side in float64
  y_re, y_im : (TILE_B, n1, n2) VMEM, natural order

The stage math is exactly ``stockham_pallas.apply_stages`` — the same
radix-8/4 work stages with a 4/2 cleanup, butterfly constants folded to
adds/swaps — applied once per axis around ``jnp.swapaxes`` on the resident
planes.  Feasibility is VMEM-capped (see ``ops.MAX_ELEMS``); the planner's
cost model charges one HBM touch inside the budget and infinity past it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.device import interpret_mode

from ..stockham_pallas.stockham_pallas import apply_stages

DEFAULT_TILE_B = 4


def _fft2_kernel(xr_ref, xi_ref, twr_ref, twi_ref, yr_ref, yi_ref, *,
                 n1: int, n2: int,
                 radices1: tuple[int, ...], radices2: tuple[int, ...],
                 offsets1: tuple[tuple[int, ...], ...],
                 offsets2: tuple[tuple[int, ...], ...], inverse: bool):
    xr = xr_ref[...]                   # (TB, n1, n2)
    xi = xi_ref[...]
    twr = twr_ref[0]                   # (L,) both axes' packed twiddles
    twi = twi_ref[0]
    # row transform: all n2 stages on the resident tile
    xr, xi = apply_stages(xr, xi, twr, twi, n=n2, radices=radices2,
                          offsets=offsets2, inverse=inverse)
    # in-VMEM transpose; column stages are row stages of the transpose
    xr = jnp.swapaxes(xr, -1, -2)      # (TB, n2, n1)
    xi = jnp.swapaxes(xi, -1, -2)
    xr, xi = apply_stages(xr, xi, twr, twi, n=n1, radices=radices1,
                          offsets=offsets1, inverse=inverse)
    yr_ref[...] = jnp.swapaxes(xr, -1, -2)
    yi_ref[...] = jnp.swapaxes(xi, -1, -2)


@functools.partial(
    jax.jit, static_argnames=("n1", "n2", "radices1", "radices2", "offsets1",
                              "offsets2", "inverse", "tile_b", "interpret"))
def fft2_pallas(xr, xi, twr, twi, *, n1: int, n2: int,
                radices1: tuple[int, ...], radices2: tuple[int, ...],
                offsets1: tuple[tuple[int, ...], ...],
                offsets2: tuple[tuple[int, ...], ...], inverse: bool,
                tile_b: int = DEFAULT_TILE_B, interpret: bool | None = None):
    """x planes: (B, n1, n2); returns y planes (B, n1, n2), natural order,
    one HBM read + one HBM write of the signal for the whole 2D transform."""
    b = xr.shape[0]
    tile_b = min(tile_b, b)
    assert b % tile_b == 0, f"batch {b} % tile {tile_b} != 0 (ops.py pads)"
    grid = (b // tile_b,)
    sig = pl.BlockSpec((tile_b, n1, n2), lambda i: (i, 0, 0))
    tw = pl.BlockSpec(twr.shape, lambda i: (0, 0))
    kernel = functools.partial(_fft2_kernel, n1=n1, n2=n2,
                               radices1=radices1, radices2=radices2,
                               offsets1=offsets1, offsets2=offsets2,
                               inverse=inverse)
    out_shape = [jax.ShapeDtypeStruct((b, n1, n2), xr.dtype)] * 2
    yr, yi = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[sig, sig, tw, tw],
        out_specs=[sig, sig],
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(xr, xi, twr, twi)
    return yr, yi
