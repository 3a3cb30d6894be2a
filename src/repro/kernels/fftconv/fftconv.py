"""Pallas TPU kernel: fused frequency-domain depthwise convolution.

FFT -> pointwise filter -> inverse FFT, entirely in VMEM, using the square
four-step factorization n = k*k (k <= 128).  With n1 == n2 the transposed
four-step output *viewed as a 2-D array* is exactly the natural-order
spectrum reshaped (n1, n2), so the spectral multiply and the inverse
transform chain with ZERO data-movement between them — the whole
Hyena-style long-conv mixer becomes 14 MXU matmuls per signal tile with one
HBM read and one HBM write.  (An unfused jnp path costs 3 separate FFT
kernels + 2 elementwise HBM round-trips.)

Grid: (channels, batch_tiles).  Per step:
  x    : (1, TILE_B, k, k) real signal tile (imag = 0 exploited: forward
         column-DFT needs only 2 real matmuls instead of 4)
  hf_* : (1, k, k) filter spectrum planes for this channel (natural order
         reshaped (k, k)); 1/n inverse normalization pre-folded in
  wf_*/wi_* : (k, k) forward/inverse DFT matrices;  tf_*/ti_* twiddles
  y    : (1, TILE_B, k, k) real output tile (natural time order when
         flattened)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.device import interpret_mode


DEFAULT_TILE_B = 4


def _fourstep_core(xr, xi, wr, wi, tr, ti):
    """One four-step pass on (TB, k, k) planes -> transposed (TB, k, k)."""
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)
    dims = (((1,), (1,)), ((), ()))  # W (k,j) . x (b,j,n) -> (k,b,n)
    if xi is None:  # real input: half the column-DFT matmuls
        br = dot(wr, xr, dims)
        bi = dot(wi, xr, dims)
    else:
        br = dot(wr, xr, dims) - dot(wi, xi, dims)
        bi = dot(wr, xi, dims) + dot(wi, xr, dims)
    t_r, t_i = tr[:, None, :], ti[:, None, :]
    cr = br * t_r - bi * t_i
    ci = br * t_i + bi * t_r
    dims2 = (((2,), (0,)), ((), ()))
    dr = dot(cr, wr, dims2) - dot(ci, wi, dims2)
    di = dot(cr, wi, dims2) + dot(ci, wr, dims2)
    return jnp.transpose(dr, (1, 2, 0)), jnp.transpose(di, (1, 2, 0))


def _fftconv_kernel(x_ref, hfr_ref, hfi_ref, wfr_ref, wfi_ref, wir_ref,
                    wii_ref, tfr_ref, tfi_ref, tir_ref, tii_ref, y_ref):
    x = x_ref[0]          # (TB, k, k)
    hfr = hfr_ref[0]      # (k, k)
    hfi = hfi_ref[0]
    # forward transform of the real signal
    xfr, xfi = _fourstep_core(x, None, wfr_ref[...], wfi_ref[...],
                              tfr_ref[...], tfi_ref[...])
    # spectral multiply (transposed layout == natural-order (k,k) view)
    er = xfr * hfr - xfi * hfi
    ei = xfr * hfi + xfi * hfr
    # inverse transform (matrices/twiddles conjugated; 1/n folded into hf)
    yr, _ = _fourstep_core(er, ei, wir_ref[...], wii_ref[...],
                           tir_ref[...], tii_ref[...])
    y_ref[0] = yr


@functools.partial(jax.jit, static_argnames=("k", "tile_b", "interpret"))
def fftconv_kernel(x, hfr, hfi, wfr, wfi, wir, wii, tfr, tfi, tir, tii, *,
                   k: int, tile_b: int = DEFAULT_TILE_B, interpret: bool | None = None):
    """x: (C, B, k, k) real; hf*: (C, k, k); returns y (C, B, k, k)."""
    c, b = x.shape[0], x.shape[1]
    tile_b = min(tile_b, b)
    assert b % tile_b == 0
    grid = (c, b // tile_b)
    sig = pl.BlockSpec((1, tile_b, k, k), lambda ci, bi: (ci, bi, 0, 0))
    hspec = pl.BlockSpec((1, k, k), lambda ci, bi: (ci, 0, 0))
    mat = pl.BlockSpec((k, k), lambda ci, bi: (0, 0))
    return pl.pallas_call(
        _fftconv_kernel,
        grid=grid,
        in_specs=[sig, hspec, hspec] + [mat] * 8,
        out_specs=sig,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret_mode(interpret),
    )(x, hfr, hfi, wfr, wfi, wir, wii, tfr, tfi, tir, tii)
