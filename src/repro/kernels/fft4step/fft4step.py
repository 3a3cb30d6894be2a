"""Pallas TPU kernel: fused four-step FFT, fully resident in VMEM.

One kernel pass computes, for a tile of TILE_B independent signals of length
n = n1*n2 (n1, n2 <= 128), each signal viewed as an (n1, n2) matrix A:

    B = W1 @ A ;  C = B * T ;  out = W2 @ C^T      (paper Eq. 2 as matmuls)

- 8 real (MXU) matmuls per complex signal (2 complex matmuls),
- twiddle multiply fused between them (VPU, no HBM round-trip),
- the four-step output transpose folded into the second matmul: W2 is
  symmetric, so (C @ W2)^T = W2 @ C^T, an "NT" matmul that contracts both
  operands on their last axis.

A butterfly FFT of n=16384 touches HBM log2(n)=14 times if staged naively;
this kernel reads the signal from HBM exactly once and writes it once —
the arithmetic-intensity transformation that moves the FFT from the paper's
"memory-bound above 1 MiB" regime toward the MXU roofline on TPU.

Every matmul and elementwise op works on one signal's 2-D (n1, n2) planes:
Mosaic lowers plain and NT 2-D matmuls at any factor size, but not the
batched 3-D contractions and (k, b, m) -> (b, m, k) transposes of a
whole-tile formulation when a factor is below the 128-lane tile.  The tile
loop is unrolled at trace time.  Matmuls run at ``Precision.HIGHEST``: the
TPU's default f32 matmul is one bf16 pass, far outside the c64 error bound.

VMEM at TILE_B=8, n=16384: in/out planes 4 x 8 x 64 KiB = 2 MiB, DFT matrices
4 x 64 KiB, twiddles 2 x 64 KiB -> ~2.5 MiB of ~16 MiB/core.

BlockSpec layout (grid over batch tiles):
  x_re, x_im : (TILE_B, n1, n2) VMEM, block i -> batch tile i
  w1_*       : (n1, n1) VMEM broadcast;  w2_* : (n2, n2) VMEM broadcast
  t_*        : (n1, n2) VMEM broadcast (twiddle grid)
  y_re, y_im : (TILE_B, n2, n1) VMEM (transposed four-step output)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.device import interpret_mode

DEFAULT_TILE_B = 8

_NT = (((1,), (1,)), ((), ()))   # contract lhs axis 1 with rhs axis 1


def _fft4step_kernel(xr_ref, xi_ref, w1r_ref, w1i_ref, w2r_ref, w2i_ref,
                     tr_ref, ti_ref, yr_ref, yi_ref, *, tile_b: int):
    w1r, w1i = w1r_ref[...], w1i_ref[...]
    w2r, w2i = w2r_ref[...], w2i_ref[...]
    tr, ti = tr_ref[...], ti_ref[...]
    # accumulate in the plane dtype (f32 planes for c64 problems, f64 for
    # c128 — double runs in interpret mode only)
    dt = xr_ref.dtype
    hi = jax.lax.Precision.HIGHEST
    nn = functools.partial(jnp.dot, precision=hi, preferred_element_type=dt)
    nt = functools.partial(jax.lax.dot_general, dimension_numbers=_NT,
                           precision=hi, preferred_element_type=dt)
    for b in range(tile_b):
        xr, xi = xr_ref[b], xi_ref[b]            # (n1, n2)
        # column DFTs: B = W1 @ A
        br = nn(w1r, xr) - nn(w1i, xi)
        bi = nn(w1r, xi) + nn(w1i, xr)
        # twiddle multiply
        cr = br * tr - bi * ti
        ci = br * ti + bi * tr
        # row DFTs with the output transpose: Y = W2 @ C^T, (n2, n1)
        yr_ref[b] = nt(w2r, cr) - nt(w2i, ci)
        yi_ref[b] = nt(w2r, ci) + nt(w2i, cr)


@functools.partial(jax.jit,
                   static_argnames=("n1", "n2", "tile_b", "interpret"))
def fft4step(xr, xi, w1r, w1i, w2r, w2i, tr, ti, *, n1: int, n2: int,
             tile_b: int = DEFAULT_TILE_B, interpret: bool | None = None):
    """x planes: (B, n1, n2); returns y planes (B, n2, n1)."""
    b = xr.shape[0]
    tile_b = min(tile_b, b)
    assert b % tile_b == 0, f"batch {b} % tile {tile_b} != 0 (ops.py pads)"
    grid = (b // tile_b,)
    sig_in = pl.BlockSpec((tile_b, n1, n2), lambda i: (i, 0, 0))
    sig_out = pl.BlockSpec((tile_b, n2, n1), lambda i: (i, 0, 0))
    m1 = pl.BlockSpec((n1, n1), lambda i: (0, 0))
    m2 = pl.BlockSpec((n2, n2), lambda i: (0, 0))
    tw = pl.BlockSpec((n1, n2), lambda i: (0, 0))
    out_shape = [jax.ShapeDtypeStruct((b, n2, n1), xr.dtype)] * 2
    yr, yi = pl.pallas_call(
        functools.partial(_fft4step_kernel, tile_b=tile_b),
        grid=grid,
        in_specs=[sig_in, sig_in, m1, m1, m2, m2, tw, tw],
        out_specs=[sig_out, sig_out],
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(xr, xi, w1r, w1i, w2r, w2i, tr, ti)
    return yr, yi
