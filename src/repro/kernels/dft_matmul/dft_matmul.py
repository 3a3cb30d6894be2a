"""Pallas TPU kernel: batched small-n DFT as a dense MXU matmul.

The TPU-native base case of the four-step decomposition (DESIGN.md §2): an
n-point DFT with n <= 512 (the planner's ``DFT_MAX_N``) is a single
(B_tile, n) x (n, n) matmul against the DFT matrix — up to four lane tiles
of systolic-array work, vs. a butterfly chain that would run on the VPU and
be bound by VMEM shuffles, or a four-step split whose factor of 22 or less
leaves the MXU nearly empty.  An n that is not a multiple of 128 (361 =
19^2) is the full array dimension of every block, which Mosaic accepts.

Complex data is carried as separate real/imag f32 planes (Pallas TPU has no
complex dtype); one complex matmul = 4 real matmuls fused in one kernel pass
so the x tiles are read from VMEM once.

BlockSpec layout (grid over batch tiles):
  x_re, x_im : (TILE_B, n)  VMEM, block i -> rows [i*TILE_B, (i+1)*TILE_B)
  w_re, w_im : (n, n)       VMEM, broadcast to every grid step
  y_re, y_im : (TILE_B, n)  VMEM
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.device import interpret_mode


# VMEM at n = 384, double-buffered: x/y planes 4 x 256 x 384 x 4 B x 2 =
# 3 MB, W planes 2 x 384 x 384 x 4 B x 2 = 2.4 MB; v5e scopes 16 MiB
DEFAULT_TILE_B = 256
# Above n = 384 the matrix grows quadratically: at n = 512 and TILE_B 256
# the planes (4 MiB x/y, 4 MiB W) and HIGHEST's bf16 operand splits need
# 16.2 MiB, over the 16 MiB limit; TILE_B 128 halves the x/y share.
WIDE_N = 384
WIDE_TILE_B = 128


def default_tile_b(n: int) -> int:
    """The batch tile for length-n planes: 256, or 128 above n = 384."""
    return DEFAULT_TILE_B if n <= WIDE_N else WIDE_TILE_B


def _dft_kernel(xr_ref, xi_ref, wr_ref, wi_ref, yr_ref, yi_ref):
    xr = xr_ref[...]
    xi = xi_ref[...]
    wr = wr_ref[...]
    wi = wi_ref[...]
    # complex matmul on the MXU; accumulate in the plane dtype (f32, or f64
    # for complex128 problems — the conformance matrix's 1e-8 double bar).
    # HIGHEST: the TPU's default f32 matmul is one bf16 pass.
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=xr.dtype)
    yr_ref[...] = dot(xr, wr) - dot(xi, wi)
    yi_ref[...] = dot(xr, wi) + dot(xi, wr)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def dft_matmul(xr: jnp.ndarray, xi: jnp.ndarray, wr: jnp.ndarray, wi: jnp.ndarray,
               *, tile_b: int = DEFAULT_TILE_B, interpret: bool | None = None):
    """Batched DFT planes (B, n) @ DFT matrix (n, n), n <= 512. B %
    tile_b may be != 0; ops.py pads. An n that is not a multiple of the 128
    lane width runs on lane tiles that Mosaic pads."""
    b, n = xr.shape
    tile_b = min(tile_b, b)
    assert b % tile_b == 0, f"batch {b} not divisible by tile {tile_b}"
    grid = (b // tile_b,)
    row_spec = pl.BlockSpec((tile_b, n), lambda i: (i, 0))
    mat_spec = pl.BlockSpec((n, n), lambda i: (0, 0))
    out_shape = [jax.ShapeDtypeStruct((b, n), xr.dtype)] * 2
    yr, yi = pl.pallas_call(
        _dft_kernel,
        grid=grid,
        in_specs=[row_spec, row_spec, mat_spec, mat_spec],
        out_specs=[row_spec, row_spec],
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(xr, xi, wr, wi)
    return yr, yi
