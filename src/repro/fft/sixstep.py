"""Six-step (Bailey) FFT composing the fused Pallas kernels — the large-N
path that lifts the fft4step kernel's n <= 16384 cap to 2^20 and beyond.

Factor n = n1 * n2 and evaluate paper Eq. 2 as two *fused-kernel* passes
with explicit transposes between them (Bailey's six steps, hence the name):

  1. view x as A[j1, j2], transpose            -> At[j2, j1]
  2. n2 batched length-n1 FFTs (contiguous)    -> Bt[j2, k1]   stockham_pallas
  3. twiddle multiply  Bt *= W_n^{j2 k1}
  4. transpose                                 -> Ct[k1, j2]
  5. n1 batched length-n2 FFTs (contiguous)    -> D[k1, k2]    fft4step kernel
  6. transpose + flatten: X[k1 + k2*n1] = D[k1, k2]

The residual length-n1 transforms run in the in-VMEM Stockham kernel
(radix-8/4/2 chain, one HBM touch) and the length-n2 transforms in the
fused four-step MXU kernel (one HBM touch), so the whole transform moves
the signal through HBM a constant ~5 times — vs log2(n) passes for the
staged jnp Stockham at n where neither single kernel fits.

Feasibility: power-of-two n with n1 <= MAX_RESIDUAL_N and n2 <=
fft4step's 16384, i.e. any power of two up to 2^24 with the default
split.  numpy semantics (inverse applies 1/n — composed from the two
sub-transforms' own 1/n1 and 1/n2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fft4step import ops as fourstep_ops
from repro.kernels.stockham_pallas import ops as stockham_ops

from .reference import twiddles

#: fft4step kernel cap: n2 = n2a * n2b with both factors <= 128.
MAX_KERNEL_N2 = 128 * 128

#: Residual (Stockham-side) cap: keeps the length-n1 planes comfortably
#: in-VMEM at useful batch tiles.
MAX_RESIDUAL_N = 1 << 10

#: Largest extent the default split supports.
MAX_N = MAX_KERNEL_N2 * MAX_RESIDUAL_N  # 2^24


def choose_split(n: int, n1: int | None = None) -> tuple[int, int]:
    """Pick n = n1 * n2: n2 (four-step side) as large as the fused kernel
    allows, n1 the power-of-two residual.  An explicit planner-supplied
    ``n1`` wins when it is valid for this n; otherwise fall back to the
    default so one tuned knob can't break other axes of an nd transform.
    """
    if n & (n - 1) or n < 4:
        raise ValueError(f"sixstep requires power-of-two n >= 4, got {n}")
    if n1 is not None and 2 <= n1 <= MAX_RESIDUAL_N and n % n1 == 0 \
            and (n1 & (n1 - 1)) == 0 and 2 <= n // n1 <= MAX_KERNEL_N2:
        return n1, n // n1
    k = n.bit_length() - 1
    k2 = min(14, k - 1)          # 2^14 == 16384, the fft4step kernel cap
    return 1 << (k - k2), 1 << k2


@functools.partial(jax.jit,
                   static_argnames=("inverse", "n1", "tile_b", "interpret"))
def fft(x: jnp.ndarray, inverse: bool = False, *, n1: int | None = None,
        tile_b: int | None = None, interpret: bool | None = None) -> jnp.ndarray:
    """Six-step FFT along the last axis via the two fused Pallas kernels.

    ``n1`` (residual split) and ``tile_b`` (batch tile of both kernels) are
    the PATIENT-searchable knobs.  jit'd with static knobs like the sibling
    ops modules, so the host-side float64 twiddle grid is built once at
    trace time, not per call.
    """
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    n = x.shape[-1]
    if n < 4 and (n & (n - 1)) == 0:
        # below the smallest n1*n2 split there is nothing to compose; run
        # the single fused kernel directly.  This keeps the backend usable
        # on the packed-real innermost axis, whose engine length is n//2.
        return stockham_ops.fft(x, inverse=inverse, tile_b=tile_b,
                                interpret=interpret)
    n1, n2 = choose_split(n, n1)
    batch = x.shape[:-1]

    a = x.reshape(*batch, n1, n2)
    at = jnp.swapaxes(a, -1, -2)                        # (..., n2, n1)
    bt = stockham_ops.fft(at, inverse=inverse, tile_b=tile_b,
                          interpret=interpret)          # length-n1 FFTs
    c = bt * twiddles(n2, n1, inverse=inverse, dtype=x.dtype)
    ct = jnp.swapaxes(c, -1, -2)                        # (..., n1, n2)
    kw = {} if tile_b is None else {"tile_b": tile_b}
    d = fourstep_ops.fft(ct, inverse=inverse, interpret=interpret,
                         **kw)                          # length-n2 FFTs
    # the sub-transforms' own 1/n1 and 1/n2 compose to the inverse's 1/n
    return jnp.swapaxes(d, -1, -2).reshape(*batch, n)


def ifft(x: jnp.ndarray) -> jnp.ndarray:
    return fft(x, inverse=True)
