"""Four-step (Bailey) FFT formulated as dense matmuls — the MXU-native path.

This is the TPU hardware adaptation of the paper's butterfly-based libraries
(DESIGN.md §2): instead of a radix-2 butterfly chain (memory-bound, VPU work),
factor n = n1 * n2 with n1 <= 128 and express the transform as

    X[k1 + k2*n1] = sum_{j2} ( W_n^{j2 k1} * sum_{j1} x[j1*n2 + j2] W_n1^{j1 k1} )
                    * W_n2^{j2 k2}                           (paper Eq. 2)

i.e.  D = (W_n1 @ A  *  T) @ W_n2,  out = transpose(D).flatten()

where A = x.reshape(n1, n2), W_r is the dense r-point DFT matrix and
T[k1, j2] = W_n^{k1 j2} the twiddle grid.  Every flop lands in a matmul, so on
TPU the whole transform runs on the 128x128 systolic MXU at high arithmetic
intensity; the length-n2 row transform recurses until n2 <= 512, which one
dense matmul takes in fewer passes than a further split (6859 runs as
19 x 361, 2^15 as 128 x 256).  Any length whose prime factors are all
<= 128 factors (:func:`repro.core.extents.fourstep_split`).

The Pallas kernel in ``repro/kernels/fft4step`` implements the n <= 16384 case
(two 128-wide matmuls + fused twiddle, all resident in VMEM); this module is
the algorithmic form, the jit-able fallback, and the oracle decomposition for
larger n.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.extents import FOURSTEP_DENSE_MAX_N, fourstep_split

from .reference import dft_matrix, twiddles

#: The TPU's default f32 matmul is one bf16 pass, far outside the c64 error
#: bound; HIGHEST keeps the DFT matmuls at full f32.
_PRECISION = jax.lax.Precision.HIGHEST


def _base_dft(x: jnp.ndarray, inverse: bool) -> jnp.ndarray:
    """Direct DFT via one matmul; n <= FOURSTEP_DENSE_MAX_N. W is
    symmetric -> x @ W."""
    n = x.shape[-1]
    w = dft_matrix(n, inverse=inverse, dtype=x.dtype)
    return jnp.matmul(x, w, precision=_PRECISION)


def _fft_unnormalized(x: jnp.ndarray, inverse: bool) -> jnp.ndarray:
    n = x.shape[-1]
    if n <= FOURSTEP_DENSE_MAX_N:
        return _base_dft(x, inverse)
    n1, n2 = fourstep_split(n)
    batch = x.shape[:-1]
    a = x.reshape(*batch, n1, n2)
    w1 = dft_matrix(n1, inverse=inverse, dtype=x.dtype)
    # column FFTs: B[k1, j2] = sum_j1 W[k1, j1] A[j1, j2]
    b = jnp.einsum("kj,...jn->...kn", w1, a, precision=_PRECISION)
    c = b * twiddles(n1, n2, inverse=inverse, dtype=x.dtype)
    # row FFTs of length n2 (recursive), batched over k1
    d = _fft_unnormalized(c, inverse)
    # output permutation: X[k1 + k2*n1] = D[k1, k2] -> transpose, flatten
    return jnp.swapaxes(d, -1, -2).reshape(*batch, n)


def fft(x: jnp.ndarray, inverse: bool = False) -> jnp.ndarray:
    """Four-step FFT along the last axis. Every prime factor of the length
    must be <= 128 (any power of two, and every 128-smooth size).

    Forward unnormalized, inverse scaled by 1/n (numpy semantics).
    """
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    y = _fft_unnormalized(x, inverse)
    if inverse:
        y = y / x.shape[-1]
    return y


def ifft(x: jnp.ndarray) -> jnp.ndarray:
    return fft(x, inverse=True)
