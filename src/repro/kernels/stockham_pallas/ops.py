"""jit'd public wrapper for the stockham_pallas kernel: complex API, mixed-
radix schedule + twiddle packing (host-side float64), batch tiling/padding,
normalization."""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .stockham_pallas import (DEFAULT_TILE_B, radix_schedule, smooth7,
                              stockham_pallas)

#: Soft VMEM budget steering the default batch tile (in/out/stage planes;
#: real VMEM is ~16 MiB/core, leave headroom for twiddles + double buffers).
VMEM_BUDGET_BYTES = 4 << 20

#: Largest single-kernel n: bounded by holding one (tile_b=1) signal's
#: working planes in VMEM.  Larger transforms go through the six-step path.
MAX_N = 1 << 20


def pack_twiddles(n: int, radices: tuple[int, ...], inverse: bool,
                  real_dtype) -> tuple[np.ndarray, np.ndarray,
                                       tuple[tuple[int, ...], ...]]:
    """Per-stage twiddle planes W_cur^{p*u} (u = 1..r-1, p < cur/r) packed
    into one (1, L) pair, plus static per-(stage, u) offsets.

    Angles use exact integer reduction of p*u mod cur before the float64
    conversion, so phases stay accurate for n in the millions even when the
    planes are float32.
    """
    sign = 2.0 if inverse else -2.0
    re_chunks, im_chunks, offsets = [], [], []
    off, cur = 0, n
    for r in radices:
        m = cur // r
        stage_offs = []
        p = np.arange(m, dtype=np.int64)
        for u in range(1, r):
            ang = (sign * np.pi / cur) * ((u * p) % cur).astype(np.float64)
            re_chunks.append(np.cos(ang))
            im_chunks.append(np.sin(ang))
            stage_offs.append(off)
            off += m
        offsets.append(tuple(stage_offs))
        cur = m
    pad = (-off) % 128 or (128 if off == 0 else 0)  # lane-align the pack
    re_chunks.append(np.zeros(pad))
    im_chunks.append(np.zeros(pad))
    twr = np.concatenate(re_chunks)[None, :].astype(real_dtype)
    twi = np.concatenate(im_chunks)[None, :].astype(real_dtype)
    return twr, twi, tuple(offsets)


def batch_tile(tile: int, batch: int) -> int:
    """The batch tile a kernel grid may use for ``batch`` rows: the whole
    batch when ``tile`` covers it, else ``tile`` rounded up to a multiple
    of 8.  Mosaic refuses a block whose second-to-last dim is neither a
    multiple of the 8 sublanes nor the whole array dim; the wrappers pad
    the batch to a multiple of the returned tile."""
    tile = -(-max(1, tile) // 8) * 8 if tile < batch else batch
    return max(1, min(tile, batch))


def default_tile_b(n: int, batch: int, itemsize: int, *, planes: int = 6,
                   cap: int = 256) -> int:
    """Largest power-of-two batch tile whose working planes fit the VMEM
    budget.  ``planes`` is the live-plane estimate per signal row (~6 here:
    in/out/stage temporaries; the rank-2 kernel passes 8 for its transpose
    temporaries), ``cap`` the kernel's tile ceiling."""
    per_row = planes * n * itemsize
    tile = max(1, VMEM_BUDGET_BYTES // max(1, per_row))
    tile = 1 << (tile.bit_length() - 1)
    return max(1, min(tile, cap, batch))


@functools.partial(jax.jit,
                   static_argnames=("inverse", "tile_b", "radix", "interpret"))
def fft(x: jnp.ndarray, inverse: bool = False, *, tile_b: int | None = None,
        radix: int = 8, interpret: bool | None = None) -> jnp.ndarray:
    """Fused Stockham FFT along the last axis via the Pallas kernel.

    7-smooth (2^a*3^b*5^c*7^d) lengths up to ``MAX_N``; all mixed-radix
    stages run on a VMEM-resident batch tile, so the signal touches HBM once
    each way.  numpy semantics (inverse applies 1/n).  ``tile_b``/``radix``
    are the PATIENT-searchable knobs (``radix`` sizes the pow2 work stages;
    ``tile_b=None`` sizes the tile to VMEM).
    """
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    n = x.shape[-1]
    if not smooth7(n):
        raise ValueError("stockham_pallas requires a 7-smooth "
                         f"(2^a*3^b*5^c*7^d) length, got {n}")
    if n > MAX_N:
        raise ValueError(f"stockham_pallas caps at n={MAX_N}; "
                         "use the sixstep backend beyond that")
    if n == 1:
        return x   # length-1 DFT is the identity (1/n factor is 1 too)

    real_dtype = jnp.float64 if x.dtype == jnp.complex128 else jnp.float32
    batch_shape = x.shape[:-1]
    flat = x.reshape(-1, n)
    b = flat.shape[0]
    tile = batch_tile(tile_b if tile_b is not None else default_tile_b(
        n, b, jnp.dtype(real_dtype).itemsize), b)
    pad = (-b) % tile

    xr = jnp.real(flat).astype(real_dtype)
    xi = jnp.imag(flat).astype(real_dtype)
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
        xi = jnp.pad(xi, ((0, pad), (0, 0)))

    radices = radix_schedule(n, radix)
    twr, twi, offsets = pack_twiddles(n, radices, inverse, real_dtype)
    yr, yi = stockham_pallas(xr, xi, jnp.asarray(twr), jnp.asarray(twi),
                             n=n, radices=radices, offsets=offsets,
                             inverse=inverse, tile_b=tile, interpret=interpret)
    y = (yr[:b] + 1j * yi[:b]).reshape(*batch_shape, n).astype(x.dtype)
    if inverse:
        y = y / n
    return y
