"""Pallas TPU kernel: fused multi-stage Stockham FFT, fully resident in VMEM.

The pure-jnp Stockham backend (``repro/fft/stockham.py``) pays one HBM
round-trip per radix-2 stage — log2(N) passes over the signal, which is
exactly the "memory-bound above 1 MiB" regime of the paper's Fig. 8.  This
kernel runs *every* stage of the autosort chain on a VMEM-resident batch
tile: the signal is read from HBM once, transformed through a static radix
schedule (radix-3/5/7 work stages for the odd factors, then radix-8/4
stages with a radix-2 cleanup for the power-of-two part), and written once.
Any 7-smooth length n = 2^a * 3^b * 5^c * 7^d — the paper's powerof2 AND
radix357 extent classes — is therefore a single HBM touch.

Stage math (DIF Stockham, same derivation as the jnp module): with the
buffer holding x[q + s*(p + m*t)] for a stage of size ``cur`` = r*m at
stride ``s`` (cur*s == N invariant), one radix-r stage computes

    y[q + s*(u + r*p)] = ( sum_t x[q + s*(p + m*t)] * W_r^{t u} )
                         * W_cur^{p u} ,    u < r, p < m

then recurses with (cur, s) <- (m, r*s).  The W_r butterfly constants are
Python-float literals resolved at trace time (multiplies by 0/±1/±i are
elided); the W_cur^{p u} stage twiddles are precomputed host-side in
float64 (exact integer reduction of p*u mod cur) and passed as two packed
(1, L) plane operands, sliced per stage at static offsets.

Layout (grid over batch tiles; all shapes static):
  x_re, x_im : (TILE_B, n) VMEM, block i -> batch tile i
  tw_re/im   : (1, L) VMEM broadcast — per-stage twiddles, concatenated
  y_re, y_im : (TILE_B, n) VMEM

Planes carry the problem's real dtype (float32, or float64 for c128), so
double precision works in interpret mode and on f64-capable backends.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.device import interpret_mode


DEFAULT_TILE_B = 8

#: Tunable radix schedules the planner may request (largest pow2 work stage;
#: odd factors always run as their own radix-3/5/7 stages).
RADICES = (2, 4, 8)

#: The prime factors the stage chain can express (paper's radix357 class).
SMOOTH_PRIMES = (2, 3, 5, 7)


def smooth7(n: int) -> bool:
    """Is ``n`` of the form 2^a * 3^b * 5^c * 7^d (n >= 1)?"""
    if n < 1:
        return False
    for p in SMOOTH_PRIMES:
        while n % p == 0:
            n //= p
    return n == 1


def radix_schedule(n: int, radix: int = 8) -> tuple[int, ...]:
    """Static mixed-radix stage schedule for a 7-smooth ``n``: the odd prime
    factors first as radix-7/5/3 work stages, then ``radix`` power-of-two
    work stages with a single 4/2 cleanup (e.g. n=3*2^10, radix=8 ->
    (3, 8, 8, 8, 2)).  The stage product is exactly ``n``."""
    if not smooth7(n):
        raise ValueError("stockham_pallas requires a 7-smooth "
                         f"(2^a*3^b*5^c*7^d) length, got {n}")
    if radix not in RADICES:
        raise ValueError(f"radix must be one of {RADICES}, got {radix}")
    out = []
    m = n
    for p in (7, 5, 3):
        while m % p == 0:
            out.append(p)
            m //= p
    k = m.bit_length() - 1
    step = radix.bit_length() - 1
    while k >= step:
        out.append(radix)
        k -= step
    if k == 2:
        out.append(4)
    elif k == 1:
        out.append(2)
    return tuple(out)


def _root(k: int, r: int, inverse: bool) -> tuple[float, float]:
    """W_r^k as (re, im) Python floats, with exact 0/±1 on the axes so the
    butterfly elides those multiplies entirely."""
    k = k % r
    ang = 2.0 * math.pi * k / r
    c, s = math.cos(ang), math.sin(ang)
    for v in (-1.0, 0.0, 1.0):
        if abs(c - v) < 1e-12:
            c = v
        if abs(s - v) < 1e-12:
            s = v
    return c, (s if inverse else -s)


def _butterfly(parts, r: int, inverse: bool):
    """r-point DFT across ``parts`` (list of (re, im) plane pairs).

    Returns the r outputs; multiplies by W_r^k in {1, -1, ±i} are folded
    into adds/swaps, so radix-2/4 stages are multiply-free and radix-8
    spends its multiplies only on the +-(1±i)/sqrt(2) terms.
    """
    outs = []
    for u in range(r):
        br, bi = parts[0]          # t = 0 term: W_r^0 == 1
        for t in range(1, r):
            c, s = _root(t * u, r, inverse)
            ar, ai = parts[t]
            if (c, s) == (1.0, 0.0):
                br, bi = br + ar, bi + ai
            elif (c, s) == (-1.0, 0.0):
                br, bi = br - ar, bi - ai
            elif (c, s) == (0.0, -1.0):   # multiply by -i
                br, bi = br + ai, bi - ar
            elif (c, s) == (0.0, 1.0):    # multiply by +i
                br, bi = br - ai, bi + ar
            else:
                br = br + ar * c - ai * s
                bi = bi + ar * s + ai * c
        outs.append((br, bi))
    return outs


def apply_stages(xr, xi, twr, twi, *, n: int, radices: tuple[int, ...],
                 offsets: tuple[tuple[int, ...], ...], inverse: bool):
    """Run the whole Stockham stage chain along the LAST axis of the
    VMEM-resident planes ``xr``/``xi`` (any leading batch dims).  Shared by
    the rank-1 kernel and the fused rank-2 kernel (which calls it once per
    axis around an in-VMEM transpose).  ``twr``/``twi`` are the packed
    per-stage twiddle vectors, ``offsets`` the static per-(stage, u) slice
    starts from ``ops.pack_twiddles``."""
    lead = xr.shape[:-1]
    ones = (1,) * len(lead)
    cur = n
    for stage, r in enumerate(radices):
        m = cur // r
        s = n // cur                   # stride invariant: cur * s == n
        vr = xr.reshape(*lead, r, m, s)
        vi = xi.reshape(*lead, r, m, s)
        parts = [(vr[..., t, :, :], vi[..., t, :, :]) for t in range(r)]
        outs = _butterfly(parts, r, inverse)
        rows = [outs[0]]               # u = 0: twiddle is all-ones
        for u in range(1, r):
            off = offsets[stage][u - 1]
            wr = twr[off:off + m].reshape(*ones, m, 1)
            wi = twi[off:off + m].reshape(*ones, m, 1)
            br, bi = outs[u]
            rows.append((br * wr - bi * wi, br * wi + bi * wr))
        xr = jnp.stack([p[0] for p in rows], axis=-2).reshape(*lead, n)
        xi = jnp.stack([p[1] for p in rows], axis=-2).reshape(*lead, n)
        cur = m
    return xr, xi


def _stockham_kernel(xr_ref, xi_ref, twr_ref, twi_ref, yr_ref, yi_ref, *,
                     n: int, radices: tuple[int, ...],
                     offsets: tuple[tuple[int, ...], ...], inverse: bool):
    yr_ref[...], yi_ref[...] = apply_stages(
        xr_ref[...], xi_ref[...], twr_ref[0], twi_ref[0],
        n=n, radices=radices, offsets=offsets, inverse=inverse)


@functools.partial(
    jax.jit, static_argnames=("n", "radices", "offsets", "inverse",
                              "tile_b", "interpret"))
def stockham_pallas(xr, xi, twr, twi, *, n: int, radices: tuple[int, ...],
                    offsets: tuple[tuple[int, ...], ...], inverse: bool,
                    tile_b: int = DEFAULT_TILE_B, interpret: bool | None = None):
    """x planes: (B, n); returns y planes (B, n), natural order, one HBM
    read + one HBM write of the signal regardless of log2(n)."""
    b = xr.shape[0]
    tile_b = min(tile_b, b)
    assert b % tile_b == 0, f"batch {b} % tile {tile_b} != 0 (ops.py pads)"
    grid = (b // tile_b,)
    sig = pl.BlockSpec((tile_b, n), lambda i: (i, 0))
    tw = pl.BlockSpec(twr.shape, lambda i: (0, 0))
    kernel = functools.partial(_stockham_kernel, n=n, radices=radices,
                               offsets=offsets, inverse=inverse)
    out_shape = [jax.ShapeDtypeStruct((b, n), xr.dtype)] * 2
    yr, yi = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[sig, sig, tw, tw],
        out_specs=[sig, sig],
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(xr, xi, twr, twi)
    return yr, yi
