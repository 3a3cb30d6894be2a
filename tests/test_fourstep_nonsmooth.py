"""The jnp four-step engine at lengths with prime factors above 13.

Rows of up to 512 are one dense matmul; longer lengths peel a radix
(the largest power of two up to 128 that divides n, else the smallest odd
prime), so 6859 = 19^3 runs as 19 x 361."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.extents import fourstep_levels
from repro.fft import fourstep, nd

# 361 = 19^2 is one dense matmul; the rest split: 513 = 3 x 171,
# 667 = 23 x 29, 6859 = 19 x 361, 16385 = 5 x 29 x 113
LENGTHS = (361, 513, 667, 6859, 16385)


def _signal(shape, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


def _rel(got, want):
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
@pytest.mark.parametrize("n", LENGTHS)
def test_fourstep_matches_numpy(n, inverse):
    x = _signal((3, n))
    got = np.asarray(fourstep.fft(jnp.asarray(x), inverse=inverse))
    assert got.dtype == np.complex64
    ref = (np.fft.ifft if inverse else np.fft.fft)(x.astype(np.complex128))
    assert _rel(got, ref) <= 1e-5


def test_fourstep_rfft_round_trip_odd_6859():
    """Odd-length R2C pays the full complex transform (no packing)."""
    x = np.random.default_rng(1).standard_normal((2, 6859)).astype(np.float32)
    spec = nd.rfftn(jnp.asarray(x), fourstep.fft, axes=(-1,))
    assert spec.shape == (2, 6859 // 2 + 1)
    assert _rel(np.asarray(spec), np.fft.rfft(x.astype(np.float64))) <= 1e-5
    back = np.asarray(nd.irfftn(spec, (6859,), fourstep.fft, axes=(-1,)))
    assert _rel(back, x) <= 1e-5


@pytest.mark.parametrize("n", (97, 361, 512, 1024, 6859, 16385, 1 << 15,
                               3 ** 9))
def test_levels_count_the_engines_matmuls(n):
    """The planner charges fourstep_levels(n) passes: one per matmul the
    engine traces (a column DFT per level, then the dense row DFT)."""
    jaxpr = jax.make_jaxpr(fourstep.fft)(
        jax.ShapeDtypeStruct((2, n), jnp.complex64))
    dots = sum(e.primitive.name == "dot_general" for e in jaxpr.jaxpr.eqns)
    assert dots == fourstep_levels(n)
