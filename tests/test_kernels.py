"""Pallas kernel tests: interpret=True vs pure-jnp oracles, shape/dtype sweeps."""

import numpy as np
import pytest
import jax.numpy as jnp

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from helpers.accuracy import assert_rel_l2
from repro.fft import nd
from repro.fft.reference import dft_matrix
from repro.kernels.dft_matmul.ref import dft_ref
from repro.kernels.dft_matmul import ops as dft_ops
from repro.kernels.fft4step.ref import fft4step_ref
from repro.kernels.fft4step import ops as fs_ops
from repro.kernels.fft4step.fft4step import fft4step
from repro.kernels.fftconv.ref import fftconv_ref
from repro.kernels.fftconv import ops as conv_ops

RNG = np.random.default_rng(7)


def rc(shape, dtype=np.complex64):
    return (RNG.standard_normal(shape) + 1j * RNG.standard_normal(shape)).astype(dtype)


# --------------------------------------------------------------------------
# dft_matmul
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n", [8, 32, 64, 128])
@pytest.mark.parametrize("b", [1, 5, 64, 300])
@pytest.mark.parametrize("inverse", [False, True])
def test_dft_matmul_kernel_vs_ref(n, b, inverse):
    x = rc((b, n))
    w = dft_matrix(n, inverse=inverse, dtype=jnp.complex128)
    wr = np.real(np.asarray(w)).astype(np.float32)
    wi = np.imag(np.asarray(w)).astype(np.float32)
    xr, xi = np.real(x).copy(), np.imag(x).copy()
    want_r, want_i = dft_ref(jnp.asarray(xr), jnp.asarray(xi), inverse=inverse)
    pad = (-b) % min(8, b) if b < 8 else (-b) % 8
    from repro.kernels.dft_matmul.dft_matmul import dft_matmul
    tile = 8 if b >= 8 else b
    bb = b + ((-b) % tile)
    xr_p = np.pad(xr, ((0, bb - b), (0, 0)))
    xi_p = np.pad(xi, ((0, bb - b), (0, 0)))
    got_r, got_i = dft_matmul(jnp.asarray(xr_p), jnp.asarray(xi_p),
                              jnp.asarray(wr), jnp.asarray(wi),
                              tile_b=tile, interpret=True)
    np.testing.assert_allclose(np.asarray(got_r)[:b], np.asarray(want_r),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got_i)[:b], np.asarray(want_i),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("n", [16, 128])
def test_dft_ops_matches_numpy(n):
    x = rc((3, 7, n))
    got = np.asarray(dft_ops.dft(jnp.asarray(x), interpret=True))
    np.testing.assert_allclose(got, np.fft.fft(x, axis=-1), rtol=1e-3, atol=1e-3)
    got_i = np.asarray(dft_ops.dft(jnp.asarray(x), inverse=True, interpret=True))
    np.testing.assert_allclose(got_i, np.fft.ifft(x, axis=-1), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("n", [361, 375])
def test_dft_engine_above_128_through_fftn(n):
    """The dense DFT at lengths the planner offers it above one lane tile
    (361 = 19^2, 375 = 3 * 5^3), as the planned path runs it: a rank-3
    nd.fftn with the dft engine on every axis, forward and inverse."""
    x = rc((2, n, 3, n))
    axes = (1, 2, 3)

    def engine(v, inverse=False):
        return dft_ops.dft(v, inverse=inverse, interpret=True)

    got = np.asarray(nd.fftn(jnp.asarray(x), engine, axes=axes))
    assert_rel_l2(got, np.fft.fftn(x.astype(np.complex128), axes=axes),
                  what=f"dft fftn n={n}")
    got_i = np.asarray(nd.fftn(jnp.asarray(x), engine, axes=axes,
                               inverse=True))
    assert_rel_l2(got_i, np.fft.ifftn(x.astype(np.complex128), axes=axes),
                  what=f"dft ifftn n={n}")


@pytest.mark.parametrize("n", [400, 512])
def test_dft_ops_above_384_matches_numpy(n):
    """The dense DFT at lengths above 384 (512, the four-chip cell's local
    length, and 400, not a power of two), forward and inverse, on 300 rows:
    several 128-row tiles and a padded tail."""
    x = rc((300, n))
    got = np.asarray(dft_ops.dft(jnp.asarray(x), interpret=True))
    assert_rel_l2(got, np.fft.fft(x.astype(np.complex128), axis=-1),
                  what=f"dft n={n}")
    got_i = np.asarray(dft_ops.dft(jnp.asarray(x), inverse=True,
                                   interpret=True))
    assert_rel_l2(got_i, np.fft.ifft(x.astype(np.complex128), axis=-1),
                  what=f"idft n={n}")


def test_dft_tile_rule():
    """Lengths up to 384 keep the 256-row tile, and the same program as an
    explicit tile_b=256; above 384 the tile is 128 rows."""
    import functools

    import jax
    from repro.kernels.dft_matmul.dft_matmul import default_tile_b

    assert [default_tile_b(n) for n in (8, 128, 361, 384, 385, 400, 512)] \
        == [256] * 4 + [128] * 3
    x = jnp.asarray(rc((600, 361)))

    def jaxpr(tile_b):
        return str(jax.make_jaxpr(functools.partial(
            dft_ops.dft, interpret=True, tile_b=tile_b))(x))

    assert jaxpr(None) == jaxpr(256)


# --------------------------------------------------------------------------
# fft4step
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n1,n2", [(4, 4), (8, 16), (32, 32), (128, 128), (64, 128)])
@pytest.mark.parametrize("inverse", [False, True])
def test_fft4step_kernel_vs_ref(n1, n2, inverse):
    b = 8
    xr = RNG.standard_normal((b, n1, n2)).astype(np.float32)
    xi = RNG.standard_normal((b, n1, n2)).astype(np.float32)
    want_r, want_i = fft4step_ref(jnp.asarray(xr), jnp.asarray(xi), n1, n2, inverse)

    from repro.fft.reference import twiddles
    f32 = lambda z: (np.real(np.asarray(z)).astype(np.float32),
                     np.imag(np.asarray(z)).astype(np.float32))
    w1r, w1i = f32(dft_matrix(n1, inverse=inverse, dtype=jnp.complex128))
    w2r, w2i = f32(dft_matrix(n2, inverse=inverse, dtype=jnp.complex128))
    tr, ti = f32(twiddles(n1, n2, inverse=inverse, dtype=jnp.complex128))
    got_r, got_i = fft4step(jnp.asarray(xr), jnp.asarray(xi),
                            jnp.asarray(w1r), jnp.asarray(w1i),
                            jnp.asarray(w2r), jnp.asarray(w2i),
                            jnp.asarray(tr), jnp.asarray(ti),
                            n1=n1, n2=n2, tile_b=4, interpret=True)
    tol = 1e-3 * np.sqrt(n1 * n2)
    np.testing.assert_allclose(np.asarray(got_r), np.asarray(want_r), rtol=1e-3, atol=tol)
    np.testing.assert_allclose(np.asarray(got_i), np.asarray(want_i), rtol=1e-3, atol=tol)


@pytest.mark.parametrize("n", [64, 256, 1024, 4096, 16384])
def test_fft4step_ops_matches_numpy(n):
    x = rc((4, n))
    got = np.asarray(fs_ops.fft(jnp.asarray(x), interpret=True))
    want = np.fft.fft(x, axis=-1)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3 * np.sqrt(n))


@pytest.mark.parametrize("n", [256, 16384])
def test_fft4step_ops_roundtrip(n):
    x = rc((2, n))
    y = fs_ops.fft(jnp.asarray(x), interpret=True)
    back = np.asarray(fs_ops.fft(y, inverse=True, interpret=True))
    np.testing.assert_allclose(back, x, rtol=2e-3, atol=2e-3)


def test_fft4step_factor_choice():
    assert fs_ops.choose_factors(16384) == (128, 128)
    assert fs_ops.choose_factors(4096) == (64, 64)
    n1, n2 = fs_ops.choose_factors(8192)
    assert n1 * n2 == 8192 and n1 <= 128 and n2 <= 128
    with pytest.raises(ValueError):
        fs_ops.choose_factors(2 ** 20)


# --------------------------------------------------------------------------
# fused fftconv
# --------------------------------------------------------------------------
@pytest.mark.parametrize("c,b,L,K", [(2, 4, 100, 5), (1, 1, 512, 64),
                                     (3, 2, 1000, 24), (2, 8, 8000, 128)])
def test_fftconv_kernel_vs_ref(c, b, L, K):
    x = RNG.standard_normal((c, b, L)).astype(np.float32)
    h = RNG.standard_normal((c, K)).astype(np.float32) / np.sqrt(K)
    n = conv_ops._next_square_pow2(L + K - 1)
    want = np.asarray(fftconv_ref(jnp.asarray(x), jnp.asarray(h), n))
    got = np.asarray(conv_ops.fftconv(jnp.asarray(x), jnp.asarray(h), interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3 * np.sqrt(L))


def test_fftconv_is_causal_linear_conv():
    c, b, L, K = 1, 1, 64, 8
    x = RNG.standard_normal((c, b, L)).astype(np.float32)
    h = RNG.standard_normal((c, K)).astype(np.float32)
    got = np.asarray(conv_ops.fftconv(jnp.asarray(x), jnp.asarray(h), interpret=True))
    want = np.zeros((L,), np.float32)
    for t in range(L):
        for s in range(K):
            if t - s >= 0:
                want[t] += h[0, s] * x[0, 0, t - s]
    np.testing.assert_allclose(got[0, 0], want, rtol=1e-3, atol=1e-3)


@settings(max_examples=10, deadline=None)
@given(logn=st.sampled_from([6, 8, 10]), seed=st.integers(0, 2**31 - 1),
       inverse=st.booleans())
def test_property_fft4step_matches_numpy(logn, seed, inverse):
    n = 2 ** logn
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((2, n)) + 1j * rng.standard_normal((2, n))).astype(np.complex64)
    got = np.asarray(fs_ops.fft(jnp.asarray(x), inverse=inverse, interpret=True))
    want = np.fft.ifft(x, axis=-1) if inverse else np.fft.fft(x, axis=-1)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3 * np.sqrt(n))
