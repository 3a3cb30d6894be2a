"""Chirp-Z regression tests: the dtype-downcast bugfix (f64 input must stay
double precision), the host-side (n, dtype, direction) table cache (the
second un-jitted call does no host trig work), and the fused Pallas engine
selection behind the planner's ``chirpz_pallas`` backend."""

import numpy as np
import pytest
import jax.numpy as jnp

from helpers.accuracy import assert_rel_l2, rel_l2
from repro.fft import bluestein

RNG = np.random.default_rng(57)

ODD = 361  # 19^2: the paper's oddshape class


def rc(shape, dtype=np.complex64):
    return (RNG.standard_normal(shape) +
            1j * RNG.standard_normal(shape)).astype(dtype)


# --------------------------------------------------------------------------
# dtype mapping (the downcast bug): f32 -> c64, f64 -> c128
# --------------------------------------------------------------------------
def test_real_f64_input_keeps_double_precision():
    """Regression: float64 real data used to silently cast to complex64,
    losing double precision on every oddshape transform."""
    x = RNG.standard_normal(ODD)                       # float64
    y = bluestein.fft(jnp.asarray(x))
    assert y.dtype == jnp.complex128
    # and it is double-precision *accurate*, not just double-width
    assert_rel_l2(np.asarray(y), np.fft.fft(x), "double",
                  "c128 chirp-Z on an oddshape length")


def test_real_f32_input_maps_to_c64():
    x = RNG.standard_normal(ODD).astype(np.float32)
    y = bluestein.fft(jnp.asarray(x))
    assert y.dtype == jnp.complex64
    assert rel_l2(y, np.fft.fft(x.astype(np.float64))) < 1e-3


def test_complex_dtypes_pass_through():
    assert bluestein.fft(jnp.asarray(rc((4,)))).dtype == jnp.complex64
    assert bluestein.fft(
        jnp.asarray(rc((4,), np.complex128))).dtype == jnp.complex128


# --------------------------------------------------------------------------
# table cache: no host trig work on the second call
# --------------------------------------------------------------------------
def test_second_call_does_no_host_trig_work(monkeypatch):
    calls = []
    real_build = bluestein._build_tables

    def counting_build(n, m, dtype, inverse):
        calls.append((n, m, jnp.dtype(dtype).name, inverse))
        return real_build(n, m, dtype, inverse)

    monkeypatch.setattr(bluestein, "_build_tables", counting_build)
    bluestein._TABLES.clear()
    x = jnp.asarray(rc((2, 45)))
    y1 = bluestein.fft(x)            # un-jitted: builds the (45, c64) table
    y2 = bluestein.fft(x)            # cache hit: NO host trig work
    assert calls == [(45, 128, "complex64", False)]
    assert rel_l2(y1, y2) == 0.0
    # a new direction / dtype each build exactly one new entry
    bluestein.fft(y1, inverse=True)
    bluestein.fft(x.astype(jnp.complex128))
    bluestein.fft(x.astype(jnp.complex128))
    assert calls == [(45, 128, "complex64", False),
                     (45, 128, "complex64", True),
                     (45, 128, "complex128", False)]


def test_table_cache_is_bounded():
    """An unbounded cache of near-cap chirp tables would grow host RSS by
    hundreds of MB per distinct length; eviction keeps it capped."""
    bluestein._TABLES.clear()
    for n in range(20, 20 + bluestein._TABLES_MAX + 5):
        bluestein.chirp_tables(n, 64, jnp.complex64)
    assert len(bluestein._TABLES) == bluestein._TABLES_MAX
    # oldest entries were evicted, newest survive
    assert (20, 64, "complex64", False) not in bluestein._TABLES
    assert (20 + bluestein._TABLES_MAX + 4, 64, "complex64", False) \
        in bluestein._TABLES
    bluestein._TABLES.clear()


def test_cached_tables_are_host_arrays():
    """The cache must hold numpy arrays: a device value captured while
    tracing a jit would leak a tracer into every later call."""
    bluestein._TABLES.clear()
    import jax
    jax.jit(bluestein.fft)(jnp.asarray(rc((2, 19))))
    assert bluestein._TABLES
    for c, fb in bluestein._TABLES.values():
        assert isinstance(c, np.ndarray) and isinstance(fb, np.ndarray)


# --------------------------------------------------------------------------
# engine resolution + smooth-m padding
# --------------------------------------------------------------------------
def test_pallas_engine_pads_to_smooth_m_not_pow2():
    """The mixed-radix kernel convolves at the smallest 7-smooth m >= 2n-1
    — 729 = 3^6 for n=361 instead of pow2 1024 — the pow2-only engines
    keep next_pow2."""
    assert bluestein.resolve_engine(361, "stockham_pallas") == \
        ("stockham_pallas", 729)
    assert bluestein.resolve_engine(361, "stockham") == ("stockham", 1024)
    assert bluestein.resolve_engine(18432, "stockham_pallas") == \
        ("stockham_pallas", 36864)          # vs pow2 65536: 1.78x tighter
    # auto on hardware takes the fused kernel + smooth pad; interpret mode
    # (off-TPU conformance, the default here) keeps the staged jnp engine
    assert bluestein.resolve_engine(361, "auto", interpret=False) == \
        ("stockham_pallas", 729)
    assert bluestein.resolve_engine(361, "auto", interpret=True) == \
        ("stockham", 1024)
    assert bluestein.resolve_engine(361, "auto") == ("stockham", 1024)
    # on hardware, past the six-step cap there is no fused engine, and auto
    # refuses rather than falling back to the staged jnp engine
    with pytest.raises(ValueError, match="cap"):
        bluestein.resolve_engine((1 << 23) + 1, "auto", interpret=False)
    # numerics hold at the tighter (non-pow2) padded length
    x = rc((2, 361))
    got = bluestein.fft(jnp.asarray(x), engine="stockham_pallas",
                        interpret=True)
    assert rel_l2(got, np.fft.fft(x, axis=-1)) < 1e-3


# --------------------------------------------------------------------------
# fused Pallas engines (the chirpz_pallas backend's knob space)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("engine", ["stockham_pallas", "sixstep", "auto"])
@pytest.mark.parametrize("n", [19, 100, ODD])
def test_fused_engines_match_numpy(engine, n):
    x = rc((2, n))
    got = bluestein.fft(jnp.asarray(x), engine=engine, interpret=True)
    assert rel_l2(got, np.fft.fft(x, axis=-1)) < 1e-3
    back = bluestein.fft(got, inverse=True, engine=engine, interpret=True)
    assert rel_l2(back, x) < 1e-3


def test_fused_engine_c128_oddshape():
    x = rc((2, ODD), np.complex128)
    got = bluestein.fft(jnp.asarray(x), engine="auto", interpret=True)
    assert np.asarray(got).dtype == np.complex128
    assert rel_l2(got, np.fft.fft(x, axis=-1)) < 1e-8


def test_unknown_engine_rejected():
    with pytest.raises(ValueError, match="chirp engine"):
        bluestein.fft(jnp.asarray(rc((2, 5))), engine="fftw")
