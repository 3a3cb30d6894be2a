"""fft2_pallas kernel: interpret-mode numerics vs the pure-jnp oracle and
numpy, knob sweeps, batching/padding, and the VMEM feasibility cap.  (The
backend x kind x precision x rank sweep lives in test_conformance.py; this
module isolates the fused-kernel lowering itself.)"""

import numpy as np
import pytest
import jax.numpy as jnp

from helpers.accuracy import rel_l2
from repro.kernels.fft2_pallas import ops as f2_ops
from repro.kernels.fft2_pallas.ref import fft2_ref

RNG = np.random.default_rng(43)


def rc(shape, dtype=np.complex64):
    return (RNG.standard_normal(shape) +
            1j * RNG.standard_normal(shape)).astype(dtype)


# --------------------------------------------------------------------------
# kernel vs oracle vs numpy
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n1,n2", [(2, 2), (4, 16), (16, 4), (32, 64),
                                   (1, 16), (16, 1)])
@pytest.mark.parametrize("inverse", [False, True])
def test_kernel_matches_ref_and_numpy(n1, n2, inverse):
    x = rc((3, n1, n2))
    want_np = (np.fft.ifft2(x, axes=(-2, -1)) if inverse
               else np.fft.fft2(x, axes=(-2, -1)))
    ref = fft2_ref(jnp.asarray(x), inverse=inverse)
    got = f2_ops.fft2(jnp.asarray(x), inverse=inverse, interpret=True)
    assert rel_l2(ref, want_np) < 1e-3
    assert rel_l2(got, want_np) < 1e-3
    assert rel_l2(got, ref) < 1e-3


@pytest.mark.parametrize("radix", [2, 4, 8])
def test_radix_knob(radix):
    x = rc((2, 16, 32))
    got = f2_ops.fft2(jnp.asarray(x), radix=radix, interpret=True)
    assert rel_l2(got, np.fft.fft2(x, axes=(-2, -1))) < 1e-3


@pytest.mark.parametrize("batch,tile_b", [((1,), None), ((5,), 2),
                                          ((2, 3), 4), ((7,), 8)])
def test_batching_and_padding(batch, tile_b):
    """Batch tiles that don't divide the batch are padded by ops.fft2."""
    x = rc((*batch, 8, 16))
    got = f2_ops.fft2(jnp.asarray(x), tile_b=tile_b, interpret=True)
    assert got.shape == x.shape
    assert rel_l2(got, np.fft.fft2(x, axes=(-2, -1))) < 1e-3


def test_double_precision():
    x = rc((2, 16, 16), dtype=np.complex128)
    got = f2_ops.fft2(jnp.asarray(x), interpret=True)
    assert got.dtype == jnp.complex128
    assert rel_l2(got, np.fft.fft2(x, axes=(-2, -1))) < 1e-12


def test_roundtrip():
    x = rc((4, 32, 32))
    y = f2_ops.fft2(jnp.asarray(x), interpret=True)
    back = f2_ops.fft2(y, inverse=True, interpret=True)
    assert rel_l2(back, x) < 1e-3


# --------------------------------------------------------------------------
# feasibility contract
# --------------------------------------------------------------------------
def test_rejects_non_pow2_and_oversize():
    with pytest.raises(ValueError):
        f2_ops.fft2(jnp.zeros((3, 12, 16), jnp.complex64), interpret=True)
    with pytest.raises(ValueError):
        f2_ops.fft2(jnp.zeros((1, 1024, 1024), jnp.complex64), interpret=True)
    with pytest.raises(ValueError):
        f2_ops.fft2(jnp.zeros((16,), jnp.complex64), interpret=True)


def test_cap_matches_planner_constant():
    from repro.core.backends import FFT2_PALLAS_MAX_ELEMS
    assert f2_ops.MAX_ELEMS == FFT2_PALLAS_MAX_ELEMS


def test_engine_rejects_wrong_rank_loudly():
    """A pinned Fft2Pallas client forced onto a rank-1/3 problem must fail
    at build time — fft2 over the last two axes of a (batch, n) array would
    transform the batch axis and return correct-shaped wrong math."""
    from repro.core.client import Problem
    from repro.core.candidates import Candidate
    from repro.core.clients.jax_fft import build_forward, build_inverse
    for ext in [(1024,), (8, 8, 8)]:
        with pytest.raises(ValueError, match="rank-2 only"):
            build_forward(Problem(ext, "Outplace_Complex"),
                          Candidate("fft2_pallas"))
        with pytest.raises(ValueError, match="rank-2 only"):
            build_inverse(Problem(ext, "Outplace_Complex"),
                          Candidate("fft2_pallas"))
