"""Subprocess helper: validate distributed FFTs on fake host devices.

Run as:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         PYTHONPATH=src python tests/helpers/dist_fft_check.py
Exits 0 on success; prints the failing check otherwise.

``... dist_fft_check.py conformance`` instead sweeps the distributed
conformance cells (decomposition x kind x rank, planned local engines,
natural order, forward differential + roundtrip) over however many devices
the process was forced to — the distributed extension of
test_conformance.py's matrix.
"""

import os
import sys
import zlib

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np              # noqa: E402
import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.fft import distributed as dist  # noqa: E402


def check_1d_single_axis():
    mesh = jax.make_mesh((8,), ("data",))
    n = 4096
    rng = np.random.default_rng(0)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(np.complex64)
    xd = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data")))
    fn, (n1, n2) = dist.make_fft1d(mesh, "data", n)
    with mesh:
        y = np.asarray(fn(xd))
    got = np.asarray(dist.transposed_to_natural(jnp.asarray(y), n1, n2))
    want = np.fft.fft(x)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3 * np.sqrt(n))
    # inverse round trip: inverse on transposed layout with swapped factors
    fn_inv, _ = dist.make_fft1d(mesh, "data", n, inverse=True)
    print("  1d single-axis ok")


def check_1d_multi_axis():
    mesh = jax.make_mesh((2, 4), ("pod", "data"))
    n = 2048
    rng = np.random.default_rng(1)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(np.complex64)
    xd = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(("pod", "data"))))
    fn, (n1, n2) = dist.make_fft1d(mesh, ("pod", "data"), n)
    with mesh:
        y = np.asarray(fn(xd))
    got = np.asarray(dist.transposed_to_natural(jnp.asarray(y), n1, n2))
    np.testing.assert_allclose(got, np.fft.fft(x), rtol=2e-3, atol=2e-3 * np.sqrt(n))
    print("  1d multi-axis ok")


def check_3d():
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    shape = (16, 8, 32)
    rng = np.random.default_rng(2)
    x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
    sh = NamedSharding(mesh, P("data", "model", None))
    xd = jax.device_put(jnp.asarray(x), sh)
    fn = dist.make_fft3d(mesh, "data", "model", shape)
    with mesh:
        y = np.asarray(fn(xd))
    want = np.fft.fftn(x)
    np.testing.assert_allclose(y, want, rtol=2e-3, atol=2e-3 * np.sqrt(np.prod(shape)))
    # inverse roundtrip through the canonical layout
    fn_inv = dist.make_fft3d(mesh, "data", "model", shape, inverse=True)
    with mesh:
        back = np.asarray(fn_inv(jax.device_put(jnp.asarray(y), sh)))
    np.testing.assert_allclose(back, x, rtol=2e-3, atol=2e-3)
    print("  3d pencil ok (+roundtrip)")


def check_3d_transposed():
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    shape = (8, 8, 16)
    rng = np.random.default_rng(3)
    x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
    xd = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data", "model", None)))
    fn = dist.make_fft3d(mesh, "data", "model", shape, keep_transposed=True)
    with mesh:
        y = np.asarray(fn(xd))
    np.testing.assert_allclose(y, np.fft.fftn(x), rtol=2e-3,
                               atol=2e-3 * np.sqrt(np.prod(shape)))
    print("  3d transposed-out ok")


def check_3d_multipod():
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    shape = (16, 8, 8)
    rng = np.random.default_rng(4)
    x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
    sh = NamedSharding(mesh, P(("pod", "data"), "model", None))
    xd = jax.device_put(jnp.asarray(x), sh)
    fn = dist.make_fft3d(mesh, ("pod", "data"), "model", shape)
    with mesh:
        y = np.asarray(fn(xd))
    np.testing.assert_allclose(y, np.fft.fftn(x), rtol=2e-3,
                               atol=2e-3 * np.sqrt(np.prod(shape)))
    print("  3d multi-pod axes ok")


def _rel_l2(got, want):
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


def check_1d_natural_roundtrip():
    """Satellite: the inverse consumes natural order symmetrically — pinned
    c64 (and, below, c128) round-trip tolerances without any host-side
    reordering in either direction."""
    from repro.launch.mesh import flat_mesh

    mesh = flat_mesh()
    n, p = 4096, jax.device_count()
    rng = np.random.default_rng(10)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(np.complex64)
    sh = NamedSharding(mesh, P("data"))
    xd = jax.device_put(jnp.asarray(x), sh)
    fwd, _ = dist.make_fft1d(mesh, "data", n, natural=True)
    inv, _ = dist.make_ifft1d(mesh, "data", n, natural=True)
    y = fwd(xd)
    assert _rel_l2(np.asarray(y), np.fft.fft(x)) < 1e-5   # already natural
    back = np.asarray(inv(jax.device_put(y, sh)))
    assert _rel_l2(back, x) < 1e-5, _rel_l2(back, x)
    # transposed layout roundtrips too (the default cheap path)
    fwd_t, _ = dist.make_fft1d(mesh, "data", n)
    inv_t, _ = dist.make_ifft1d(mesh, "data", n)
    back = np.asarray(inv_t(jax.device_put(fwd_t(xd), sh)))
    assert _rel_l2(back, x) < 1e-5, _rel_l2(back, x)
    print(f"  1d natural+transposed roundtrip ok (p={p})")


def check_1d_roundtrip_c128():
    """Double precision pins the asymmetry fix at c128 tolerance.  Runs
    LAST: enabling x64 affects constant dtypes in later traces."""
    jax.config.update("jax_enable_x64", True)
    from repro.launch.mesh import flat_mesh

    mesh = flat_mesh()
    n = 4096
    rng = np.random.default_rng(11)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(np.complex128)
    sh = NamedSharding(mesh, P("data"))
    xd = jax.device_put(jnp.asarray(x), sh)
    for natural in (False, True):
        fwd, _ = dist.make_fft1d(mesh, "data", n, natural=natural)
        inv, _ = dist.make_ifft1d(mesh, "data", n, natural=natural)
        back = np.asarray(inv(jax.device_put(fwd(xd), sh)))
        assert back.dtype == np.complex128
        assert _rel_l2(back, x) < 1e-12, (natural, _rel_l2(back, x))
    print("  1d c128 roundtrip ok (both layouts)")


def _cell_mesh(backend, mesh_shape):
    from repro.launch.mesh import flat_mesh, reshaped_mesh

    names = ("d0", "d1")[:len(mesh_shape)]
    return reshaped_mesh(flat_mesh(), mesh_shape, names)


def check_conformance_cells():
    """The distributed conformance matrix: every (decomposition, kind, rank)
    cell dist_supports claims on this host's mesh, run through planner-
    selected local engines with natural-order output — forward differential
    against numpy + inverse roundtrip, exactly like check_cell for the
    single-device backends.  Real kinds must claim nothing."""
    from repro.core.client import KINDS, Problem
    from repro.core.candidates import (Candidate, DIST_BACKENDS,
                                       _pencil_mesh_shapes, dist_supports)
    from repro.core.clients.dist_fft import dist_engines

    p_dev = jax.device_count()
    probes = {1: (1024,), 2: (16, 16), 3: (8, 8, 16)}
    cells, refused = [], 0
    for backend in DIST_BACKENDS:
        for rank, ext in sorted(probes.items()):
            for kind in KINDS:
                problem = Problem(ext, kind, "float")
                shapes = ([(p_dev,)] if backend != "pencil"
                          else _pencil_mesh_shapes(p_dev))
                shape = shapes[0] if shapes else (p_dev,)
                if dist_supports(backend, problem, shape):
                    cells.append((backend, problem, shape))
                else:
                    refused += 1
                    assert "Complex" not in kind or (
                        (backend, rank) not in
                        {("dist1d", 1), ("slab", 2), ("slab", 3),
                         ("pencil", 3)}), (backend, kind, rank)
    # every complex kind x claimed rank is a cell; no real kind ever is
    assert len(cells) == 8, [c[:1] + (c[1].signature(),) for c in cells]
    assert all(c[1].complex_input for c in cells)

    done = set()
    for backend, problem, mesh_shape in cells:
        key = (backend, problem.extents)    # kinds share the transform math
        if key in done:
            continue
        done.add(key)
        mesh = _cell_mesh(backend, mesh_shape)
        cand = Candidate(backend, mesh=mesh_shape)
        engines = dist_engines(problem, cand)
        rng = np.random.default_rng(zlib.crc32(repr(key).encode()))
        x = (rng.standard_normal(problem.extents)
             + 1j * rng.standard_normal(problem.extents)).astype(np.complex64)
        if backend == "dist1d":
            n = problem.extents[0]
            fwd, _ = dist.make_fft1d(mesh, "d0", n, natural=True,
                                     engines=engines)
            inv, _ = dist.make_ifft1d(mesh, "d0", n, natural=True,
                                      engines=engines)
            sh_in = sh_out = NamedSharding(mesh, P("d0"))
            xb = x
        else:
            if backend == "slab":
                fwd, in_spec, out_spec = dist.make_slab_fftnd(
                    mesh, "d0", problem.extents, natural=True,
                    engines=engines)
                inv, _, _ = dist.make_slab_fftnd(
                    mesh, "d0", problem.extents, inverse=True, natural=True,
                    engines=engines)
            else:
                fwd, in_spec, out_spec = dist.make_pencil_fftnd(
                    mesh, "d0", "d1", problem.extents, natural=True,
                    engines=engines)
                inv, _, _ = dist.make_pencil_fftnd(
                    mesh, "d0", "d1", problem.extents, inverse=True,
                    natural=True, engines=engines)
            sh_in = NamedSharding(mesh, in_spec)
            sh_out = NamedSharding(mesh, out_spec)
            xb = x[None]                    # (batch=1, *extents)
        xd = jax.device_put(jnp.asarray(xb), sh_in)
        y = fwd(xd)
        want = np.fft.fft(x) if problem.rank == 1 else np.fft.fftn(x)
        got = np.asarray(y).reshape(want.shape)
        assert _rel_l2(got, want) < 1e-3, \
            (backend, problem.signature(), _rel_l2(got, want))
        back = np.asarray(inv(jax.device_put(y, sh_out))).reshape(x.shape)
        assert _rel_l2(back, x) < 1e-3, \
            (backend, problem.signature(), _rel_l2(back, x))
        print(f"  cell {backend}[{'x'.join(map(str, mesh_shape))}] "
              f"{problem.signature()} ok")
    print(f"ALL {len(done)} DISTRIBUTED CONFORMANCE CELLS PASSED "
          f"({len(cells)} kind cells, {refused} refused)")


if __name__ == "__main__":
    if "conformance" in sys.argv[1:]:
        assert jax.device_count() >= 4, \
            f"need >= 4 host devices, got {jax.device_count()}"
        check_conformance_cells()
        sys.exit(0)
    assert jax.device_count() == 8, f"need 8 host devices, got {jax.device_count()}"
    check_1d_single_axis()
    check_1d_multi_axis()
    check_3d()
    check_3d_transposed()
    check_3d_multipod()
    check_1d_natural_roundtrip()
    check_conformance_cells()
    check_1d_roundtrip_c128()
    print("ALL DISTRIBUTED CHECKS PASSED")
