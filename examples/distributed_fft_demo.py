"""Distributed pencil-FFT demo on 8 simulated devices: the pod-scale FFT
path of DESIGN.md §2, validated against numpy.

Re-execs itself with XLA_FLAGS so the host presents 8 devices.  CPU only:
the re-exec pins JAX to the CPU backend, so on a TPU host the demo never
claims the chips (the four-chip path on hardware is
``python chip_smoke.py --chips 4``).

  PYTHONPATH=src python examples/distributed_fft_demo.py
"""

import os
import sys

if os.environ.get("XLA_FLAGS", "").find("host_platform_device_count") < 0:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import numpy as np                                    # noqa: E402
import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.fft import distributed as dist             # noqa: E402
from repro.launch.mesh import make_mesh               # noqa: E402


def main() -> None:
    mesh = make_mesh((2, 4), ("data", "model"))
    shape = (32, 16, 64)
    rng = np.random.default_rng(0)
    x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
         ).astype(np.complex64)
    xd = jax.device_put(jnp.asarray(x),
                        NamedSharding(mesh, P("data", "model", None)))
    fft3d = dist.make_fft3d(mesh, "data", "model", shape)
    with mesh:
        y = fft3d(xd)
    err = np.abs(np.asarray(y) - np.fft.fftn(x)).max()
    print(f"3D pencil FFT {shape} on mesh {dict(mesh.shape)}: "
          f"max |err| = {err:.2e}")
    print("per-device shards:", xd.sharding.shard_shape(xd.shape))

    n = 1 << 14
    x1 = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(np.complex64)
    mesh1 = make_mesh((8,), ("data",))
    x1d = jax.device_put(jnp.asarray(x1), NamedSharding(mesh1, P("data")))
    fft1d, (n1, n2) = dist.make_fft1d(mesh1, "data", n)
    with mesh1:
        y1 = fft1d(x1d)
    nat = np.asarray(dist.transposed_to_natural(jnp.asarray(y1), n1, n2))
    err1 = np.abs(nat - np.fft.fft(x1)).max() / np.abs(np.fft.fft(x1)).max()
    print(f"1D distributed four-step n={n} (n1={n1}, n2={n2}): "
          f"rel err = {err1:.2e} (transposed-out layout)")

    # transposed-in inverse: round trip without any reordering pass
    ifft1d, _ = dist.make_ifft1d(mesh1, "data", n)
    with mesh1:
        xr = ifft1d(y1)
    err2 = np.abs(np.asarray(xr) - x1).max()
    print(f"1D inverse (TRANSPOSED_IN): roundtrip err = {err2:.2e}")

    # the same path measured through the declarative Suite API
    from repro.core.suite import Session, SuiteSpec                   # noqa: E402

    spec = SuiteSpec(clients=("DistFFT1D",), extents=("4096",),
                     kinds=("Outplace_Complex",), precisions=("float",),
                     warmups=1, repetitions=3, output=None, verbose=True)
    results = Session().run(spec)
    for (lib, ext, prec, kind, rigor, op, mean, sd, cnt) in \
            results.aggregate(op="execute_forward"):
        print(f"{lib} n={ext} on 8 devices: execute_forward "
              f"{mean*1e3:.1f} us (n={cnt})")
    stats = results.plan_stats
    print(f"plan cache: {stats.hits} hits, {stats.misses} misses")


if __name__ == "__main__":
    main()
