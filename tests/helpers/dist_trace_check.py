"""Subprocess helper: the spans and executable names of one distributed
client, on four fake host devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \\
        python tests/helpers/dist_trace_check.py slab|pencil|dist1d <trace dir>

Builds ``DistFFTNDClient`` at 32^3 C2C with ``dist_backend`` forced to
``slab`` or ``pencil`` (or ``DistFFT1DClient`` at 1024), runs one forward
and one inverse under the profiler, then once more for the check against
``bench/reference.py``'s float64 forward.  Prints one JSON line: the span
table, the traced spans and modules, the executable names and the two
relative L2 errors.
"""

import glob
import json
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax                      # noqa: E402
import numpy as np              # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench"))

import reference                # noqa: E402
from repro.core import trace    # noqa: E402
from repro.core.client import Context, Problem  # noqa: E402
from repro.core.clients.dist_fft import (DistFFT1DClient,  # noqa: E402
                                         DistFFTNDClient)
from repro.fft import distributed as dist  # noqa: E402


def build(backend: str):
    if backend == "dist1d":
        problem = Problem((1024,), "Outplace_Complex", "float")
        ctx = Context()
        ctx.create()
        return DistFFT1DClient(problem, ctx)
    problem = Problem((32, 32, 32), "Outplace_Complex", "float")
    ctx = Context({"dist_backend": backend})
    ctx.create()
    return DistFFTNDClient(problem, ctx)


def traced(path: str):
    """``(modules, spans)`` of the one trace under ``path``."""
    from jax.profiler import ProfileData

    (pb,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    modules, spans = set(), []
    for plane in ProfileData.from_file(pb).planes:
        for line in plane.lines:
            for ev in line.events:
                st = {k: v for k, v in ev.stats}
                if "hlo_module" in st and not ev.name.startswith("end:"):
                    modules.add(st["hlo_module"])
                if ev.name in trace.SPANS:
                    spans.append([ev.name, st.get("exe"), st.get("seq")])
    return sorted(modules), spans


def main(backend: str, trace_dir: str) -> None:
    trace.reset_counters()
    c = build(backend)
    p = c.problem
    c.allocate()
    c.init_forward()
    c.init_inverse()
    rng = np.random.default_rng(7)
    shape = (p.batch, *p.extents)
    x = (rng.standard_normal(shape)
         + 1j * rng.standard_normal(shape)).astype(np.complex64)
    c.upload(x)
    counters = {k: v[0] for k, v in trace.counters().items()}

    jax.profiler.start_trace(trace_dir)
    try:
        c.execute_forward()
        c.execute_inverse()
    finally:
        jax.profiler.stop_trace()
    modules, spans = traced(trace_dir)

    c.upload(x)
    c.execute_forward()
    got = np.asarray(c._spec)
    c.execute_inverse()
    back = np.asarray(c._buf)
    if backend == "dist1d":
        # the forward's spectrum is in transposed order, k = k1 + k2*n1
        _, (n1, n2) = dist.make_fft1d(c._mesh, "data", p.extents[0])
        got = np.asarray(dist.transposed_to_natural(got, n1, n2))
    fwd = float(reference.rel_l2_rows(
        got.reshape(1, -1),
        reference.forward(x, p.rank, False).reshape(1, -1)).max())
    inv = float(reference.rel_l2_rows(back.reshape(1, -1),
                                      x.reshape(1, -1)).max())
    c.destroy()
    print(json.dumps({"counters": counters, "modules": modules,
                      "spans": spans, "fwd_name": c._fwd_name,
                      "inv_name": c._inv_name, "fwd_rel_l2": fwd,
                      "inv_rel_l2": inv}))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
