"""The program's tracing module: executable names, spans and their table,
and what a CPU profiler trace of a planned client holds."""

from __future__ import annotations

import glob
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import trace
from repro.core.candidates import Candidate
from repro.core.client import Context, Problem
from repro.core.clients import jax_fft
from repro.fft import distributed as dist

IDENT = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")

# (problem extents, candidate, family)
CASES = {
    "xla": ((4096,), Candidate("xla"), "xla"),
    "fourstep_pallas": ((4096,), Candidate("fourstep_pallas",
                                           (("tile_b", 8),)), "pallas"),
    "dft": ((64,), Candidate("dft"), "pallas"),
    "nd": ((64, 4096), Candidate("nd", axes=(
        Candidate("stockham"), Candidate("fourstep_pallas",
                                         (("tile_b", 8),)))), "pallas"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_executable_name_is_deterministic_and_safe(case):
    extents, cand, family = CASES[case]
    names = set()
    for kind in ("Outplace_Complex", "Outplace_Real"):
        problem = Problem(extents, kind, "float", batch=16)
        for direction in ("fwd", "inv"):
            name = trace.executable_name(problem, cand, direction)
            # the same inputs give the same name, with no id or hash in it
            again = trace.executable_name(
                Problem(extents, kind, "float", batch=16), cand, direction)
            assert name == again
            assert IDENT.fullmatch(name), name
            assert name.startswith(f"fft_{family}_")
            tail = "x".join(map(str, extents))
            kind_tag = "c2c" if kind.endswith("Complex") else "r2c"
            assert name.endswith(f"_{tail}_b16_{kind_tag}_f32_op_{direction}")
            names.add(name)
    assert len(names) == 4   # distinct per direction and kind


def test_executable_name_spells_out_the_plan():
    problem = Problem((4096,), "Outplace_Complex", "float", batch=16)
    assert trace.executable_name(problem, Candidate("xla"), "fwd") \
        == "fft_xla_xla_4096_b16_c2c_f32_op_fwd"
    cand = Candidate("fourstep_pallas", (("tile_b", 8),))
    assert trace.executable_name(problem, cand, "inv") \
        == "fft_pallas_fourstep_pallas_tile_b_8__4096_b16_c2c_f32_op_inv"
    # precision and placement are part of the name
    inplace = Problem((4096,), "Inplace_Complex", "double", batch=16)
    assert trace.executable_name(inplace, Candidate("xla"), "fwd") \
        == "fft_xla_xla_4096_b16_c2c_f64_ip_fwd"
    jnp_only = Candidate("nd", axes=(Candidate("stockham"),
                                     Candidate("fourstep")))
    assert trace.family(Problem((8, 8)), jnp_only) == "jnp"
    with pytest.raises(ValueError):
        trace.executable_name(problem, cand, "forward")


def test_named_sets_the_module_name():
    problem = Problem((64,), "Outplace_Complex", "float", batch=2)
    fn = jax_fft.build_forward(problem, Candidate("xla"))
    text = fn.lower(jax.ShapeDtypeStruct((2, 64), np.complex64)).as_text()
    assert "module @jit_fft_xla_xla_64_b2_c2c_f32_op_fwd" in text
    wrapped = trace.named(lambda x: x + 1, "fft_plain")
    assert wrapped.__name__ == wrapped.__qualname__ == "fft_plain"
    assert wrapped(1) == 2


def test_distributed_executables_are_named():
    mesh = Mesh(np.array(jax.devices()[:1]), ("d0",))
    fn, _, _ = dist.make_slab_fftnd(mesh, "d0", (8, 16))
    x = jax.ShapeDtypeStruct((1, 8, 16), np.complex64)
    assert "module @jit_fft_slab1_8x16_tr_fwd" in fn.lower(x).as_text()
    fn, _ = dist.make_ifft1d(mesh, "d0", 64, natural=True)
    y = jax.ShapeDtypeStruct((64,), np.complex64)
    assert "module @jit_fft_dist1d1_64_nat_inv" in fn.lower(y).as_text()


def test_span_without_a_profiler_session():
    trace.reset_counters()
    with trace.span("fft.plan"):
        pass
    with pytest.raises(KeyError):
        with trace.span("fft.build", exe="x"):
            raise KeyError("propagates")
    # the hot-path spans are trace events only, not counted
    with trace.span("fft.dispatch", exe="x", seq=1):
        pass
    got = trace.counters()
    assert set(got) == {"fft.plan", "fft.build"}
    assert got["fft.plan"][0] == 1 and got["fft.build"][0] == 1
    assert all(s >= 0 for _, s in got.values())
    trace.reset_counters()
    assert trace.counters() == {}


def _stat(event) -> dict:
    return {k: v for k, v in event.stats}


def test_cpu_trace_of_a_planned_client(tmp_path):
    from jax.profiler import ProfileData

    trace.reset_counters()
    ctx = Context()
    ctx.create()
    problems = [Problem((64,), "Outplace_Complex", "float", batch=4),
                Problem((16, 32), "Outplace_Real", "float", batch=2)]
    clients = []
    for p in problems:
        c = jax_fft.PlannedClient(p, ctx)
        c.allocate()
        c.init_forward()
        c.init_inverse()
        c.upload(np.ones((p.batch, *p.extents), p.input_dtype))
        clients.append(c)
    got = trace.counters()
    # one build per executable: a forward and an inverse per client
    assert got["fft.build"][0] == 2 * len(clients)
    assert got["fft.plan"][0] == len(clients)

    jax.profiler.start_trace(str(tmp_path))
    try:
        for c in clients:
            c.execute_forward()
            c.execute_inverse()
    finally:
        jax.profiler.stop_trace()
    for c in clients:
        c.destroy()
    # the hot path adds nothing to the table
    assert trace.counters() == got
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    modules, spans = set(), []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                st = _stat(ev)
                if "hlo_module" in st and not ev.name.startswith("end:"):
                    modules.add(st["hlo_module"])
                if ev.name in trace.SPANS:
                    spans.append((ev.name, st.get("exe"), st.get("seq")))
    names = {c._fwd_name for c in clients} | {c._inv_name for c in clients}
    assert modules == {"jit_" + n for n in names}
    for c in clients:
        # execute_* ran twice since the client was built: in set-up nothing
        # ran, so the traced forward is seq 1 and the inverse seq 2
        for exe, seq in ((c._fwd_name, 1), (c._inv_name, 2)):
            assert ("fft.dispatch", exe, seq) in spans
            assert ("fft.sync", exe, seq) in spans
    assert len(spans) == 4 * len(clients)


@pytest.mark.parametrize("backend", ["slab", "pencil", "dist1d"])
def test_distributed_client_spans(backend, tmp_path):
    """A distributed client on four fake devices (a subprocess, since a
    process's device count is fixed when JAX starts): ``fft.plan`` once,
    ``fft.build`` per executable, the hot-path spans tagged with the
    executable the device trace names ``jit_<exe>``, and an answer within
    the AccFFT cell's limits."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "helpers",
                                      "dist_trace_check.py"),
         backend, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["counters"] == {"fft.plan": 1, "fft.build": 2}
    mesh, extents = {"slab": ("slab4", "32x32x32"),
                     "pencil": ("pencil2x2", "32x32x32"),
                     "dist1d": ("dist1d4", "1024")}[backend]
    fwd, inv = (f"fft_{mesh}_{extents}_tr_{d}" for d in ("fwd", "inv"))
    assert (got["fwd_name"], got["inv_name"]) == (fwd, inv)
    assert sorted(map(tuple, got["spans"])) == sorted(
        [("fft.dispatch", fwd, 1), ("fft.sync", fwd, 1),
         ("fft.dispatch", inv, 2), ("fft.sync", inv, 2)])
    assert got["modules"] == sorted(["jit_" + fwd, "jit_" + inv])
    with open(os.path.join(root, "bench", "configs",
                           "accfft-c2c-512.json")) as f:
        limits = json.load(f)["limits"]
    assert got["fwd_rel_l2"] <= limits["fwd_rel_l2"]
    assert got["inv_rel_l2"] <= limits["inv_rel_l2"]
