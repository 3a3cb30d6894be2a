"""The benchmark's own tests, on the CPU at the rehearsal sizes:

    python -m pytest bench/tests -q -n 4

* every cell's rehearsal runs end to end and reads ``correct``;
* a run of ``run.py`` without a TPU, and a rehearsal without the program
  beside it, fail and print no result;
* with the timed path broken underneath (``faults.py``), ``correct``
  comes out false, once for each fault the cell can have;
* the control of each kind of cell reads above the cell's limits;
* ``selfcheck.py`` (the trace reduction on a recorded chip trace, the
  least-bytes count by hand) passes.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _env(cache) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    return env


@pytest.fixture(scope="session")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


def _run(script, args, cache, cwd=ROOT, platform=None):
    env = _env(cache)
    if platform:
        env["JAX_PLATFORMS"] = platform
    return subprocess.run([sys.executable, os.path.join(cwd, "bench", script),
                           *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=900)


def _result(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-6000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


CELLS = ("gearshifft-float.pow2", "gearshifft-float.nonpow2",
         "fft-service.zipf-steady", "accfft-c2c-512.planned")


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_is_correct(cell, trace, cache):
    r = _result(_run("rehearse.py", ["--workload", cell, "--seed",
                                     "3000000017", "--seconds", "1",
                                     "--trace", str(trace)], cache))
    assert r["correct"] is True
    assert r["device"]["platform"] == "cpu"
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in bench[kind]
            if cell in m.get("workloads", [cell])}
    assert set(r["metrics"]) == want
    if trace:
        assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
        assert r["breakdown"]["device_ops"]


def test_no_tpu_refuses(cache):
    proc = _run("run.py", ["--workload", "gearshifft-float.pow2", "--seed",
                           "1", "--seconds", "1", "--trace", "0"], cache,
                platform="cpu")
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "tpu" in proc.stderr


def test_without_the_program_refuses(cache, tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for script in ("run.py", "rehearse.py"):
        proc = _run(script, ["--workload", "gearshifft-float.pow2",
                             "--seed", "1", "--seconds", "1", "--trace",
                             "0"], cache, cwd=str(tmp_path))
        assert proc.returncode != 0
        assert '"correct"' not in proc.stdout


FAULTS = [("gearshifft-float.pow2", f)
          for f in ("unchanged", "half_batch", "altered")] + \
         [("fft-service.zipf-steady", f)
          for f in ("unchanged", "half_batch", "altered")] + \
         [("accfft-c2c-512.planned", f)
          for f in ("unchanged", "half_batch", "altered", "no_exchange")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_reads_not_correct(cell, fault, cache):
    r = _result(_run("rehearse.py", ["--workload", cell, "--seed", "5",
                                     "--seconds", "1", "--trace", "0",
                                     "--fault", fault], cache))
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("cell", ["gearshifft-float.pow2",
                                  "fft-service.zipf-steady",
                                  "accfft-c2c-512.planned"])
def test_control_reads_above_the_limits(cell, cache):
    proc = _run("control.py", ["--workload", cell, "--seeds", "1,2,3",
                               "--platform", "cpu"], cache, platform="cpu")
    assert _result(proc)["control_fails"] is True


def test_selfcheck(cache):
    proc = _run("selfcheck.py", [], cache, platform="cpu")
    assert proc.returncode == 0, proc.stdout + proc.stderr
