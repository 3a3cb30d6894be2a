"""The reduction of the program's own trace events, on the CPU:

    python -m pytest bench/tests/test_program_trace.py -q

* a traced rehearsal through ``program_window.py`` reads every number the
  reduction gives (launch, sync, the two families' HBM shares, a clock
  offset of 0) and reports ``build_s.setup`` and ``plan_s.setup``;
* ``selfcheck_program.py`` passes on the recorded chip traces.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NUMBER = r"(-?[0-9.]+(?:e-?\d+)?)"


def _run(script, args, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return subprocess.run([sys.executable, os.path.join(BENCH, script),
                           *args], cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)


@pytest.mark.parametrize("cell", ["gearshifft-float.pow2",
                                  "gearshifft-float.nonpow2"])
def test_rehearsal_reads_the_program_metrics(cell, tmp_path):
    proc = _run("program_window.py", ["--workload", cell, "--seed",
                                      "3000000019", "--seconds", "1",
                                      "--rehearsal"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-6000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["metrics"]["build_s.setup"]["value"] > 0
    assert result["metrics"]["plan_s.setup"]["value"] > 0
    assert re.search(r"clock_offset_ms=0\.0 ", proc.stderr)
    (exe,) = re.findall(r"exe_seconds (.*)", proc.stderr)
    for name in ("launch_ms.fft", "sync_ms.fft", "hbm_share.xla_plans",
                 "hbm_share.pallas_plans"):
        family = re.fullmatch(r"hbm_share\.(\w+)_plans", name)
        if family and f"fft_{family.group(1)}_" not in exe:
            # no executable of the family ran: the rehearsal's nonpow2
            # sizes all plan on dft
            assert f"{name}=None" in proc.stderr
            continue
        m = re.search(rf"{re.escape(name)}={NUMBER}", proc.stderr)
        assert m, f"{name} read no number"
        assert float(m.group(1)) >= 0


def test_selfcheck_program(tmp_path):
    proc = _run("selfcheck_program.py", [], tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
