"""chip_smoke.py: its CPU rehearsal ends cleanly, and without a TPU (or
without the repository around it) it fails and prints no result."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(args, tmp_path, cwd=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    return subprocess.run([sys.executable, SCRIPT if cwd is None else
                           os.path.join(cwd, "chip_smoke.py"), *args],
                          cwd=cwd or ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


@pytest.mark.parametrize("args,count", [(["--cpu-rehearsal"], 1),
                                        (["--cpu-rehearsal", "--chips", "4"],
                                         4)], ids=["one-chip", "four-chips"])
def test_cpu_rehearsal_ends_cleanly(tmp_path, args, count):
    proc = _run(args, tmp_path)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": count}}
    checked = [ln for ln in lines if ln.startswith(("suite ", "dist "))]
    assert checked and all("source=fallback" not in ln for ln in checked)
    if count == 1:
        service = next(ln for ln in lines if ln.startswith("service "))
        assert "errors=0 demotions=0 sheds=0" in service


def test_no_tpu_fails_without_result(tmp_path):
    proc = _run([], tmp_path)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "tpu" in proc.stderr


def test_alone_without_the_repo_fails_without_result(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SCRIPT, alone / "chip_smoke.py")
    proc = _run([], tmp_path, cwd=str(alone))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; otherwise
    the cache sits at one fixed, git-ignored path inside the checkout."""
    import jax
    from repro.core import device

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert device.setup_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = device.setup_compile_cache()
        assert path == device.DEFAULT_CACHE_DIR == os.path.join(
            ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        ignored = open(os.path.join(ROOT, ".gitignore")).read().split()
        assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
