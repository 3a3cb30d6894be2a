"""Roofline module: table assembly robustness, the vlm parameter
accounting, and the FFT roofline helpers the bench grid annotates with."""

from __future__ import annotations

import builtins
import json
from dataclasses import replace

import pytest

from repro.configs.base import get_config
from repro.roofline import analysis
from repro.roofline.analysis import (
    DEVICE_PEAKS, HBM_BW, PEAK_FLOPS, active_params, device_peaks,
    fft_model_flops, fft_roofline_frac, load_rows, markdown_table,
    row_from_record,
)


def _rec(**over):
    rec = {"arch": "qwen3-1.7b", "shape": "train_4k", "mesh": "16x16",
           "status": "ok", "flops_per_device": 1e15,
           "dot_bytes_per_device": 1e12,
           "collectives": {"total_bytes": 1e9}, "compile_s": 1.0}
    rec.update(over)
    return rec


# ---------------------------------------------------------------------------
# table assembly
# ---------------------------------------------------------------------------
def test_unknown_mesh_becomes_skipped_row():
    # an unfamiliar dry-run mesh used to KeyError and abort the whole table
    row = row_from_record(_rec(mesh="4x4"))
    assert row.status == "skipped: unknown mesh 4x4"
    assert row.compute_s == 0.0
    # skipped rows render as a dash line, not a crash
    assert "skipped: unknown mesh 4x4" in markdown_table([row])


def test_known_mesh_row():
    row = row_from_record(_rec())
    assert row.status == "ok"
    assert row.compute_s == pytest.approx(1e15 / PEAK_FLOPS)
    assert row.memory_s == pytest.approx(1e12 / HBM_BW)
    assert row.dominant == "compute"
    assert row.roofline_fraction > 0


def test_load_rows_closes_file_handles(tmp_path, monkeypatch):
    for i in range(3):
        (tmp_path / f"r{i}.json").write_text(
            json.dumps(_rec(status="error")))
    opened = []
    real_open = builtins.open

    def tracking_open(*a, **kw):
        f = real_open(*a, **kw)
        opened.append(f)
        return f

    monkeypatch.setattr(builtins, "open", tracking_open)
    rows = load_rows(str(tmp_path), mesh=None)
    monkeypatch.undo()
    assert len(rows) == 3
    assert opened and all(f.closed for f in opened)


# ---------------------------------------------------------------------------
# vlm parameter accounting
# ---------------------------------------------------------------------------
def test_vlm_counts_cross_attention_layers():
    cfg = get_config("llama-3.2-vision-90b")
    total, active = active_params(cfg)
    # 100 layers = 80 self + 20 cross (every 5th); both layer kinds carry
    # q/k/v/o attention weights plus the gated MLP
    d, hd = cfg.d_model, cfg.head_dim
    attn = (d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd
            + cfg.n_heads * hd * d)
    mlp = 3 * d * cfg.d_ff
    expected = 80 * (attn + mlp) + 20 * (attn + mlp)
    assert total == active == expected
    assert total > 0


def test_vlm_cross_every_zero_is_all_self_attention():
    # guard: cross_every=0 must not divide by zero
    cfg = replace(get_config("llama-3.2-vision-90b"), cross_every=0)
    total, _ = active_params(cfg)
    d, hd = cfg.d_model, cfg.head_dim
    attn = (d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd
            + cfg.n_heads * hd * d)
    assert total == cfg.n_layers * (attn + 3 * d * cfg.d_ff)


# ---------------------------------------------------------------------------
# FFT roofline helpers
# ---------------------------------------------------------------------------
def test_device_peaks_prefix_match():
    assert device_peaks("TPU v4 (4 cores)") == DEVICE_PEAKS["tpu v4"]
    assert device_peaks("TPU v5 lite") == DEVICE_PEAKS["tpu v5 lite"]
    assert device_peaks("cpu") == DEVICE_PEAKS["cpu"]
    # a kind with no entry is an error, never another device's envelope
    for kind in ("NVIDIA H100", None, ""):
        with pytest.raises(KeyError, match="no roofline peaks"):
            device_peaks(kind)


def test_fft_model_flops():
    assert fft_model_flops((1024,)) == pytest.approx(5.0 * 1024 * 10)
    # nd flops depend only on total N (sum of per-axis log2 terms)
    assert fft_model_flops((32, 32)) == fft_model_flops((1024,))
    assert fft_model_flops((1024,), batch=4) == \
        pytest.approx(4 * fft_model_flops((1024,)))
    assert fft_model_flops((1,)) == 0.0
    assert fft_model_flops(()) == 0.0


def test_fft_roofline_frac_finite():
    peak_flops, hbm_bw = device_peaks("cpu")
    # memory-bound: bytes term dominates
    frac = fft_roofline_frac(1.0, 1e6, 2e7, "cpu")
    assert frac == pytest.approx((2e7 / hbm_bw) / 1e-3)
    # infeasible-candidate byte sentinel must not poison the fraction
    frac = fft_roofline_frac(1.0, 1e9, float("inf"), "cpu")
    assert frac == pytest.approx((1e9 / peak_flops) / 1e-3)
    # no model at all -> 0, never NaN
    assert fft_roofline_frac(1.0, 0.0, float("inf"), "cpu") == 0.0
    assert fft_roofline_frac(0.0, 1e9, 1e6, "cpu") == 0.0
