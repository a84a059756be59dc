"""Work counts against hand sums at Wan-2.1 widths (the Wan family's step,
each kernel call's work), and the peak table."""

from __future__ import annotations

import json

import pytest

from chipbench.catalog import BENCH_DIR, load_family
from chipbench.peaks import peaks_for
from chipbench.work import adaln_work, flash_work, least_seconds

WAN = load_family("wan")
model_flops = WAN.model_flops


def _dims(name):
    return WAN.dims_of(json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text()))


def test_dims_of_the_configs():
    d = _dims("wan2.1-1.3b")
    assert (d.d, d.heads, d.head_dim, d.ffn, d.layers) == (1536, 12, 128, 8960, 12)
    assert (d.text_len, d.text_dim, d.patch_in, d.freq_dim) == (512, 4096, 64, 256)
    d = _dims("wan2.1-14b")
    assert (d.d, d.heads, d.head_dim, d.ffn, d.layers) == (5120, 40, 128, 13824, 1)


def test_model_flops_1p3b_by_hand():
    d = _dims("wan2.1-1.3b")
    B, S, T, D, F, L = 1, 1024, 512, 1536, 8960, 12
    # per layer, 6 FLOPs a weight a token (forward, weight and input
    # gradients): wqkv 3D^2, wo D^2, xq D^2, xo D^2, MLP 3DF on the S
    # tokens; xkv 2D^2 on the T text tokens
    layer = 6 * S * (6 * D * D + 3 * D * F) + 6 * T * 2 * D * D
    # attention: 4 S S' D forward, twice that backward
    layer += 12 * S * S * D + 12 * S * T * D
    # outside the blocks: x_in (64->D) and txt_in (4096->D) take no input
    # gradient (4 FLOPs a weight a token); t_mlp1 (256->D) likewise on one
    # row; t_mlp2 (D->6D), final_mod (D->2D) on one row, x_out (D->64)
    outer = 4 * S * 64 * D + 4 * T * 4096 * D + 4 * 256 * D
    outer += 6 * (6 * D * D + 2 * D * D) + 6 * S * D * 64
    assert model_flops(d, B, S) == B * (L * layer + outer)
    assert model_flops(d, 5, S) == 5 * model_flops(d, 1, S)


def test_flash_work_by_hand():
    w = flash_work(1, 12, 7800, 7800, 128)
    assert w["fwd"][0] == 4 * 12 * 7800 * 7800 * 128 == 373_800_960_000
    assert w["bwd"][0] == 2 * w["fwd"][0]
    q = 12 * 7800 * 128 * 2
    # forward reads q, k, v and writes o (bf16) and the LSE rows (fp32)
    assert w["fwd"][1] == 4 * q + 12 * 7800 * 4
    # backward reads q, k, v, o, dO, LSE and writes dq, dk, dv
    assert w["bwd"][1] == 8 * q + 12 * 7800 * 4
    cross = flash_work(2, 12, 1560, 512, 128)
    assert cross["fwd"][0] == 4 * 2 * 12 * 1560 * 512 * 128


def test_adaln_work_by_hand():
    w = adaln_work(1, 1024, 1536)
    act, rows, mod = 1024 * 1536 * 2, 1024 * 4, 1536 * 4
    assert w["fwd"][1] == 2 * act + 2 * rows + 2 * mod
    assert w["bwd"][1] == 3 * act + 2 * rows + 3 * mod


def test_least_seconds_takes_the_binding_bound():
    # attention at S=7800 is bound by FLOPs; AdaLN by bytes
    peak_f, peak_b = 197e12, 819e9
    f = flash_work(1, 12, 7800, 7800, 128)
    assert least_seconds(f, peak_f, peak_b) == pytest.approx(
        (f["fwd"][0] + f["bwd"][0]) / peak_f
    )
    a = adaln_work(1, 7800, 1536)
    assert least_seconds(a, peak_f, peak_b) == pytest.approx(
        (a["fwd"][1] + a["bwd"][1]) / peak_b
    )


def test_peaks_known_and_unknown_kinds():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
