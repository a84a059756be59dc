"""The trace reducer on a small trace recorded on a TPU v5e through the
harness: two traced steps of a one-layer Wan-2.1-1.3B, each two
microbatches of B=1, S=1024, then the optimizer update."""

from __future__ import annotations

import pytest

from chipbench import xtrace
from chipbench.catalog import BENCH_DIR

TRACE = BENCH_DIR / "testdata" / "tiny.xplane.pb.gz"
MICRO, LAYERS = 4, 1  # grad steps in the window, blocks of the model


@pytest.fixture(scope="module")
def reduced():
    return xtrace.reduce_trace(xtrace.load(TRACE))


def _events(pd, kernel):
    return [
        e for p in pd.planes if p.name.startswith(xtrace.DEVICE_PREFIX)
        for line in p.lines if line.name == xtrace.OPS_LINE
        for e in line.events if xtrace.classify_kernel(e.name) == kernel
    ]


def test_busy_union_and_gaps_tile_the_window(reduced):
    (dev,) = reduced.devices
    w0, w1 = reduced.window
    assert reduced.window_s == pytest.approx(0.047306127)
    assert 0 < dev.busy_ns < w1 - w0
    assert dev.busy_ns + sum(e - s for s, e in dev.gaps) == w1 - w0
    assert all(w0 <= s < e <= w1 for s, e in dev.gaps)
    assert reduced.busy_s == pytest.approx(dev.busy_ns * 1e-9)


def test_programs_in_the_window(reduced):
    names = [m[0] for m in reduced.devices[0].modules]
    assert names.count("jit_grad_step") == MICRO
    assert names.count("jit_update") == MICRO // 2


def test_kernel_sums_and_counts(reduced):
    pd = xtrace.load(TRACE)
    counts = {k: len(_events(pd, k)) for k in reduced.devices[0].kernels_ns}
    # per grad step and block: self- and cross-attention, each forward
    # twice (the block is rematerialised), dq and dkv once; two AdaLN per
    # block and one at the head, forward twice for the blocks' (remat)
    assert counts["flash_fwd"] == MICRO * LAYERS * 2 * 2
    assert counts["flash_dq"] == counts["flash_dkv"] == MICRO * LAYERS * 2
    assert counts["adaln_fwd"] == MICRO * (LAYERS * 2 * 2 + 1)
    assert counts["adaln_dx"] == counts["adaln_dmod"] == MICRO * (LAYERS * 2 + 1)
    ns = reduced.devices[0].kernels_ns
    assert reduced.kernel_s("flash_") == pytest.approx(
        (ns["flash_fwd"] + ns["flash_dq"] + ns["flash_dkv"]) * 1e-9
    )
    assert reduced.kernel_s("adaln_") < reduced.kernel_s("flash_") < reduced.busy_s


def test_breakdown(reduced):
    ops = reduced.top_ops()
    assert len(ops) == 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert not any(name in ("while", "conditional", "call") for name, _ in ops)
    gaps = reduced.top_gaps()
    idle = reduced.window_s - reduced.busy_s
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=0.05)
    assert gaps[0][0] != "unattributed"


@pytest.mark.parametrize("hlo, kernel", [
    ("%closed_call.81 = (f32[5,12,1664,128]{3,2,1,0:T(8,128)}, f32[5,12,1664,1]{3,2,1,0}) "
     "custom-call(bf16[5,12,1664,128]{3,2,1,0} %pad.321, bf16[5,12,1664,128]{3,2,1,0} %p, "
     "bf16[5,12,1664,128]{3,2,1,0} %q, s32[5,1664,1]{2,1,0} %r, s32[5,1,1664]{2,1,0} %s), "
     'custom_call_target="tpu_custom_call", operand_layout_constraints={}', "flash_fwd"),
    ("%checkpoint.122 = bf16[5,12,1664,128]{3,2,1,0} custom-call(bf16[5,12,1664,128]{3,2,1,0} "
     "%a, f32[5,12,1664,1]{3,2,1,0} %b), custom_call_target=\"tpu_custom_call\"", "flash_dq"),
    ("%checkpoint.128 = (bf16[5,12,512,128]{3,2,1,0}, bf16[5,12,512,128]{3,2,1,0}) "
     "custom-call(bf16[5,12,1664,128]{3,2,1,0} %a), custom_call_target=\"tpu_custom_call\"",
     "flash_dkv"),
    ("%closed_call.78 = (bf16[5,1560,1536]{2,1,0}, f32[5,1560,1]{2,1,0}, f32[5,1560,1]{2,1,0}) "
     "custom-call(bf16[5,1560,1536]{2,1,0} %x, f32[5,1,1536]{2,1,0} %s, f32[5,1,1536]{2,1,0} %t), "
     'custom_call_target="tpu_custom_call"', "adaln_fwd"),
    ("%transpose_jvp___.2 = bf16[5,1560,1536]{2,1,0} custom-call(bf16[5,1560,1536]{2,1,0} %a, "
     "f32[5,1,1536]{2,1,0} %b), custom_call_target=\"tpu_custom_call\"", "adaln_dx"),
    ("%transpose_jvp___.3 = (f32[5,1,1536]{2,1,0}, f32[5,1,1536]{2,1,0}) custom-call("
     "bf16[5,1560,1536]{2,1,0} %a), custom_call_target=\"tpu_custom_call\"", "adaln_dmod"),
    ("%closed_call.80 = (bf16[93600,128]{1,0}, f32[93600,1]{1,0}) custom-call(bf16[93600,128]{1,0} "
     "%a, f32[1,128]{1,0} %b), custom_call_target=\"tpu_custom_call\"", "rmsnorm"),
    ("%fusion.200 = bf16[5,1560,1536]{2,1,0} fusion(bf16[64,1536]{1,0} %a), kind=kOutput", None),
])
def test_kernels_are_told_apart_by_their_shapes(hlo, kernel):
    assert xtrace.classify_kernel(hlo) == kernel
