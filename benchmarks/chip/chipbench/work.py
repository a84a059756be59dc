"""Operations and bytes that one kernel call needs, computed from shapes.

Work is counted as the algorithm needs it, not as the program runs it:
no recomputation (the program rematerialises each block in the backward
pass) and no padding (the flash kernels pad sequences to their tiles).
A later change that drops either then raises a roofline share and cannot
push it past 100 %.

How many calls a step makes, and of which shapes, is the model's: each
family file (``chipbench/families/<family>.py``) counts its step from
these.
"""

from __future__ import annotations

BF16 = 2
F32 = 4


def matmul_flops(m: int, k: int, n: int, input_grad: bool) -> int:
    """Forward plus backward FLOPs of ``[m, k] @ [k, n]`` with a trained
    weight: forward, the weight's gradient, and the input's gradient where
    the input needs one."""
    return 2 * m * k * n * (3 if input_grad else 2)


def attention_flops(b: int, heads: int, sq: int, skv: int, head_dim: int) -> tuple[int, int]:
    """(forward, backward) FLOPs of softmax attention: QK^T and PV forward;
    dV, dP, dQ and dK backward.  The softmax itself is not counted."""
    fwd = 4 * b * heads * sq * skv * head_dim
    return fwd, 2 * fwd


def flash_work(b: int, heads: int, sq: int, skv: int, head_dim: int) -> dict:
    """FLOPs and HBM bytes of one attention call, forward and backward
    apart: bf16 q, k, v, o, dO, dq, dk, dv, fp32 LSE rows."""
    fwd_f, bwd_f = attention_flops(b, heads, sq, skv, head_dim)
    q = b * heads * sq * head_dim * BF16
    kv = b * heads * skv * head_dim * BF16
    lse = b * heads * sq * F32
    return {
        "fwd": (fwd_f, q + 2 * kv + q + lse),  # read q, k, v; write o, lse
        # read q, k, v, o, dO, lse; write dq, dk, dv
        "bwd": (bwd_f, 3 * q + 2 * kv + lse + q + 2 * kv),
    }


def adaln_work(b: int, s: int, d: int) -> dict:
    """FLOPs and HBM bytes of one LayerNorm-Modulate, forward and backward
    apart: bf16 activations, fp32 modulation and per-row statistics."""
    act = b * s * d * BF16
    rows = b * s * F32
    mod = b * d * F32
    return {
        # read x, scale, shift; write y, mean, rstd.  ~8 FLOPs an element
        "fwd": (8 * b * s * d, act + 2 * mod + act + 2 * rows),
        # read dy, x, mean, rstd, scale; write dx, dscale, dshift
        "bwd": (14 * b * s * d, 2 * act + 2 * rows + mod + act + 2 * mod),
    }


def least_seconds(work: dict, peak_flops: float, peak_bytes: float) -> float:
    """Least time the chip could take for ``work`` (phases as from
    :func:`flash_work`): per phase, the larger of FLOPs over peak and bytes
    over bandwidth."""
    return sum(max(f / peak_flops, by / peak_bytes) for f, by in work.values())
