"""Operations and bytes that the algorithm needs, computed from shapes.

Work is counted as the algorithm needs it, not as the program runs it:
no recomputation (the program rematerialises each block in the backward
pass) and no padding (the flash kernels pad sequences to their tiles).
A later change that drops either then raises a roofline share and cannot
push it past 100 %.

Shapes follow the model as implemented (see the config files'
``departures``): a gated three-matrix MLP, one ``txt_in`` linear, per-head
q/k RMSNorm, cross-attention to ``text_len`` text tokens.
"""

from __future__ import annotations

import dataclasses

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class Dims:
    """Widths of an MMDiT configuration, in the program's terms."""

    d: int  # hidden size
    heads: int
    head_dim: int
    ffn: int  # MLP inner width (each of w1, w3)
    layers: int
    text_len: int  # cross-attention text tokens per sample
    text_dim: int  # text-encoder width fed to txt_in
    patch_in: int  # latent channels x patch volume, one token's input width
    freq_dim: int  # sinusoidal timestep embedding width

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim


def _mm(m: int, k: int, n: int, input_grad: bool) -> int:
    """Forward plus backward FLOPs of ``[m, k] @ [k, n]`` with a trained
    weight: forward, the weight's gradient, and the input's gradient where
    the input needs one."""
    return 2 * m * k * n * (3 if input_grad else 2)


def attention_flops(b: int, heads: int, sq: int, skv: int, head_dim: int) -> tuple[int, int]:
    """(forward, backward) FLOPs of softmax attention: QK^T and PV forward;
    dV, dP, dQ and dK backward.  The softmax itself is not counted."""
    fwd = 4 * b * heads * sq * skv * head_dim
    return fwd, 2 * fwd


def model_flops(dims: Dims, b: int, s: int) -> int:
    """Forward + backward FLOPs of one microbatch of ``b`` samples of ``s``
    latent tokens: every matmul, self-attention at ``s^2``,
    cross-attention to the text tokens and the text-KV projections.
    Elementwise work (norms, modulation, activations) is not counted."""
    D, F, I, T = dims.d, dims.ffn, dims.inner, dims.text_len
    per_sample = (
        _mm(s, dims.patch_in, D, False)  # x_in (input: data)
        + _mm(T, dims.text_dim, D, False)  # txt_in (input: data)
        + _mm(1, dims.freq_dim, D, False)  # t_mlp1 (input: sinusoid)
        + _mm(1, D, 6 * D, True)  # t_mlp2
        + _mm(1, D, 2 * D, True)  # final_mod
        + _mm(s, D, dims.patch_in, True)  # x_out
    )
    per_layer = (
        _mm(s, D, 3 * I, True)  # wqkv
        + _mm(s, I, D, True)  # wo
        + _mm(s, D, I, True)  # xq
        + _mm(T, D, 2 * I, True)  # xkv on the text tokens
        + _mm(s, I, D, True)  # xo
        + 2 * _mm(s, D, F, True)  # w1, w3
        + _mm(s, F, D, True)  # w2
        + sum(attention_flops(1, dims.heads, s, s, dims.head_dim))
        + sum(attention_flops(1, dims.heads, s, T, dims.head_dim))
    )
    return b * (per_sample + dims.layers * per_layer)


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


def step_kernel_seconds(dims: Dims, microbatches, peak_flops: float,
                        peak_bytes: float) -> dict:
    """Least seconds of the flash and AdaLN calls of ``microbatches``
    (``[(b, s), ...]``): per block one self- and one cross-attention and
    two LayerNorm-Modulates, and one more LayerNorm-Modulate at the head."""
    flash = adaln = 0.0
    for b, s in microbatches:
        self_attn = flash_work(b, dims.heads, s, s, dims.head_dim)
        cross = flash_work(b, dims.heads, s, dims.text_len, dims.head_dim)
        flash += dims.layers * (
            least_seconds(self_attn, peak_flops, peak_bytes)
            + least_seconds(cross, peak_flops, peak_bytes)
        )
        adaln += (2 * dims.layers + 1) * least_seconds(
            adaln_work(b, s, dims.d), peak_flops, peak_bytes
        )
    return {"flash": flash, "adaln": adaln}
