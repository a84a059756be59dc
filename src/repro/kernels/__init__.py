"""Kernel dispatch layer.

Models call these wrappers; the backend is chosen by platform the first
time a wrapper traces (never at import):

* ``"pallas"`` on TPU — the Pallas kernels, compiled by Mosaic.  A shape
  the kernels cannot tile raises :class:`NotImplementedError` instead of
  quietly running a jnp oracle on the chip;
* ``"ref"`` elsewhere — fused ``jax.custom_vjp`` jnp implementations (these
  already deliver the paper's *graph-level* fusion — minimal residuals —
  and are the numeric oracles).

``set_backend`` overrides the choice for tests and benchmarks:
``"pallas_interpret"`` runs the kernels' tile programs on CPU (fallbacks
then warn with :class:`KernelFallbackWarning`), ``"naive"`` runs the
paper's discrete-op baseline.
"""

from __future__ import annotations

import jax

from .fallback import KernelFallbackWarning, kernel_fallback
from .fused_adaln.ref import (
    activation_bytes_fused,
    activation_bytes_naive,
    adaln_fused_ref,
    adaln_naive,
    adaln_reference,
)
from .fused_rmsnorm.ref import (
    gated_rms_norm_fused_ref,
    gated_rms_norm_naive,
    qk_norm_naive,
    rms_norm_fused_ref,
    rms_norm_naive,
)

# "naive" = discrete ops, no fused VJP (the paper's baseline);
# "ref"   = fused custom_vjp jnp (graph-level fusion, the non-TPU default);
# "pallas" = the compiled TPU kernels (the TPU default);
# "pallas_interpret" = the same kernels interpreted on CPU (tests).
_VALID = ("naive", "ref", "pallas", "pallas_interpret")
_override: str | None = None  # set_backend's choice; None = by platform


def set_backend(name: str) -> None:
    global _override
    if name not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}, got {name!r}")
    _override = name


def get_backend() -> str:
    if _override is not None:
        return _override
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _pallas() -> bool:
    return get_backend().startswith("pallas")


def _interpret() -> bool:
    return get_backend() == "pallas_interpret"


def adaln_modulate(x, scale, shift, eps: float = 1e-6):
    """Fused LayerNorm-Modulate (paper §3.3)."""
    if _pallas():
        from .fused_adaln.ops import adaln_modulate as op

        return op(x, scale, shift, eps=eps, interpret=_interpret())
    if get_backend() == "naive":
        return adaln_naive(x, scale, shift, eps)
    return adaln_fused_ref(x, scale, shift, eps)


def _head_dim_fallback(kernel: str, dh: int) -> None:
    kernel_fallback(
        f"{kernel} needs head_dim % 128 == 0 (got dh={dh})",
        interpret=_interpret(),
    )


def attention(
    q,  # [B, Sq, Hq, dh]
    k,  # [B, Skv, Hkv, dh]  (GQA: Hq % Hkv == 0)
    v,
    *,
    causal: bool,
    q_segment_ids=None,  # [B, Sq] int32, non-negative; None = one segment
    kv_segment_ids=None,  # [B, Skv]
    scale: float | None = None,
    seq_axis: str | None = None,  # mesh axis name: ring sequence-parallel
):
    """Segment-aware self/cross attention (model [B, S, H, dh] layout).

    On the pallas backends this routes through the flash-attention kernel
    (Pallas forward AND backward, (q_tile, kv_tile) pairs with disjoint
    segment ranges skipped); otherwise through ``blocked_attention``, the
    jnp oracle and SPMD-friendly CPU/dry-run path.  Both mask by segment-id
    equality, so packed variable-length windows never attend across
    document boundaries.

    ``seq_axis`` selects the sequence-parallel ring variant: the caller is
    inside ``shard_map`` over that mesh axis and passes its contiguous
    shard of one packed window; KV blocks rotate via ``ppermute`` (see
    ``flash_attention.ring``).  Pallas backends ring the flash kernel,
    jnp backends ring the reference block — both match the single-device
    packed kernel on the gathered window.
    """
    # models are layered above kernels; import lazily to avoid the cycle
    from repro.models.attention import blocked_attention, repeat_kv

    hq, dh = q.shape[2], q.shape[3]
    hkv = k.shape[2]
    if hq % hkv != 0:  # no backend can group these heads
        raise ValueError(f"GQA needs Hq % Hkv == 0, got Hq={hq}, Hkv={hkv}")
    if seq_axis is not None:
        if _pallas() and dh % 128 == 0:
            from .flash_attention.ring import ring_flash_attention

            out = ring_flash_attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                q_segment_ids, kv_segment_ids,
                axis_name=seq_axis, causal=causal, scale=scale,
                interpret=_interpret(),
            )
            return out.swapaxes(1, 2)
        if _pallas():
            _head_dim_fallback("ring flash attention", dh)
        from .flash_attention.ring import ring_attention_ref

        out = ring_attention_ref(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            q_segment_ids, kv_segment_ids,
            axis_name=seq_axis, causal=causal, scale=scale,
        )
        return out.swapaxes(1, 2)
    if _pallas():
        if dh % 128 == 0:
            from .flash_attention.ops import flash_attention

            out = flash_attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                q_segment_ids, kv_segment_ids,
                causal=causal, scale=scale, interpret=_interpret(),
            )
            return out.swapaxes(1, 2)
        _head_dim_fallback("flash attention", dh)
    g = hq // hkv
    return blocked_attention(
        q, repeat_kv(k, g), repeat_kv(v, g),
        causal=causal, scale=scale,
        q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
    )


def paged_attention(
    q,  # [B, Hq, dh]: one new token per decode slot
    k_pages,  # [P, page_size, Hkv, dh]: shared KV-cache pool
    v_pages,
    page_table,  # [B, pages_max] int32 (unused entries -> a scratch page)
    kv_lens,  # [B] int32 valid tokens per slot (0 = inactive, exact zeros)
    *,
    scale: float | None = None,
):
    """Decode attention over a paged KV-cache pool (continuous batching).

    On the pallas backends this routes through the paged-attention kernel
    (page-table-chasing BlockSpecs, whole pages past ``kv_len`` skipped —
    the page table is segment ids over the pool); otherwise through the
    jnp gather-and-mask twin, which is also the numeric oracle.
    """
    hq, dh = q.shape[1], q.shape[2]
    hkv = k_pages.shape[2]
    if hq % hkv != 0:
        raise ValueError(f"GQA needs Hq % Hkv == 0, got Hq={hq}, Hkv={hkv}")
    if _pallas():
        if dh % 128 == 0:
            from .flash_attention.paged import paged_attention_pallas

            return paged_attention_pallas(
                q, k_pages, v_pages, page_table, kv_lens,
                scale=scale, interpret=_interpret(),
            )
        _head_dim_fallback("paged attention", dh)
    from .flash_attention.paged import paged_attention_ref

    return paged_attention_ref(
        q, k_pages, v_pages, page_table, kv_lens, scale=scale
    )


def rms_norm(x, w, eps: float = 1e-6):
    if _pallas():
        from .fused_rmsnorm.ops import rms_norm as op

        return op(x, w, eps=eps, interpret=_interpret())
    if get_backend() == "naive":
        return rms_norm_naive(x, w, eps)
    return rms_norm_fused_ref(x, w, eps)


def gated_rms_norm(x, w, gate, eps: float = 1e-6):
    """rmsnorm(x) * w * silu(gate) — paper's Gate+Norm fusion."""
    if _pallas():
        from .fused_rmsnorm.ops import gated_rms_norm as op

        return op(x, w, gate, eps=eps, interpret=_interpret())
    if get_backend() == "naive":
        return gated_rms_norm_naive(x, w, gate, eps)
    return gated_rms_norm_fused_ref(x, w, gate, eps)


def qk_norm(q, k, wq, wk, eps: float = 1e-6):
    """Joint per-head q/k RMSNorm — paper's QNorm+KNorm fusion."""
    if _pallas():
        from .fused_rmsnorm.ops import rms_norm as op

        return (
            op(q, wq, eps=eps, interpret=_interpret()),
            op(k, wk, eps=eps, interpret=_interpret()),
        )
    if get_backend() == "naive":
        return (rms_norm_naive(q, wq, eps), rms_norm_naive(k, wk, eps))
    return qk_norm_naive(q, k, wq, wk, eps)


__all__ = [
    "KernelFallbackWarning",
    "set_backend",
    "get_backend",
    "attention",
    "paged_attention",
    "adaln_modulate",
    "rms_norm",
    "gated_rms_norm",
    "qk_norm",
    "adaln_naive",
    "adaln_reference",
    "adaln_fused_ref",
    "rms_norm_naive",
    "rms_norm_fused_ref",
    "gated_rms_norm_naive",
    "gated_rms_norm_fused_ref",
    "qk_norm_naive",
    "activation_bytes_naive",
    "activation_bytes_fused",
]
