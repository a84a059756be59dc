"""jit'd public wrapper for the fused AdaLN Pallas kernels (custom VJP)."""

from __future__ import annotations

import functools

import jax

from ..fallback import kernel_fallback
from .adaln import (
    DEFAULT_D_BLOCK,
    DEFAULT_DMOD_SEQ_BLOCK,
    DEFAULT_SEQ_BLOCK,
    adaln_bwd_dmod_pallas,
    adaln_bwd_dx_pallas,
    adaln_fwd_pallas,
)
from .ref import adaln_fused_ref


def _divisor_block(n: int, target: int, granule: int = 8) -> int:
    """Largest divisor of ``n`` that is <= ``target`` and a multiple of
    ``granule`` (8 for a sublane dim, 128 for a lane dim: the TPU block
    rule); ``n`` itself when ``n <= target``.

    Never exceeds the VMEM-safe ``target``; for awkward ``n`` (e.g. prime)
    this bottoms out at 1 and ``_pallas_supported`` refuses the shape
    instead of letting a huge degenerate block blow up VMEM.
    """
    if n <= target:
        return n
    for blk in range(target - target % granule, 0, -granule):
        if n % blk == 0:
            return blk
    return 1


def _pallas_supported(x, scale, shift) -> bool:
    return (
        x.ndim == 3
        and scale.ndim == 2
        and x.shape[-1] % 128 == 0
        and x.shape[0] == scale.shape[0]
        and _divisor_block(x.shape[1], DEFAULT_SEQ_BLOCK) >= 8
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _adaln_pallas(x, scale, shift, eps, interpret):
    y, _, _ = adaln_fwd_pallas(
        x, scale, shift, eps=eps,
        seq_block=_divisor_block(x.shape[1], DEFAULT_SEQ_BLOCK),
        interpret=interpret,
    )
    return y


def _fwd(x, scale, shift, eps, interpret):
    y, mu, rstd = adaln_fwd_pallas(
        x, scale, shift, eps=eps,
        seq_block=_divisor_block(x.shape[1], DEFAULT_SEQ_BLOCK),
        interpret=interpret,
    )
    return y, (x, scale, mu, rstd)


def _bwd(eps, interpret, res, dy):
    x, scale, mu, rstd = res
    s, d = x.shape[1], x.shape[2]
    dx = adaln_bwd_dx_pallas(
        dy, x, mu, rstd, scale,
        seq_block=_divisor_block(s, DEFAULT_SEQ_BLOCK), interpret=interpret,
    )
    dscale, dshift = adaln_bwd_dmod_pallas(
        dy, x, mu, rstd,
        d_block=_divisor_block(d, DEFAULT_D_BLOCK, granule=128),
        seq_block=_divisor_block(s, DEFAULT_DMOD_SEQ_BLOCK),
        interpret=interpret,
    )
    return dx, dscale.astype(scale.dtype), dshift.astype(scale.dtype)


_adaln_pallas.defvjp(_fwd, _bwd)


def adaln_modulate(x, scale, shift, *, eps: float = 1e-6, interpret: bool = False):
    """Fused LayerNorm + Modulate.  x: [B, S, D]; scale/shift: [B, D].

    A shape outside the kernel's tiling constraints (D not a multiple of
    128, or no sequence tile of >= 8 rows dividing S) raises when compiled
    and runs the fused jnp reference in interpret mode.
    """
    if not _pallas_supported(x, scale, shift):
        kernel_fallback(
            f"fused AdaLN needs x [B, S, D] with D % 128 == 0 and a sequence "
            f"tile of >= 8 rows dividing S (got x {x.shape}, scale "
            f"{scale.shape})",
            interpret=interpret,
        )
        return adaln_fused_ref(x, scale, shift, eps)
    return _adaln_pallas(x, scale, shift, eps, interpret)
