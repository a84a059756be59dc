"""jit'd wrappers for the fused RMSNorm Pallas kernels (custom VJP)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..fallback import kernel_fallback
from .ref import gated_rms_norm_fused_ref, rms_norm_fused_ref
from .rmsnorm import (
    DEFAULT_D_BLOCK,
    DEFAULT_ROW_BLOCK,
    gated_rms_fwd_pallas,
    rms_bwd_dw_pallas,
    rms_bwd_dx_pallas,
    rms_fwd_pallas,
)


def _blk(n: int, target: int) -> int:
    b = target
    while n % b != 0 and b > 8:
        b //= 2
    return b if n % b == 0 else n


def _supported(x, interpret: bool) -> bool:
    """Whether the kernels tile ``x`` (D % 128, rows % 8).  An untileable
    shape raises when compiled and warns in interpret mode."""
    if x.shape[-1] % 128 == 0 and (x.size // x.shape[-1]) % 8 == 0:
        return True
    kernel_fallback(
        f"fused RMSNorm needs D % 128 == 0 and a row count divisible by 8 "
        f"(got {x.shape})",
        interpret=interpret,
    )
    return False


# -- plain rmsnorm -------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_pallas(x2d, w, eps, interpret):
    y, _ = rms_fwd_pallas(
        x2d, w, eps=eps, row_block=_blk(x2d.shape[0], DEFAULT_ROW_BLOCK),
        interpret=interpret,
    )
    return y


def _rms_fwd(x2d, w, eps, interpret):
    y, rstd = rms_fwd_pallas(
        x2d, w, eps=eps, row_block=_blk(x2d.shape[0], DEFAULT_ROW_BLOCK),
        interpret=interpret,
    )
    return y, (x2d, w, rstd)


def _rms_bwd(eps, interpret, res, dy):
    x2d, w, rstd = res
    rb = _blk(x2d.shape[0], DEFAULT_ROW_BLOCK)
    dx = rms_bwd_dx_pallas(dy, x2d, w, rstd, row_block=rb, interpret=interpret)
    dw = rms_bwd_dw_pallas(
        dy, x2d, rstd,
        d_block=_blk(x2d.shape[1], DEFAULT_D_BLOCK), row_block=rb,
        interpret=interpret,
    )
    return dx, dw.astype(w.dtype)


_rms_pallas.defvjp(_rms_fwd, _rms_bwd)


def rms_norm(x, w, *, eps: float = 1e-6, interpret: bool = False):
    if not _supported(x, interpret):
        return rms_norm_fused_ref(x, w, eps)
    shape = x.shape
    y = _rms_pallas(x.reshape(-1, shape[-1]), w, eps, interpret)
    return y.reshape(shape)


# -- gated rmsnorm --------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grms_pallas(x2d, w, g2d, eps, interpret):
    y, _ = gated_rms_fwd_pallas(
        x2d, w, g2d, eps=eps, row_block=_blk(x2d.shape[0], DEFAULT_ROW_BLOCK),
        interpret=interpret,
    )
    return y


def _grms_fwd(x2d, w, g2d, eps, interpret):
    y, rstd = gated_rms_fwd_pallas(
        x2d, w, g2d, eps=eps, row_block=_blk(x2d.shape[0], DEFAULT_ROW_BLOCK),
        interpret=interpret,
    )
    return y, (x2d, w, g2d, rstd)


def _grms_bwd(eps, interpret, res, dy):
    """dx/dw via the rms kernels on the gate-scaled cotangent; dgate rowwise
    in jnp (elementwise, XLA fuses it)."""
    x2d, w, g2d, rstd = res
    gf = g2d.astype(jnp.float32)
    sig = jax.nn.sigmoid(gf)
    silu = gf * sig
    dy_eff = (dy.astype(jnp.float32) * silu).astype(dy.dtype)
    rb = _blk(x2d.shape[0], DEFAULT_ROW_BLOCK)
    dx = rms_bwd_dx_pallas(dy_eff, x2d, w, rstd, row_block=rb, interpret=interpret)
    dw = rms_bwd_dw_pallas(
        dy_eff, x2d, rstd,
        d_block=_blk(x2d.shape[1], DEFAULT_D_BLOCK), row_block=rb,
        interpret=interpret,
    )
    x_hat = x2d.astype(jnp.float32) * rstd[:, None]
    dsilu = sig * (1.0 + gf * (1.0 - sig))
    dg = dy.astype(jnp.float32) * x_hat * w.astype(jnp.float32)[None, :] * dsilu
    return dx, dw.astype(w.dtype), dg.astype(g2d.dtype)


_grms_pallas.defvjp(_grms_fwd, _grms_bwd)


def gated_rms_norm(x, w, gate, *, eps: float = 1e-6, interpret: bool = False):
    if not _supported(x, interpret):
        return gated_rms_norm_fused_ref(x, w, gate, eps)
    shape = x.shape
    y = _grms_pallas(
        x.reshape(-1, shape[-1]), w, gate.reshape(-1, shape[-1]), eps, interpret
    )
    return y.reshape(shape)
