"""Fused RMSNorm / Gated-RMSNorm Pallas TPU kernels (paper §4.4 fusion suite).

Same design language as the AdaLN kernel:
* forward computes stats in fp32 over the lane (feature) dimension, writes
  the output and the rstd statistics for backward reuse;
* the weight gradient uses the **D-tile coalesced reduction**: grid
  ``(D_tiles, N_tiles)`` with row tiles innermost, fp32 accumulator block
  resident in VMEM;
* the gated variant folds ``silu(gate)`` into the same pass (Gate+Norm).

Inputs are processed as [N, D] row matrices (callers flatten leading dims).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_ROW_BLOCK = 256
DEFAULT_D_BLOCK = 512


# Block layout: the weight rides as a [1, D] row and rstd as an [N, 1]
# column.  1-D blocks are refused by the TPU compiler (XLA and Mosaic tile a
# 1-D f32 array differently), and the lane reduction leaves rstd
# sublane-major, so the column stores with no relayout.


def _rows(rb, d):
    return pl.BlockSpec((rb, d), lambda i: (i, 0))


def _weight(d):
    return pl.BlockSpec((1, d), lambda i: (0, 0))


def _col(rb):
    return pl.BlockSpec((rb, 1), lambda i: (i, 0))


# -- forward -----------------------------------------------------------------


def _fwd_kernel(x_ref, w_ref, y_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    rstd = jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps)
    y_ref[...] = (x * rstd * w_ref[...].astype(jnp.float32)).astype(y_ref.dtype)
    rstd_ref[...] = rstd


def rms_fwd_pallas(x2d, w, *, eps: float, row_block: int, interpret: bool):
    """Returns (y [N, D], rstd [N] fp32)."""
    n, d = x2d.shape
    rb = min(row_block, n)
    assert n % rb == 0 and d % 128 == 0
    y, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(n // rb,),
        in_specs=[_rows(rb, d), _weight(d)],
        out_specs=[_rows(rb, d), _col(rb)],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2d.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        name="rmsnorm_fwd",
        interpret=interpret,
    )(x2d, w[None, :])
    return y, rstd[:, 0]


def _gated_fwd_kernel(x_ref, w_ref, g_ref, y_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    rstd = jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps)
    silu = g * jax.nn.sigmoid(g)
    y_ref[...] = (x * rstd * w_ref[...].astype(jnp.float32) * silu).astype(
        y_ref.dtype
    )
    rstd_ref[...] = rstd


def gated_rms_fwd_pallas(x2d, w, g2d, *, eps: float, row_block: int, interpret: bool):
    n, d = x2d.shape
    rb = min(row_block, n)
    assert n % rb == 0 and d % 128 == 0
    y, rstd = pl.pallas_call(
        functools.partial(_gated_fwd_kernel, eps=eps),
        grid=(n // rb,),
        in_specs=[_rows(rb, d), _weight(d), _rows(rb, d)],
        out_specs=[_rows(rb, d), _col(rb)],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2d.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        name="rmsnorm_gated_fwd",
        interpret=interpret,
    )(x2d, w[None, :], g2d)
    return y, rstd[:, 0]


# -- backward: dx (rowwise) ----------------------------------------------------


def _bwd_dx_kernel(dy_ref, x_ref, w_ref, rstd_ref, dx_ref):
    dy = dy_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    rstd = rstd_ref[...]  # [rb, 1]
    x_hat = x * rstd
    dxhat = dy * w_ref[...].astype(jnp.float32)
    m = (dxhat * x_hat).mean(axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (dxhat - x_hat * m)).astype(dx_ref.dtype)


def rms_bwd_dx_pallas(dy, x2d, w, rstd, *, row_block: int, interpret: bool):
    n, d = x2d.shape
    rb = min(row_block, n)
    return pl.pallas_call(
        _bwd_dx_kernel,
        grid=(n // rb,),
        in_specs=[_rows(rb, d), _rows(rb, d), _weight(d), _col(rb)],
        out_specs=_rows(rb, d),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        name="rmsnorm_dx",
        interpret=interpret,
    )(dy, x2d, w[None, :], rstd[:, None])


# -- backward: dw via D-tile coalesced reduction -------------------------------


def _bwd_dw_kernel(dy_ref, x_ref, rstd_ref, dw_ref):
    n_idx = pl.program_id(1)  # innermost: row tiles

    @pl.when(n_idx == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dy = dy_ref[...].astype(jnp.float32)  # [rb, db]
    x_hat = x_ref[...].astype(jnp.float32) * rstd_ref[...]
    dw_ref[...] += (dy * x_hat).sum(axis=0, keepdims=True)


def rms_bwd_dw_pallas(dy, x2d, rstd, *, d_block: int, row_block: int, interpret: bool):
    n, d = x2d.shape
    db = min(d_block, d)
    rb = min(row_block, n)
    assert n % rb == 0 and d % db == 0
    tile = pl.BlockSpec((rb, db), lambda j, k: (k, j))
    (dw,) = pl.pallas_call(
        _bwd_dw_kernel,
        grid=(d // db, n // rb),  # rows innermost -> VMEM accumulation
        in_specs=[tile, tile, pl.BlockSpec((rb, 1), lambda j, k: (k, 0))],
        out_specs=[pl.BlockSpec((1, db), lambda j, k: (0, j))],
        out_shape=[jax.ShapeDtypeStruct((1, d), jnp.float32)],
        name="rmsnorm_dw",
        interpret=interpret,
    )(dy, x2d, rstd[:, None])
    return dw[0]
