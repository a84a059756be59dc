"""Fused LayerNorm-Modulate Pallas TPU kernels (paper §3.3, §3.4).

TPU adaptation of the paper's CUDA design (see DESIGN.md §2):

* **Forward** — one ``pallas_call`` per (sample, seq-tile): computes LN
  statistics in fp32 registers/VMEM over the 128-lane minor (feature)
  dimension and writes the modulated output directly; the normalized
  intermediate never exists in HBM.  Statistics (mean, rstd) are written out
  once and *reused by the backward kernels* — the paper's "caches computed
  statistics in global memory for subsequent reuse".

* **Backward dmod — the D-tile coalesced reduction** — grid
  ``(B, D_tiles, S_tiles)`` with the sequence dimension innermost and
  *arbitrary* (sequential) semantics: the ``[1, d_tile]`` fp32 accumulator
  block stays resident in VMEM while ``[s_tile, d_tile]`` input blocks
  stream from HBM with the feature dim minor.  Every HBM transaction is a
  dense (8, 128)-tiled read — the TPU analogue of warp-coalesced access —
  and the accumulation itself is pure VMEM traffic.  This is the paper's
  loop-hierarchy swap: thread<-feature, march down sequence.

* **Backward dx** — rowwise LN backward, same tiling as forward.

All kernels accumulate in fp32 regardless of input dtype (paper §4.5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_SEQ_BLOCK = 128
DEFAULT_D_BLOCK = 512
DEFAULT_DMOD_SEQ_BLOCK = 512


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, scale_ref, shift_ref, y_ref, mu_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)  # [s_blk, D]
    mu = x.mean(axis=-1, keepdims=True)  # [s_blk, 1]
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    x_hat = (x - mu) * rstd
    sc = scale_ref[...].astype(jnp.float32)  # [1, D]
    sh = shift_ref[...].astype(jnp.float32)
    y_ref[...] = (x_hat * (1.0 + sc) + sh).astype(y_ref.dtype)
    mu_ref[...] = mu
    rstd_ref[...] = rstd


def _row_spec(sb, d):
    """[s_blk, D] tile of sample i, sequence tile j (batch dim squeezed)."""
    return pl.BlockSpec((None, sb, d), lambda i, j: (i, j, 0))


def _mod_spec(d):
    """Sample i's [1, D] modulation row.  The operand is [B, 1, D] rather
    than [B, D]: a (1, D) block of a [B, D] array breaks the TPU rule that
    the last two block dims be (8, 128)-divisible or whole once B > 1."""
    return pl.BlockSpec((None, 1, d), lambda i, j: (i, 0, 0))


def _stat_spec(sb):
    """Per-row statistics as [B, S, 1] columns: the reduction over lanes
    leaves them sublane-major, so no relayout is needed to store them."""
    return pl.BlockSpec((None, sb, 1), lambda i, j: (i, j, 0))


def adaln_fwd_pallas(x, scale, shift, *, eps: float, seq_block: int, interpret: bool):
    """Returns (y [B, S, D], mu [B, S] fp32, rstd [B, S] fp32)."""
    b, s, d = x.shape
    sb = min(seq_block, s)
    assert s % sb == 0 and d % 128 == 0
    grid = (b, s // sb)
    y, mu, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[_row_spec(sb, d), _mod_spec(d), _mod_spec(d)],
        out_specs=[_row_spec(sb, d), _stat_spec(sb), _stat_spec(sb)],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, d), x.dtype),
            jax.ShapeDtypeStruct((b, s, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, s, 1), jnp.float32),
        ],
        name="adaln_fwd",
        interpret=interpret,
    )(x, scale[:, None, :], shift[:, None, :])
    return y, mu[..., 0], rstd[..., 0]


# ---------------------------------------------------------------------------
# backward: dx (rowwise)
# ---------------------------------------------------------------------------


def _bwd_dx_kernel(dy_ref, x_ref, mu_ref, rstd_ref, scale_ref, dx_ref):
    dy = dy_ref[...].astype(jnp.float32)  # [s_blk, D]
    x = x_ref[...].astype(jnp.float32)
    rstd = rstd_ref[...]  # [s_blk, 1]
    sc = scale_ref[...].astype(jnp.float32)  # [1, D]
    x_hat = (x - mu_ref[...]) * rstd
    dxhat = dy * (1.0 + sc)
    m1 = dxhat.mean(axis=-1, keepdims=True)
    m2 = (dxhat * x_hat).mean(axis=-1, keepdims=True)
    dx_ref[...] = ((dxhat - m1 - x_hat * m2) * rstd).astype(dx_ref.dtype)


def adaln_bwd_dx_pallas(dy, x, mu, rstd, scale, *, seq_block: int, interpret: bool):
    b, s, d = x.shape
    sb = min(seq_block, s)
    assert s % sb == 0
    grid = (b, s // sb)
    return pl.pallas_call(
        _bwd_dx_kernel,
        grid=grid,
        in_specs=[
            _row_spec(sb, d), _row_spec(sb, d),
            _stat_spec(sb), _stat_spec(sb), _mod_spec(d),
        ],
        out_specs=_row_spec(sb, d),
        out_shape=jax.ShapeDtypeStruct((b, s, d), x.dtype),
        name="adaln_dx",
        interpret=interpret,
    )(dy, x, mu[..., None], rstd[..., None], scale[:, None, :])


# ---------------------------------------------------------------------------
# backward: d_scale / d_shift — the D-tile coalesced reduction
# ---------------------------------------------------------------------------


def _bwd_dmod_kernel(dy_ref, x_ref, mu_ref, rstd_ref, dscale_ref, dshift_ref):
    s_idx = pl.program_id(2)  # innermost: sequence tiles

    @pl.when(s_idx == 0)
    def _init():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)
        dshift_ref[...] = jnp.zeros_like(dshift_ref)

    dy = dy_ref[...].astype(jnp.float32)  # [s_blk, d_blk] — D minor/lanes
    x_hat = (x_ref[...].astype(jnp.float32) - mu_ref[...]) * rstd_ref[...]
    # vertical accumulation along sequence tiles into the resident block
    dshift_ref[...] += dy.sum(axis=0, keepdims=True)
    dscale_ref[...] += (dy * x_hat).sum(axis=0, keepdims=True)


def adaln_bwd_dmod_pallas(
    dy, x, mu, rstd, *, d_block: int, seq_block: int, interpret: bool
):
    b, s, d = x.shape
    db = min(d_block, d)
    sb = min(seq_block, s)
    assert s % sb == 0 and d % db == 0
    grid = (b, d // db, s // sb)  # sequence tiles innermost -> accumulation
    tile = pl.BlockSpec((None, sb, db), lambda i, j, k: (i, k, j))
    stat = pl.BlockSpec((None, sb, 1), lambda i, j, k: (i, k, 0))
    acc = pl.BlockSpec((None, 1, db), lambda i, j, k: (i, 0, j))  # not k
    dscale, dshift = pl.pallas_call(
        _bwd_dmod_kernel,
        grid=grid,
        in_specs=[tile, tile, stat, stat],
        out_specs=[acc, acc],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, d), jnp.float32),
        ],
        name="adaln_dmod",
        interpret=interpret,
    )(dy, x, mu[..., None], rstd[..., None])
    return dscale[:, 0], dshift[:, 0]


# ---------------------------------------------------------------------------
# naive-access backward variant (for the Figure-1 access-pattern benchmark)
# ---------------------------------------------------------------------------


def _bwd_dmod_naive_kernel(dy_ref, x_ref, mu_ref, rstd_ref, dscale_ref, dshift_ref):
    """Paper Fig. 1 'Naive Access': one grid step per sample reduces the whole
    sequence at once — no D-tiling, peak VMEM ~ S x D."""
    dy = dy_ref[...].astype(jnp.float32)  # [S, D]
    x_hat = (x_ref[...].astype(jnp.float32) - mu_ref[...]) * rstd_ref[...]
    dshift_ref[...] = dy.sum(axis=0, keepdims=True)
    dscale_ref[...] = (dy * x_hat).sum(axis=0, keepdims=True)


def adaln_bwd_dmod_naive_pallas(dy, x, mu, rstd, *, interpret: bool):
    b, s, d = x.shape
    tile = pl.BlockSpec((None, s, d), lambda i: (i, 0, 0))
    stat = pl.BlockSpec((None, s, 1), lambda i: (i, 0, 0))
    row = pl.BlockSpec((None, 1, d), lambda i: (i, 0, 0))
    dscale, dshift = pl.pallas_call(
        _bwd_dmod_naive_kernel,
        grid=(b,),
        in_specs=[tile, tile, stat, stat],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, d), jnp.float32),
        ],
        name="adaln_dmod_naive",
        interpret=interpret,
    )(dy, x, mu[..., None], rstd[..., None])
    return dscale[:, 0], dshift[:, 0]
