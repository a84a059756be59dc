"""Segment-aware flash-attention Pallas TPU kernels: forward AND backward.

The paper takes FlashAttention as given infrastructure (§1); on TPU we supply
the equivalent: blocked attention kernels whose working set lives in VMEM,
extended with *segment-id masking* so the packed variable-length windows from
``data/packing.py`` train without cross-document contamination — and so the
compiled FLOPs track the per-segment quadratic load Σ len_i² instead of S².

Design:
* **forward** — grid = (batch, q_heads, q_tiles, kv_tiles), kv innermost
  ("arbitrary" semantics) so the fp32 (m, l, acc) state for one q tile stays
  in VMEM scratch across the kv sweep; emits the logsumexp rows (LSE) that
  the backward reuses;
* **backward dq** — same kv-sweep layout as the forward: the [q_blk, dh]
  fp32 dq accumulator is VMEM-resident while k/v tiles stream past;
* **backward dk/dv** — q-sweep with the kv tile's [kv_blk, dh] fp32
  accumulators VMEM-resident, mirroring the D-tile coalesced-reduction
  strategy of ``fused_adaln``: grid = (batch, kv_heads, kv_tiles, group,
  q_tiles) with the q sweep (and the GQA group sweep) innermost, so the
  cross-q-head reduction for grouped kv heads happens on-chip in fp32;
* **GQA** without materializing repeated kv: k/v BlockSpec index maps send
  q-head h to kv-head h // group_size;
* **tile-level skipping**: a (q_tile, kv_tile) pair is skipped entirely
  (`pl.when`) when the causal triangle excludes it, when the tiles'
  segment-id ranges don't overlap, OR when either tile is padding only.
  For packed windows (contiguous, non-decreasing segment ids) the range
  test is exact, so executed tiles — and compiled FLOPs — follow Σ len_i².
  ``causal=False`` is a first-class mode for bidirectional DiT blocks;
* fp32 softmax state, bf16/f32 inputs.  Segment ids are int32 ``[B, S]``;
  ids must be non-negative — ``-1`` marks padding, which attends nothing
  and which nothing attends: real rows are exact, padded rows read zero
  output and zero gradients, whatever the tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -2.0e38

DEFAULT_Q_BLOCK = 256
DEFAULT_KV_BLOCK = 256

LSE_FLOOR = 1e-37  # guards log/div on fully-masked (padding-only) rows
# Scoped VMEM: Mosaic's default (16 MiB on a v5e) holds every kernel's
# working set up to a 512x512 score tile; larger tiles get more of the
# chip's 128 MiB.  The backward's four fp32 [qb, kb] temporaries dominate.
_DEFAULT_VMEM_TILE = 512 * 512
_LARGE_TILE_VMEM = 64 << 20
_PAD_ROW = -2  # a padded q row's id inside the mask: equal to no kv id


def _tile_overlap(qs_ref, ks_ref):
    """Can any (q row, kv column) pair of a (q_tile, kv_tile) tile see each
    other?  No when the tiles' segment-id ranges are disjoint, or when either
    tile is padding only (every id ``-1``: padding attends nothing).

    Exact for contiguous (sorted-run) segment layouts, conservative (never
    skips a needed tile) otherwise.
    """
    q_min = jnp.min(qs_ref[...])
    q_max = jnp.max(qs_ref[...])
    k_min = jnp.min(ks_ref[...])
    k_max = jnp.max(ks_ref[...])
    return (q_min <= k_max) & (k_min <= q_max) & (q_max >= 0) & (k_max >= 0)


def _causal_tile_live(qi, kj, qb, kb):
    """Causal tile test that is correct for q_block != kv_block: the tile is
    live iff its last q position can see its first kv position."""
    return (qi + 1) * qb - 1 >= kj * kb


def _masks(s_shape, qi, kj, causal, qs_ref, ks_ref):
    """Combined validity mask for one [qb, kb] score tile (or None)."""
    qb, kb = s_shape
    mask = None
    if causal:
        q_pos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        k_pos = kj * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        mask = q_pos >= k_pos
    if qs_ref is not None:
        # [qb, 1] column against a [1, kb] row: lane and sublane broadcasts.
        # A padded q row's id moves below every kv id, so padding attends
        # nothing; real rows never match a padded column's -1.
        qs = qs_ref[...]
        seg = jnp.where(qs >= 0, qs, _PAD_ROW) == ks_ref[...]
        mask = seg if mask is None else (mask & seg)
    return mask


def _compiler_params(qb, kb):
    if qb * kb <= _DEFAULT_VMEM_TILE:
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_LARGE_TILE_VMEM)


# Block layout.  The TPU compiler wants the last two dims of every block
# (8, 128)-divisible or equal to the array's, so per-row data never travels
# as a [B, S] or [B, H, S] array with (1, blk) blocks:
#
# * per-q-row values (LSE, delta, q segment ids) are [..., S, 1] columns —
#   softmax statistics come out of lane reductions sublane-major, so they
#   store and broadcast against [qb, kb] score tiles with no relayout;
# * kv segment ids are a [B, 1, S] row, broadcast down the sublanes;
# * batch and head dims are squeezed (``None``) out of every block.
#
# The public functions keep the [B, H, S] / [B, S] interfaces; the reshapes
# to and from these layouts are free.


def _q_col(qb, index_map):
    return pl.BlockSpec((None, qb, 1), index_map)


def _kv_row(kb, index_map):
    return pl.BlockSpec((None, 1, kb), index_map)


def _seg_operands(q_segment_ids, kv_segment_ids):
    return [q_segment_ids[:, :, None], kv_segment_ids[:, None, :]]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, kv_tiles, causal, has_segments):
    if has_segments:
        qs_ref, ks_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        qs_ref = ks_ref = None
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    qb, kb = q_ref.shape[0], k_ref.shape[0]

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = _causal_tile_live(qi, kj, qb, kb) if causal else (kj >= 0)
    if qs_ref is not None:
        run = run & _tile_overlap(qs_ref, ks_ref)

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale  # [qb, dh]
        k = k_ref[...].astype(jnp.float32)  # [kb, dh]
        v = v_ref[...].astype(jnp.float32)
        s = q @ k.T  # [qb, kb]
        mask = _masks(s.shape, qi, kj, causal, qs_ref, ks_ref)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]  # [qb, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)  # exp(NEG_INF - NEG_INF) guard
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + p @ v
        m_scr[...] = m_new

    @pl.when(kj == kv_tiles - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], LSE_FLOOR)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(denom)


def flash_attention_fwd_pallas(
    q,  # [B, Hq, Sq, dh]
    k,  # [B, Hkv, Skv, dh]
    v,
    q_segment_ids=None,  # [B, Sq] int32 or None
    kv_segment_ids=None,  # [B, Skv] int32 or None
    *,
    causal: bool = True,
    q_block: int = DEFAULT_Q_BLOCK,
    kv_block: int = DEFAULT_KV_BLOCK,
    scale: float | None = None,
    interpret: bool = False,
    out_dtype=None,
):
    """Returns (out [B, Hq, Sq, dh], lse [B, Hq, Sq] fp32).

    ``out_dtype`` defaults to ``q.dtype``; the grad path requests fp32 so the
    backward's delta rows come from the unrounded accumulator (the bf16
    output cast would otherwise inject ~2^-8 noise into dq/dk).
    """
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    qb = min(q_block, sq)
    kb = min(kv_block, skv)
    assert sq % qb == 0 and skv % kb == 0 and dh % 128 == 0
    assert hq % hkv == 0
    kv_tiles = skv // kb
    scale = scale if scale is not None else dh**-0.5
    has_segments = q_segment_ids is not None

    from jax.experimental.pallas import tpu as pltpu

    q_tile = pl.BlockSpec((None, None, qb, dh), lambda bi, h, i, j: (bi, h, i, 0))
    kv_tile = pl.BlockSpec(
        (None, None, kb, dh), lambda bi, h, i, j, g=g: (bi, h // g, j, 0)
    )
    in_specs = [q_tile, kv_tile, kv_tile]
    operands = [q, k, v]
    if has_segments:
        in_specs += [
            _q_col(qb, lambda bi, h, i, j: (bi, i, 0)),
            _kv_row(kb, lambda bi, h, i, j: (bi, 0, j)),
        ]
        operands += _seg_operands(q_segment_ids, kv_segment_ids)

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel,
            scale=scale,
            kv_tiles=kv_tiles,
            causal=causal,
            has_segments=has_segments,
        ),
        grid=(b, hq, sq // qb, kv_tiles),
        in_specs=in_specs,
        out_specs=[
            q_tile,
            pl.BlockSpec((None, None, qb, 1), lambda bi, h, i, j: (bi, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, dh), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, dh), jnp.float32),
        ],
        name="flash_fwd",
        compiler_params=_compiler_params(qb, kb),
        interpret=interpret,
    )(*operands)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# backward: shared tile recompute
# ---------------------------------------------------------------------------


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    qs_ref, ks_ref, qi, kj, scale, causal):
    """Recompute (p, ds) for one (q_tile, kv_tile) pair from fp32 residuals.

    p  = exp(s - lse)           — the forward's softmax tile,
    ds = p * (do @ v^T - delta) — d(scores), with masked entries exactly 0 so
    padded/foreign-segment positions contribute nothing to any gradient.
    """
    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[...]  # [qb, 1]
    delta = delta_ref[...]  # [qb, 1]
    s = (q @ k.T) * scale
    mask = _masks(s.shape, qi, kj, causal, qs_ref, ks_ref)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)
    if mask is not None:
        # fully-masked rows have lse == NEG_INF -> exp(0) == 1; zero them.
        p = jnp.where(mask, p, 0.0)
    dp = do @ v.T  # [qb, kb]
    ds = p * (dp - delta)
    return q, k, do, p, ds


# ---------------------------------------------------------------------------
# backward: dq (kv sweep, VMEM-resident dq accumulator)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, kv_tiles, causal, has_segments):
    if has_segments:
        qs_ref, ks_ref, dq_ref, dq_scr = rest
    else:
        dq_ref, dq_scr = rest
        qs_ref = ks_ref = None
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    qb, kb = q_ref.shape[0], k_ref.shape[0]

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = _causal_tile_live(qi, kj, qb, kb) if causal else (kj >= 0)
    if qs_ref is not None:
        run = run & _tile_overlap(qs_ref, ks_ref)

    @pl.when(run)
    def _compute():
        _, k, _, _, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            qs_ref, ks_ref, qi, kj, scale, causal,
        )
        dq_scr[...] += (ds @ k) * scale

    @pl.when(kj == kv_tiles - 1)
    def _finalize():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def flash_attention_bwd_dq_pallas(
    q, k, v, do, lse, delta,
    q_segment_ids=None, kv_segment_ids=None,
    *,
    causal: bool,
    q_block: int = DEFAULT_Q_BLOCK,
    kv_block: int = DEFAULT_KV_BLOCK,
    scale: float,
    interpret: bool = False,
):
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    qb = min(q_block, sq)
    kb = min(kv_block, skv)
    kv_tiles = skv // kb
    has_segments = q_segment_ids is not None

    from jax.experimental.pallas import tpu as pltpu

    q_tile = pl.BlockSpec((None, None, qb, dh), lambda bi, h, i, j: (bi, h, i, 0))
    kv_tile = pl.BlockSpec(
        (None, None, kb, dh), lambda bi, h, i, j, g=g: (bi, h // g, j, 0)
    )
    row = pl.BlockSpec((None, None, qb, 1), lambda bi, h, i, j: (bi, h, i, 0))
    in_specs = [q_tile, kv_tile, kv_tile, q_tile, row, row]
    operands = [q, k, v, do, lse[..., None], delta[..., None]]
    if has_segments:
        in_specs += [
            _q_col(qb, lambda bi, h, i, j: (bi, i, 0)),
            _kv_row(kb, lambda bi, h, i, j: (bi, 0, j)),
        ]
        operands += _seg_operands(q_segment_ids, kv_segment_ids)

    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel,
            scale=scale,
            kv_tiles=kv_tiles,
            causal=causal,
            has_segments=has_segments,
        ),
        grid=(b, hq, sq // qb, kv_tiles),
        in_specs=in_specs,
        out_specs=q_tile,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((qb, dh), jnp.float32)],
        name="flash_dq",
        compiler_params=_compiler_params(qb, kb),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# backward: dk/dv (q sweep; group + q tiles innermost so the per-kv-tile fp32
# accumulators stay VMEM-resident across the whole reduction — the same
# coalesced-reduction strategy as fused_adaln's dmod kernel)
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, q_tiles, group, causal, has_segments):
    if has_segments:
        qs_ref, ks_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
        qs_ref = ks_ref = None
    kj = pl.program_id(2)
    gi = pl.program_id(3)
    qi = pl.program_id(4)
    qb, kb = q_ref.shape[0], k_ref.shape[0]

    @pl.when((gi == 0) & (qi == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = _causal_tile_live(qi, kj, qb, kb) if causal else (qi >= 0)
    if qs_ref is not None:
        run = run & _tile_overlap(qs_ref, ks_ref)

    @pl.when(run)
    def _compute():
        q, _, do, p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            qs_ref, ks_ref, qi, kj, scale, causal,
        )
        dv_scr[...] += p.T @ do
        dk_scr[...] += (ds.T @ q) * scale

    @pl.when((gi == group - 1) & (qi == q_tiles - 1))
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd_dkv_pallas(
    q, k, v, do, lse, delta,
    q_segment_ids=None, kv_segment_ids=None,
    *,
    causal: bool,
    q_block: int = DEFAULT_Q_BLOCK,
    kv_block: int = DEFAULT_KV_BLOCK,
    scale: float,
    interpret: bool = False,
):
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    qb = min(q_block, sq)
    kb = min(kv_block, skv)
    q_tiles = sq // qb
    has_segments = q_segment_ids is not None

    from jax.experimental.pallas import tpu as pltpu

    def qhead(h, gi, g=g):
        return h * g + gi

    q_tile = pl.BlockSpec(
        (None, None, qb, dh), lambda bi, h, j, gi, i: (bi, qhead(h, gi), i, 0)
    )
    kv_tile = pl.BlockSpec((None, None, kb, dh), lambda bi, h, j, gi, i: (bi, h, j, 0))
    row = pl.BlockSpec(
        (None, None, qb, 1), lambda bi, h, j, gi, i: (bi, qhead(h, gi), i, 0)
    )
    in_specs = [q_tile, kv_tile, kv_tile, q_tile, row, row]
    operands = [q, k, v, do, lse[..., None], delta[..., None]]
    if has_segments:
        in_specs += [
            _q_col(qb, lambda bi, h, j, gi, i: (bi, i, 0)),
            _kv_row(kb, lambda bi, h, j, gi, i: (bi, 0, j)),
        ]
        operands += _seg_operands(q_segment_ids, kv_segment_ids)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel,
            scale=scale,
            q_tiles=q_tiles,
            group=g,
            causal=causal,
            has_segments=has_segments,
        ),
        grid=(b, hkv, skv // kb, g, q_tiles),
        in_specs=in_specs,
        out_specs=[kv_tile, kv_tile],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, skv, dh), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, skv, dh), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((kb, dh), jnp.float32),
            pltpu.VMEM((kb, dh), jnp.float32),
        ],
        name="flash_dkv",
        compiler_params=_compiler_params(qb, kb),
        interpret=interpret,
    )(*operands)
    return dk, dv


# ---------------------------------------------------------------------------
# host-side tile-skip oracle (CPU mirror of the kernels' skip predicate)
# ---------------------------------------------------------------------------


def attention_tile_counts(
    q_segment_ids,  # [B, Sq] int-like, or None
    kv_segment_ids,  # [B, Skv]
    *,
    sq: int | None = None,
    skv: int | None = None,
    q_block: int = DEFAULT_Q_BLOCK,
    kv_block: int = DEFAULT_KV_BLOCK,
    causal: bool = False,
) -> tuple[int, int]:
    """(executed, total) (q_tile, kv_tile) pairs per the kernels' skip rule.

    Mirrors ``_causal_tile_live`` + ``_tile_overlap`` exactly (padding-only
    tiles included); benchmarks and tests use it to report the tile-skip
    rate without running the kernel.
    """
    if q_segment_ids is None:
        assert sq is not None and skv is not None
        qs = np.zeros((1, sq), np.int64)
        ks = np.zeros((1, skv), np.int64)
    else:
        qs = np.asarray(q_segment_ids)
        ks = np.asarray(kv_segment_ids)
    b, sq = qs.shape
    skv = ks.shape[1]
    qb = min(q_block, sq)
    kb = min(kv_block, skv)
    executed = total = 0
    for bi in range(b):
        for qi in range(sq // qb):
            qt = qs[bi, qi * qb : (qi + 1) * qb]
            for kj in range(skv // kb):
                total += 1
                if causal and not ((qi + 1) * qb - 1 >= kj * kb):
                    continue
                kt = ks[bi, kj * kb : (kj + 1) * kb]
                overlap = qt.min() <= kt.max() and kt.min() <= qt.max()
                if overlap and qt.max() >= 0 and kt.max() >= 0:
                    executed += 1
    return executed, total
