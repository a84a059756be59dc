"""jit'd wrapper: Pallas flash attention, forward AND backward.

Both passes run Pallas kernels (``flash.py``): the forward keeps its softmax
state in VMEM and emits LSE rows; the backward recomputes score tiles from
the (q, k, v, out, lse) residuals — dq via a kv-sweep, dk/dv via a q-sweep
with VMEM-resident fp32 accumulators — instead of re-materializing fp32
score residuals through the jnp oracle's VJP (the old reference-VJP
recompute path this replaced).

Segment-id masking makes packed variable-length windows first-class: pass
``q_segment_ids``/``kv_segment_ids`` (int32 ``[B, S]``, non-negative ids;
``-1`` = padding) and (q_tile, kv_tile) pairs whose segment ranges don't
overlap are skipped entirely, so compiled attention work follows the
per-segment quadratic load Σ len_i² rather than S².  ``causal=False`` is a
first-class mode for bidirectional DiT blocks.

Ragged sequence lengths are handled here: inputs are padded up to the tile
grid and outputs are sliced back.  Padded kv columns are marked as segment
``-1`` (padding attends nothing and nothing attends it, so every real row is
exact); padded q rows need no mark when the kv side is unpadded, since they
are sliced off and their zero cotangent gives zero gradients.

Tiles follow the shape (:func:`choose_tiles`): a grid step has a fixed cost
(DMA issue and wait, the segment test, the mask), so large tiles amortise
it, while padding up to a large tile wastes work.  The rule weighs the two
for each call's lengths; callers that pass blocks get those blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .flash import (
    DEFAULT_KV_BLOCK,
    DEFAULT_Q_BLOCK,
    flash_attention_bwd_dkv_pallas,
    flash_attention_bwd_dq_pallas,
    flash_attention_fwd_pallas,
)

PAD_SEGMENT_ID = -1
_MIN_BLOCK = 128  # lane width: LSE/segment blocks keep full lanes
# The tile rule's cost model, in score elements of a tile: one grid step of
# fwd + dq + dkv costs STEP + qb * (kb + ROW).  Fitted to the three kernels
# timed alone on a TPU v5e at the Wan buckets (dh=128, bf16 inputs): 1.93 us
# fixed, 14.5 ps an element, 1.5 ns a q row (PERF.md, section 6).
_STEP_ELEMS = 133_000
_ROW_ELEMS = 104
#: the largest score tile (elements); past 512x512 the kernels raise their
#: scoped VMEM (``flash._compiler_params``)
_MAX_TILE_ELEMS = 1 << 21


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _splits(s: int, gran: int) -> set[tuple[int, int]]:
    """(block, padded length) pairs for ``s``: for each tile count the least
    block, a multiple of ``gran``, that covers ``s``; and one block of the
    whole unpadded length (a block may equal the array's dimension)."""
    out = {(s, s)}
    for n in range(1, -(-s // _MIN_BLOCK) + 1):
        blk = _round_up(-(-s // n), gran)
        out.add((blk, n * blk))
    return out


def _live_tiles(qb: int, sq_p: int, kb: int, skv_p: int, causal: bool) -> int:
    nq, nk = sq_p // qb, skv_p // kb
    if not causal:
        return nq * nk
    last_kv = (np.arange(1, nq + 1) * qb - 1) // kb  # _causal_tile_live
    return int(np.minimum(nk, last_kv + 1).sum())


@functools.lru_cache(maxsize=None)
def choose_tiles(sq: int, skv: int, dtype, causal: bool) -> tuple[int, int, int, int]:
    """``(q_block, sq_padded, kv_block, skv_padded)`` for one call's lengths.

    q blocks sit on sublanes (a multiple of 8 rows for f32, 16 for bf16),
    kv blocks on lanes (a multiple of 128).  Of every split whose tile fits
    the size cap, the one of least estimated time wins: executed tiles
    times the step cost of the model above, the block counted at the lane
    and sublane granules the compiler pads it to.  Causal calls count only
    the tiles on or below the diagonal.
    """
    sub = 32 // jnp.dtype(dtype).itemsize
    best = None
    for qb, sq_p in _splits(sq, sub):
        for kb, skv_p in _splits(skv, _MIN_BLOCK):
            rows, cols = _round_up(qb, sub), _round_up(kb, _MIN_BLOCK)
            area = rows * cols
            if area > _MAX_TILE_ELEMS:
                continue
            step = _STEP_ELEMS + rows * (cols + _ROW_ELEMS)
            cost = _live_tiles(qb, sq_p, kb, skv_p, causal) * step
            key = (cost, sq_p + skv_p, -area, qb, kb)
            if best is None or key < best[0]:
                best = (key, (qb, sq_p, kb, skv_p))
    return best[1]


def _pick_block(s: int, block: int) -> tuple[int, int]:
    """An explicit block: pad a ragged length only to the lane granule, not
    a whole block (sq=300 pads to 384 with 128-tiles, not to 512 with a
    256-tile of mostly padding)."""
    if s % block == 0:
        return min(block, s), s
    gran = min(block, _MIN_BLOCK)
    s_p = _round_up(s, gran)
    blk = block if s_p % block == 0 else gran
    return min(blk, s_p), s_p


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, q_seg, kv_seg, causal, q_block, kv_block, scale, interpret):
    out, _ = flash_attention_fwd_pallas(
        q, k, v, q_seg, kv_seg,
        causal=causal, q_block=q_block, kv_block=kv_block,
        scale=scale, interpret=interpret,
    )
    return out


def _fwd(q, k, v, q_seg, kv_seg, causal, q_block, kv_block, scale, interpret):
    # fp32 residual output: delta rows in the backward see the unrounded
    # accumulator, not the bf16 cast handed to the caller
    out32, lse = flash_attention_fwd_pallas(
        q, k, v, q_seg, kv_seg,
        causal=causal, q_block=q_block, kv_block=kv_block,
        scale=scale, interpret=interpret, out_dtype=jnp.float32,
    )
    return out32.astype(q.dtype), (q, k, v, q_seg, kv_seg, out32, lse)


def _bwd(causal, q_block, kv_block, scale, interpret, res, g):
    q, k, v, q_seg, kv_seg, out, lse = res
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [B, Hq, Sq]
    kw = dict(
        causal=causal, q_block=q_block, kv_block=kv_block,
        scale=scale, interpret=interpret,
    )
    dq = flash_attention_bwd_dq_pallas(q, k, v, g, lse, delta, q_seg, kv_seg, **kw)
    dk, dv = flash_attention_bwd_dkv_pallas(q, k, v, g, lse, delta, q_seg, kv_seg, **kw)
    return dq, dk, dv, None, None


_flash.defvjp(_fwd, _bwd)


def flash_attention(
    q,  # [B, Hq, Sq, dh]
    k,  # [B, Hkv, Skv, dh]
    v,
    q_segment_ids=None,  # [B, Sq] int32, non-negative; None = one segment
    kv_segment_ids=None,  # [B, Skv]
    *,
    causal: bool = True,
    q_block: int | None = None,
    kv_block: int | None = None,
    scale: float | None = None,
    interpret: bool = False,
):
    """Segment-aware flash attention with a Pallas forward and backward.

    GQA is native (Hq a multiple of Hkv); dh must be a multiple of 128.
    Ragged Sq/Skv are padded to the tile grid and sliced back here.  With no
    blocks given the tiles follow the lengths (:func:`choose_tiles`); a
    given block keeps the lane-granule padding of :func:`_pick_block`, and
    the side left out takes the kernels' default block.
    """
    b, hq, sq, dh = q.shape
    skv = k.shape[2]
    if dh % 128 != 0:
        raise ValueError(f"head_dim must be a multiple of 128, got {dh}")
    scale = float(scale) if scale is not None else dh**-0.5
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("pass both q_segment_ids and kv_segment_ids, or neither")

    if q_block is None and kv_block is None:
        qb, sq_p, kb, skv_p = choose_tiles(sq, skv, jnp.dtype(q.dtype), causal)
    else:
        qb, sq_p = _pick_block(sq, q_block or DEFAULT_Q_BLOCK)
        kb, skv_p = _pick_block(skv, kv_block or DEFAULT_KV_BLOCK)
    pq, pk = sq_p - sq, skv_p - skv

    if pk and q_segment_ids is None:  # padded columns must be masked
        q_segment_ids = jnp.zeros((b, sq), jnp.int32)
        kv_segment_ids = jnp.zeros((b, skv), jnp.int32)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    if q_segment_ids is not None:
        q_segment_ids = jnp.pad(
            q_segment_ids.astype(jnp.int32), ((0, 0), (0, pq)),
            constant_values=PAD_SEGMENT_ID,
        )
        kv_segment_ids = jnp.pad(
            kv_segment_ids.astype(jnp.int32), ((0, 0), (0, pk)),
            constant_values=PAD_SEGMENT_ID,
        )

    out = _flash(q, k, v, q_segment_ids, kv_segment_ids,
                 causal, qb, kb, scale, interpret)
    return out[:, :, :sq] if pq else out
