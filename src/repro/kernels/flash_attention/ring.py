"""Ring segment-aware flash attention: one packed window across k ranks.

The planner's sequence-parallel "split buckets" put a contiguous Q shard of
one packed window on each of ``k`` mesh ranks (a new ``"seq"`` sub-axis).
Attention then needs every shard to see every KV block, which this module
supplies as a ring: each rank holds its local (k, v, segment_ids) block and
rotates it one hop per step via ``jax.lax.ppermute``, so after ``k`` steps
every Q shard has consumed the whole window without any rank ever holding
more than ``S/k`` of it.

Reuses the existing segment-id machinery at two levels:

* **shard-level skip** — a remote KV block whose per-row segment-id ranges
  don't intersect the local Q shard's is skipped outright (``lax.cond``
  around the per-step kernel call).  The predicate is the same min/max
  range intersect as the kernel's ``_tile_overlap``, padding-only shards
  included, so skipping is exactly as conservative as the in-kernel tile
  skip and never changes the result.
* **tile-level skip** — each surviving per-step call is the *existing*
  Pallas forward/backward kernel, so intra-block tiles still skip by
  segment range.

Numerics: per-step partial outputs merge through a streaming fp32
logsumexp (running max ``m``, normalizer ``s = sum exp(lse_t - m)``,
numerator ``num = sum exp(lse_t - m) * o_t``), matching the single-device
kernel's softmax to fp32 reassociation error.  Causality decomposes
exactly over contiguous shards: the local (diagonal) block runs the kernel
with ``causal=True``; a block from a lower rank is fully visible
(``causal=False``); a block from a higher rank is fully masked and is
skipped.  The backward rotates (k, v, dk, dv) around the full ring so KV
gradients arrive home after ``k`` hops, using the *merged* LSE/delta rows
— ``p = exp(s - lse_global)`` is the true global softmax, so each block's
dq/dk/dv contribution is exact.

``ring_attention_ref`` is the pure-jnp twin (plain JAX AD through the ring
— ``ppermute`` transposes to the inverse permutation) used by the
``ref``/``naive`` backends and as the CPU oracle for the Pallas path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash import (
    DEFAULT_KV_BLOCK,
    DEFAULT_Q_BLOCK,
    LSE_FLOOR,
    NEG_INF,
    flash_attention_bwd_dkv_pallas,
    flash_attention_bwd_dq_pallas,
    flash_attention_fwd_pallas,
)


def ring_axis_size(axis_name) -> int:
    """Static size of a mesh axis from inside ``shard_map`` (psum of a
    python literal constant-folds at trace time)."""
    return int(lax.psum(1, axis_name))


def _pick_block(s: int, default: int) -> int:
    """Largest supported tile size dividing ``s`` (shards are planned to a
    128-token granule, so no ragged padding is needed at ring level)."""
    if s % default == 0:
        return default
    if s % 128 == 0:
        return 128
    raise ValueError(
        f"ring attention needs the local sequence ({s}) to be a multiple "
        f"of 128; the split planner only emits 128-aligned shards"
    )


def _block_overlap(q_seg, kv_seg):
    """Shard-level skip predicate: do any batch row's segment ranges
    intersect, with neither shard padding only?  Mirrors the kernel's
    ``_tile_overlap`` (raw min/max) so a skipped block is one the kernel
    itself would have masked to nothing."""
    q_min = jnp.min(q_seg, axis=1)
    q_max = jnp.max(q_seg, axis=1)
    k_min = jnp.min(kv_seg, axis=1)
    k_max = jnp.max(kv_seg, axis=1)
    live = (q_min <= k_max) & (k_min <= q_max) & (q_max >= 0) & (k_max >= 0)
    return jnp.any(live)


def _merge(state, o_t, lse_t):
    """Streaming fp32 logsumexp merge of one ring step's partial result.

    A fully-masked block arrives as (o=0, lse~NEG_INF): its weight
    ``exp(lse_t - m)`` underflows to 0 against any real block, and rows
    masked in EVERY block converge to out=0 — the single-device kernel's
    convention for padding-only rows."""
    m, s, num = state
    m_new = jnp.maximum(m, lse_t)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(lse_t - m_new)
    s = s * alpha + beta
    num = num * alpha[..., None] + beta[..., None] * o_t
    return m_new, s, num


def _rotate(tree, axis_name, axis_size):
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    return lax.ppermute(tree, axis_name, perm)


# ---------------------------------------------------------------------------
# per-step block attention (pallas kernel / jnp reference)
# ---------------------------------------------------------------------------


def _block_pallas(q, k, v, q_seg, kv_seg, *, causal, scale, q_block,
                  kv_block, interpret):
    out, lse = flash_attention_fwd_pallas(
        q, k, v, q_seg, kv_seg,
        causal=causal, q_block=q_block, kv_block=kv_block, scale=scale,
        interpret=interpret, out_dtype=jnp.float32,
    )
    return out, lse


def _block_ref(q, k, v, q_seg, kv_seg, *, causal, scale):
    """jnp block attention returning (o fp32, lse fp32) with the kernel's
    exact masking conventions (fully-masked rows -> o=0, lse=NEG_INF+log
    floor)."""
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:  # GQA: repeat kv heads for the einsum path
        g = hq // hkv
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    mask = (q_seg[:, None, :, None] == kv_seg[:, None, None, :]) & (
        q_seg >= 0
    )[:, None, :, None]  # padding (-1) sees nothing
    if causal:
        sq, skv = q.shape[2], k.shape[2]
        qpos = lax.broadcasted_iota(jnp.int32, (sq, skv), 0)
        kpos = lax.broadcasted_iota(jnp.int32, (sq, skv), 1)
        mask = mask & (qpos >= kpos)[None, None]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    denom = jnp.maximum(l, LSE_FLOOR)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    o = o / denom[..., None]
    lse = m + jnp.log(denom)
    return o, lse


# ---------------------------------------------------------------------------
# the ring forward
# ---------------------------------------------------------------------------


def _ring_fwd_loop(q, k, v, q_seg, kv_seg, *, block_fn, causal, axis_name,
                   axis_size):
    """Unrolled k-step ring.  ``ppermute`` stays OUTSIDE every ``cond`` —
    all ranks must participate in each rotation even when their local
    (visibility x segment-range) predicate skips the block compute."""
    b, hq, sq, dh = q.shape
    my = lax.axis_index(axis_name)
    m = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    s = jnp.zeros((b, hq, sq), jnp.float32)
    num = jnp.zeros((b, hq, sq, dh), jnp.float32)
    kc, vc, segc = k, v, kv_seg
    for t in range(axis_size):
        if t == 0:
            o_t, lse_t = block_fn(q, kc, vc, q_seg, segc, causal=causal)
            m, s, num = _merge((m, s, num), o_t, lse_t)
        else:
            live = _block_overlap(q_seg, segc)
            if causal:
                # src rank is (my - t) mod k: lower iff t <= my (fully
                # visible); higher ranks are entirely in the future
                live = live & (my >= t)

            def run(kc, vc, segc):
                return block_fn(q, kc, vc, q_seg, segc, causal=False)

            def skip(kc, vc, segc):
                return (
                    jnp.zeros((b, hq, sq, dh), jnp.float32),
                    jnp.full((b, hq, sq), NEG_INF, jnp.float32),
                )

            o_t, lse_t = lax.cond(live, run, skip, kc, vc, segc)
            m, s, num = _merge((m, s, num), o_t, lse_t)
        if t < axis_size - 1:
            kc, vc, segc = _rotate((kc, vc, segc), axis_name, axis_size)
    denom = jnp.maximum(s, LSE_FLOOR)
    out = num / denom[..., None]
    lse = m + jnp.log(denom)
    return out, lse


# ---------------------------------------------------------------------------
# custom_vjp pallas op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _ring(q, k, v, q_seg, kv_seg, causal, axis_name, axis_size, scale,
          q_block, kv_block, interpret):
    out, _res = _ring_fwd(
        q, k, v, q_seg, kv_seg, causal, axis_name, axis_size, scale,
        q_block, kv_block, interpret,
    )
    return out


def _ring_fwd(q, k, v, q_seg, kv_seg, causal, axis_name, axis_size, scale,
              q_block, kv_block, interpret):
    block_fn = functools.partial(
        _block_pallas, scale=scale, q_block=q_block, kv_block=kv_block,
        interpret=interpret,
    )
    out32, lse = _ring_fwd_loop(
        q, k, v, q_seg, kv_seg,
        block_fn=block_fn, causal=causal, axis_name=axis_name,
        axis_size=axis_size,
    )
    return out32.astype(q.dtype), (q, k, v, q_seg, kv_seg, out32, lse)


def _ring_bwd(causal, axis_name, axis_size, scale, q_block, kv_block,
              interpret, res, g):
    q, k, v, q_seg, kv_seg, out32, lse = res
    my = lax.axis_index(axis_name)
    # everything fp32 end-to-end: per-block contributions accumulate
    # unrounded, so the single final cast matches the one rounding the
    # single-device kernel applies (bf16 parity depends on this)
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    delta = jnp.sum(gf * out32, axis=-1)  # [B, Hq, Sq] fp32
    dq = jnp.zeros(q.shape, jnp.float32)
    kc = k.astype(jnp.float32)
    vc = v.astype(jnp.float32)
    segc = kv_seg
    dkc = jnp.zeros(k.shape, jnp.float32)
    dvc = jnp.zeros(v.shape, jnp.float32)

    def block_grads(kc, vc, segc, *, block_causal):
        dq_t = flash_attention_bwd_dq_pallas(
            qf, kc, vc, gf, lse, delta, q_seg, segc,
            causal=block_causal, q_block=q_block, kv_block=kv_block,
            scale=scale, interpret=interpret,
        )
        dk_t, dv_t = flash_attention_bwd_dkv_pallas(
            qf, kc, vc, gf, lse, delta, q_seg, segc,
            causal=block_causal, q_block=q_block, kv_block=kv_block,
            scale=scale, interpret=interpret,
        )
        return dq_t, dk_t, dv_t

    for t in range(axis_size):
        if t == 0:
            dq_t, dk_t, dv_t = block_grads(kc, vc, segc, block_causal=causal)
            dq, dkc, dvc = dq + dq_t, dkc + dk_t, dvc + dv_t
        else:
            live = _block_overlap(q_seg, segc)
            if causal:
                live = live & (my >= t)

            def run(kc, vc, segc):
                return block_grads(kc, vc, segc, block_causal=False)

            def skip(kc, vc, segc):
                return (
                    jnp.zeros(q.shape, jnp.float32),
                    jnp.zeros(k.shape, jnp.float32),
                    jnp.zeros(v.shape, jnp.float32),
                )

            dq_t, dk_t, dv_t = lax.cond(live, run, skip, kc, vc, segc)
            dq, dkc, dvc = dq + dq_t, dkc + dk_t, dvc + dv_t
        # rotate every step (k hops total) so the traveling dk/dv
        # accumulators land back on the rank that owns their kv block
        kc, vc, segc, dkc, dvc = _rotate(
            (kc, vc, segc, dkc, dvc), axis_name, axis_size
        )
    return (
        dq.astype(q.dtype), dkc.astype(k.dtype), dvc.astype(v.dtype),
        None, None,
    )


_ring.defvjp(_ring_fwd, _ring_bwd)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def ring_flash_attention(
    q,  # [B, Hq, S_local, dh] — this rank's contiguous Q shard
    k,  # [B, Hkv, S_local, dh]
    v,
    q_segment_ids=None,  # [B, S_local] int32 (-1 = padding); None = one doc
    kv_segment_ids=None,
    *,
    axis_name: str,
    causal: bool = True,
    scale: float | None = None,
    q_block: int = DEFAULT_Q_BLOCK,
    kv_block: int = DEFAULT_KV_BLOCK,
    interpret: bool = False,
):
    """Sequence-parallel segment-aware flash attention (Pallas per step).

    Call from inside ``shard_map`` over mesh axis ``axis_name``; each rank
    passes its contiguous shard of the packed window.  Matches the
    single-device packed kernel on the gathered window to fp32
    reassociation error (the tier-1 parity suite gates <=1e-5 rel-L2).
    """
    b, hq, sq, dh = q.shape
    if dh % 128 != 0:
        raise ValueError(f"flash ring attention needs head_dim % 128 == 0, got {dh}")
    if q_segment_ids is None:
        q_segment_ids = jnp.zeros((b, sq), jnp.int32)
        kv_segment_ids = jnp.zeros((b, k.shape[2]), jnp.int32)
    axis_size = ring_axis_size(axis_name)
    scale = scale if scale is not None else dh**-0.5
    qb = _pick_block(sq, q_block)
    kb = _pick_block(k.shape[2], kv_block)
    return _ring(
        q, k, v, q_segment_ids, kv_segment_ids, causal, axis_name,
        axis_size, scale, qb, kb, interpret,
    )


def ring_attention_ref(
    q, k, v, q_segment_ids=None, kv_segment_ids=None, *,
    axis_name: str, causal: bool = True, scale: float | None = None,
):
    """Pure-jnp ring attention (same layout/semantics as
    :func:`ring_flash_attention`); differentiable by plain JAX AD, so the
    ``ref`` backend trains through it and the Pallas ring is validated
    against it."""
    b, hq, sq, dh = q.shape
    if q_segment_ids is None:
        q_segment_ids = jnp.zeros((b, sq), jnp.int32)
        kv_segment_ids = jnp.zeros((b, k.shape[2]), jnp.int32)
    axis_size = ring_axis_size(axis_name)
    scale = scale if scale is not None else dh**-0.5
    block_fn = functools.partial(_block_ref, scale=scale)
    # upcast BEFORE the ring: AD then accumulates per-hop cotangents in
    # fp32 and rounds once at the boundary, like the Pallas backward
    out, _ = _ring_fwd_loop(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        q_segment_ids, kv_segment_ids,
        block_fn=block_fn, causal=causal, axis_name=axis_name,
        axis_size=axis_size,
    )
    return out.astype(q.dtype)


__all__ = [
    "ring_attention_ref",
    "ring_axis_size",
    "ring_flash_attention",
]
