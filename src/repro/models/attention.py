"""Attention implementations that never materialize [S, S] scores.

* ``blocked_attention``   — flash-style lax.scan over KV blocks with running
  (m, l, acc) softmax state.  Memory O(Sq * kv_block); used for training and
  prefill (causal) and for cross-attention (full).  On TPU the Pallas
  flash-attention kernel replaces it; this jnp version is its oracle and the
  SPMD-friendly CPU/dry-run path.
* ``local_attention``     — Griffin-style windowed causal attention via
  chunking (attend to own + previous chunk), memory O(S * 2w).
* ``decode_attention``    — one-token query against a KV cache (masked
  single-shot softmax; scores are only [B, H, S]).

All softmax math is fp32 regardless of input dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38

# must match kernels.flash_attention.ops.PAD_SEGMENT_ID (duplicated so this
# module stays importable without pallas; drift is guarded by a unit test)
PAD_SEGMENT_ID = -1


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, Hkv, dh] -> [B, S, Hkv*n_rep, dh] (GQA head replication)."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def blocked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    kv_block: int = 1024,
    q_offset: int = 0,
    scale: float | None = None,
    q_segment_ids: jax.Array | None = None,  # [B, Sq] int; -1 = padding
    kv_segment_ids: jax.Array | None = None,  # [B, Skv]
) -> jax.Array:
    """q: [B, Sq, H, dh], k/v: [B, Skv, H, dh] (same head count; GQA callers
    repeat kv first).  Returns [B, Sq, H, dh] in q.dtype.

    Segment-id masking (equality defines visibility; padding, -1, sees
    nothing) is the CPU/dry-run oracle for the Pallas kernel's packed-window
    path.  A Skv that doesn't divide ``kv_block`` is padded on the KV side
    with masked keys — score memory stays O(Sq · kv_block) for odd lengths
    instead of degenerating to one O(Sq · Skv) block.
    """
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("pass both q_segment_ids and kv_segment_ids, or neither")
    b, sq, h, dh = q.shape
    skv = k.shape[1]
    kv_block = min(kv_block, skv)
    pad = -skv % kv_block
    if pad:
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
        if kv_segment_ids is not None:
            kv_segment_ids = jnp.pad(
                kv_segment_ids, ((0, 0), (0, pad)), constant_values=PAD_SEGMENT_ID
            )
    n_blocks = (skv + pad) // kv_block
    scale = scale if scale is not None else dh**-0.5

    qf = q.astype(jnp.float32) * scale
    kb = k.reshape(b, n_blocks, kv_block, h, dh).swapaxes(0, 1)
    vb = v.reshape(b, n_blocks, kv_block, h, dh).swapaxes(0, 1)
    q_pos = q_offset + jnp.arange(sq)
    if kv_segment_ids is not None:
        seg_b = kv_segment_ids.astype(jnp.int32).reshape(b, n_blocks, kv_block)
        seg_b = seg_b.swapaxes(0, 1)  # [n_blocks, B, kv_block]
        q_seg = q_segment_ids.astype(jnp.int32)
    else:
        seg_b = jnp.zeros((n_blocks, b, 0), jnp.int32)  # unused scan leaf
        q_seg = None

    @jax.checkpoint  # recompute per-block scores in bwd: the scan must not
    def body(carry, xs):  # stack [n_blocks, B, H, Sq, kb] f32 residuals
        m, denom, acc = carry
        kj, vj, segj, j = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kj.astype(jnp.float32))
        k_pos = j * kv_block + jnp.arange(kv_block)
        mask = (k_pos < skv)[None, None, None, :] if pad else None
        if causal:
            cm = (q_pos[:, None] >= k_pos[None, :])[None, None]
            mask = cm if mask is None else (mask & cm)
        if q_seg is not None:
            sm = (q_seg[:, None, :, None] == segj[:, None, None, :]) & (
                q_seg >= 0
            )[:, None, :, None]  # padding (-1) sees nothing
            mask = sm if mask is None else (mask & sm)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)  # exact zeros on fully-masked rows
        denom_new = denom * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vj.astype(jnp.float32)
        )
        return (m_new, denom_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    d0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dh), jnp.float32)
    (m, denom, acc), _ = jax.lax.scan(
        body, (m0, d0, a0), (kb, vb, seg_b, jnp.arange(n_blocks))
    )
    out = acc / jnp.maximum(denom, 1e-37)[..., None]
    return out.swapaxes(1, 2).astype(q.dtype)  # [B, Sq, H, dh]


def segment_relative_positions(segment_ids: jax.Array) -> jax.Array:
    """[B, S] segment ids (contiguous runs) -> position within each run.

    Packed windows need RoPE positions that restart at every document
    boundary; padding (-1) runs restart too, which is harmless.
    """
    b, s = segment_ids.shape
    idx = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    boundary = jnp.concatenate(
        [
            jnp.ones((b, 1), jnp.bool_),
            segment_ids[:, 1:] != segment_ids[:, :-1],
        ],
        axis=1,
    )
    run_start = jax.lax.cummax(jnp.where(boundary, idx, 0), axis=1)
    return idx - run_start


def local_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int,
    scale: float | None = None,
    segment_ids: jax.Array | None = None,  # [B, S] int; -1 = padding
) -> jax.Array:
    """Causal sliding-window attention (Griffin local layers).

    A token at position t attends to positions (t - window, t].  S must be a
    multiple of ``window``; each chunk attends to itself + previous chunk.
    With ``segment_ids`` (packed windows) the sliding window additionally
    stops at document boundaries.
    """
    b, s, h, dh = q.shape
    w = window
    if s <= w:
        return blocked_attention(
            q, k, v, causal=True, kv_block=min(s, 1024), scale=scale,
            q_segment_ids=segment_ids, kv_segment_ids=segment_ids,
        )
    if s % w != 0:
        # pad at the end: padded keys are strictly in the future of every real
        # query under the causal window mask, so outputs for [:s] are exact.
        pad = w - s % w
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        if segment_ids is not None:
            segment_ids = jnp.pad(
                segment_ids, ((0, 0), (0, pad)), constant_values=PAD_SEGMENT_ID
            )
        out = local_attention(
            jnp.pad(q, padw), jnp.pad(k, padw), jnp.pad(v, padw),
            window=window, scale=scale, segment_ids=segment_ids,
        )
        return out[:, :s]
    t = s // w
    scale = scale if scale is not None else dh**-0.5

    qc = q.reshape(b, t, w, h, dh)
    kc = k.reshape(b, t, w, h, dh)
    vc = v.reshape(b, t, w, h, dh)
    # previous chunk (zero-padded for chunk 0)
    kprev = jnp.pad(kc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    vprev = jnp.pad(vc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    k2 = jnp.concatenate([kprev, kc], axis=2)  # [B, T, 2w, H, dh]
    v2 = jnp.concatenate([vprev, vc], axis=2)

    sjk = jnp.einsum(
        "btqhd,btkhd->bthqk", qc.astype(jnp.float32) * scale, k2.astype(jnp.float32)
    )
    a_idx = jnp.arange(w)[:, None]  # query offset in chunk
    b_idx = jnp.arange(2 * w)[None, :]  # key offset in concat
    # global rel = w + a - b; valid iff 0 <= rel < w  <=>  a < b <= a + w
    mask = (b_idx > a_idx) & (b_idx <= a_idx + w)
    # chunk 0 has no previous chunk: keys with b < w are padding
    chunk_ids = jnp.arange(t)[:, None, None]
    mask = (mask[None] & ((b_idx[None] >= w) | (chunk_ids > 0)))[None]  # [1,T,w,2w]
    if segment_ids is not None:
        segc = segment_ids.astype(jnp.int32).reshape(b, t, w)
        segprev = jnp.pad(
            segc[:, :-1], ((0, 0), (1, 0), (0, 0)), constant_values=PAD_SEGMENT_ID
        )
        seg2 = jnp.concatenate([segprev, segc], axis=2)  # [B, T, 2w]
        mask = mask & (segc[:, :, :, None] == seg2[:, :, None, :])  # [B,T,w,2w]
    sjk = jnp.where(mask[:, :, None], sjk, NEG_INF)  # [B,T,H,w,2w]
    p = jax.nn.softmax(sjk, axis=-1)
    out = jnp.einsum("bthqk,btkhd->btqhd", p, v2.astype(jnp.float32))
    return out.reshape(b, s, h, dh).astype(q.dtype)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array | int,
    *,
    scale: float | None = None,
) -> jax.Array:
    """q: [B, 1, H, dh]; caches: [B, Smax, H, dh]; positions >= cache_len are
    masked out.  Returns [B, 1, H, dh]."""
    b, _, h, dh = q.shape
    smax = k_cache.shape[1]
    scale = scale if scale is not None else dh**-0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, k_cache.astype(jnp.float32)
    )  # [B, H, 1, Smax]
    mask = jnp.arange(smax)[None, None, None, :] < jnp.asarray(cache_len).reshape(
        -1, 1, 1, 1
    )
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)
