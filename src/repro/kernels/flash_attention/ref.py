"""Pure-jnp oracle for the flash-attention kernel (segment-aware)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def attention_reference(
    q,  # [B, Hq, Sq, dh]
    k,  # [B, Hkv, Skv, dh]
    v,
    *,
    causal: bool = True,
    scale: float | None = None,
    q_segment_ids=None,  # [B, Sq] int; equal ids see each other,
    kv_segment_ids=None,  # [B, Skv]  but padding (-1) sees nothing
):
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else dh**-0.5
    # upcast BEFORE repeating: the backward then sums the per-q-head dk/dv
    # contributions in fp32 and rounds once, matching the kernel's on-chip
    # fp32 group reduction
    kr = jnp.repeat(k.astype(jnp.float32), g, axis=1)
    vr = jnp.repeat(v.astype(jnp.float32), g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale, kr)
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), jnp.bool_), k=skv - sq)[None, None]
    if q_segment_ids is not None:
        seg = (
            (q_segment_ids[:, None, :, None] == kv_segment_ids[:, None, None, :])
            & (q_segment_ids >= 0)[:, None, :, None]
        )  # [B, 1, Sq, Skv]
        mask = seg if mask is None else (mask & seg)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if mask is not None:
        # fully-masked rows: softmax over identical NEG_INF is uniform junk;
        # the kernel emits exact zeros there, so the oracle must too.
        p = jnp.where(mask.any(axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vr).astype(q.dtype)
