"""Paged-attention Pallas kernel: decode over a paged KV-cache pool.

Continuous-batching serving (``repro.serve``) keeps every request's KV
cache in fixed-size pages drawn from one shared pool, addressed through a
per-request page table.  A page table *is* segment ids over the pool: the
same machinery the packed flash kernel uses to skip (q_tile, kv_tile)
pairs with disjoint segment ranges here skips whole pages past a
request's context length, the GQA group reduction happens on-chip, and
the fp32 (m, l, acc) online-softmax state carries across the page sweep
exactly as it carries across the kv sweep in ``flash.py``.

Layout:

* ``q``        — ``[B, Hq, dh]``: one new token per decode slot,
* ``k_pages``/``v_pages`` — ``[P, page_size, Hkv, dh]``: the shared pool
  (callers typically allocate P = num_pages + 1 with the last page as a
  scratch sink for inactive slots),
* ``page_table`` — ``[B, pages_max]`` int32: physical page of each
  logical page; every entry must be a valid pool index (point unused
  entries at a scratch page — they are fetched but fully masked),
* ``kv_lens``  — ``[B]`` int32: valid tokens per slot.  ``kv_lens == 0``
  rows emit exact zeros (inactive decode slots).

Grid = (B, pages_max) with the page sweep innermost ("arbitrary"
semantics); one grid step takes every kv head of a page.  The page table
and kv_lens ride in scalar-prefetch slots so the k/v BlockSpec index maps
can chase ``table[b, j]`` — the pool page is DMA'd directly; no gather
materializes the contiguous cache.

Block layout: a per-head block would be ``(g, dh)`` of q and
``(ps, 1, dh)`` of a page, and the TPU compiler refuses both (the last two
block dims must be (8, 128)-divisible or whole).  So q comes whole
(``[Hq, dh]``) and each page is viewed as ``[ps, Hkv * dh]`` (a free
reshape), from which kv head ``h`` is the lane-aligned slice
``[:, h*dh:(h+1)*dh]``.  Query rows of group ``h`` select their scores and
values with a row mask, so no unaligned sublane slice is needed.

Non-causal by construction: the query is the newest token, every cached
slot ``< kv_len`` is visible.  Forward only — decode needs no backward.
Validated in interpret mode like the rest of the Pallas stack; needs
``dh % 128 == 0`` (lane tiling) and ``Hq % Hkv == 0``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .flash import LSE_FLOOR, NEG_INF


def _paged_kernel(
    table_ref,  # scalar prefetch: [B, pages_max] int32
    lens_ref,  # scalar prefetch: [B] int32
    q_ref,  # [Hq, dh]
    k_ref,  # [ps, Hkv * dh]
    v_ref,  # [ps, Hkv * dh]
    o_ref,  # [Hq, dh]
    m_scr,  # VMEM [Hq, 1] f32
    l_scr,  # VMEM [Hq, 1] f32
    acc_scr,  # VMEM [Hq, dh] f32
    *,
    scale: float,
    pages_max: int,
    page_size: int,
    n_kv_heads: int,
):
    del table_ref  # consumed by the k/v index maps
    b = pl.program_id(0)
    j = pl.program_id(1)
    ctx = lens_ref[b]
    hq, dh = q_ref.shape
    g = hq // n_kv_heads

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def group_rows(shape, h):
        """Rows of q head group ``h`` (the q heads sharing kv head h)."""
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        return (row >= h * g) & (row < (h + 1) * g)

    # page tile-skip: the paged analog of flash.py's _tile_overlap —
    # logical page j holds slots [j*ps, (j+1)*ps); it is dead past ctx
    @pl.when(j * page_size < ctx)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale  # [Hq, dh]
        s = jnp.zeros((hq, page_size), jnp.float32)
        for h in range(n_kv_heads):
            k = k_ref[:, h * dh:(h + 1) * dh].astype(jnp.float32)  # [ps, dh]
            s = jnp.where(group_rows(s.shape, h), q @ k.T, s)
        slot = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (hq, page_size), 1
        )
        mask = slot < ctx
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)  # exp(NEG_INF - NEG_INF) guard
        pv = jnp.zeros((hq, dh), jnp.float32)
        for h in range(n_kv_heads):
            v = v_ref[:, h * dh:(h + 1) * dh].astype(jnp.float32)
            pv = jnp.where(group_rows(pv.shape, h), p @ v, pv)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(j == pages_max - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], LSE_FLOOR)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def paged_attention_pallas(
    q,  # [B, Hq, dh]
    k_pages,  # [P, page_size, Hkv, dh]
    v_pages,
    page_table,  # [B, pages_max] int32
    kv_lens,  # [B] int32
    *,
    scale: float | None = None,
    interpret: bool = False,
):
    """Returns the attention output ``[B, Hq, dh]`` (q's dtype)."""
    b, hq, dh = q.shape
    p_pool, ps, hkv, dh_k = k_pages.shape
    assert dh == dh_k and dh % 128 == 0
    assert hq % hkv == 0
    pages_max = page_table.shape[1]
    scale = scale if scale is not None else dh**-0.5

    from jax.experimental.pallas import tpu as pltpu

    rows = pl.BlockSpec((None, hq, dh), lambda bi, j, t, n: (bi, 0, 0))
    page = pl.BlockSpec(
        (None, ps, hkv * dh), lambda bi, j, t, n: (t[bi, j], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_max),
        in_specs=[rows, page, page],
        out_specs=rows,
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, pages_max=pages_max, page_size=ps,
            n_kv_heads=hkv,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, dh), q.dtype),
        name="paged_decode",
        interpret=interpret,
    )(
        page_table.astype(jnp.int32),
        kv_lens.astype(jnp.int32),
        q,
        k_pages.reshape(p_pool, ps, hkv * dh),
        v_pages.reshape(p_pool, ps, hkv * dh),
    )


def paged_attention_ref(
    q,  # [B, Hq, dh]
    k_pages,  # [P, page_size, Hkv, dh]
    v_pages,
    page_table,  # [B, pages_max] int32
    kv_lens,  # [B] int32
    *,
    scale: float | None = None,
):
    """jnp twin: gather pages to a contiguous view, masked softmax.

    The numeric oracle for the Pallas kernel and the CPU/dry-run serving
    path (any head_dim).  ``kv_lens == 0`` rows return exact zeros, like
    the kernel's LSE-floored finalize.
    """
    b, hq, dh = q.shape
    _, ps, hkv, _ = k_pages.shape
    g = hq // hkv
    pages_max = page_table.shape[1]
    scale = scale if scale is not None else dh**-0.5
    # [B, pages_max, ps, Hkv, dh] -> [B, S_max, Hkv, dh]
    k = k_pages[page_table].reshape(b, pages_max * ps, hkv, dh)
    v = v_pages[page_table].reshape(b, pages_max * ps, hkv, dh)
    k = jnp.repeat(k, g, axis=2)  # [B, S_max, Hq, dh]
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum(
        "bhd,bshd->bhs", q.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )
    valid = jnp.arange(pages_max * ps)[None, :] < kv_lens[:, None]  # [B, S]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(valid[:, None, :], p, 0.0)
    denom = jnp.maximum(p.sum(axis=-1, keepdims=True), LSE_FLOOR)
    out = jnp.einsum("bhs,bshd->bhd", p / denom, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_tile_counts(kv_lens, page_size: int, pages_max: int) -> tuple[int, int]:
    """(executed, total) pages per the kernel's skip rule — the host-side
    oracle benchmarks use to report the paged skip fraction, mirroring
    ``flash.attention_tile_counts``."""
    lens = np.asarray(kv_lens)
    total = int(lens.shape[0]) * pages_max
    executed = int(
        sum(min(-(-int(n) // page_size), pages_max) for n in lens)
    )
    return executed, total
