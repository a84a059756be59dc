"""Segment-aware flash attention: forward/backward vs per-segment references.

The contract under test: packed-window attention with segment ids must be
*indistinguishable* from running attention independently on every segment —
values and all three gradients — across causal/bidirectional, GQA group
sizes, and ragged final tiles, with the Pallas kernels in interpret mode.

Acceptance thresholds (ISSUE 2): gradient parity vs the jnp oracle within
1e-5 (f32) / 1e-3 (bf16), measured relative to the gradient magnitude (bf16
has ~7.8e-3 ulp at 1.0, so absolute parity below that is representable only
after normalization).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core.cost_model import packed_load
from repro.data.packing import pack_documents, segment_id_batch, window_segment_ids
from repro.kernels.flash_attention.flash import attention_tile_counts
from repro.kernels.flash_attention.ops import choose_tiles, flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.models.attention import (
    blocked_attention,
    segment_relative_positions,
)

DH = 128  # kernel minimum head dim


def _inputs(key, b, hq, hkv, sq, skv, dt):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, hq, sq, DH), jnp.float32).astype(dt)
    k = jax.random.normal(ks[1], (b, hkv, skv, DH), jnp.float32).astype(dt)
    v = jax.random.normal(ks[2], (b, hkv, skv, DH), jnp.float32).astype(dt)
    dy = jax.random.normal(ks[3], (b, hq, sq, DH), jnp.float32).astype(dt)
    return q, k, v, dy


def _segments(seg_lengths, b):
    ids = np.concatenate(
        [np.full(n, i, np.int32) for i, n in enumerate(seg_lengths)]
    )
    return jnp.asarray(np.tile(ids[None], (b, 1)))


def _per_segment_reference(q, k, v, seg_lengths, causal):
    """Stitch independent per-segment reference attention along S (the
    ISSUE's ground truth). Differentiable, so it also oracles gradients."""
    outs = []
    off = 0
    for n in seg_lengths:
        sl = slice(off, off + n)
        outs.append(
            attention_reference(
                q[:, :, sl], k[:, :, sl], v[:, :, sl], causal=causal
            )
        )
        off += n
    return jnp.concatenate(outs, axis=2)


def _rel_err(a, b):
    """Relative L2 parity (the acceptance metric: scale-normalized so bf16
    quantization of O(1) values doesn't swamp the algorithmic comparison)."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1.0))


def _grads(fn, q, k, v, dy):
    obj = lambda q, k, v: jnp.sum(
        fn(q, k, v).astype(jnp.float32) * dy.astype(jnp.float32)
    )
    return jax.grad(obj, (0, 1, 2))(q, k, v)


def _check_packed_case(seg_lengths, causal, group, dt, qb, kb, seed=0):
    tol = 1e-5 if dt == jnp.float32 else 1e-3
    b, hkv = 1, 2
    hq = hkv * group
    s = int(sum(seg_lengths))
    q, k, v, dy = _inputs(jax.random.PRNGKey(seed), b, hq, hkv, s, s, dt)
    seg = _segments(seg_lengths, b)

    flash = lambda q, k, v: flash_attention(
        q, k, v, seg, seg, causal=causal, q_block=qb, kv_block=kb, interpret=True
    )
    ref = lambda q, k, v: _per_segment_reference(q, k, v, seg_lengths, causal)

    assert _rel_err(flash(q, k, v), ref(q, k, v)) < tol, "forward mismatch"
    for name, g_p, g_r in zip("qkv", _grads(flash, q, k, v, dy), _grads(ref, q, k, v, dy)):
        err = _rel_err(g_p, g_r)
        assert err < tol, f"d{name} rel err {err} >= {tol}"


# -- deterministic coverage (runs without hypothesis) ------------------------


@pytest.mark.parametrize(
    "seg_lengths,causal,group,dt",
    [
        ((100, 156), False, 1, jnp.float32),   # bidirectional DiT mode
        ((100, 156), True, 1, jnp.float32),    # causal packed LM
        ((64, 100, 92), False, 2, jnp.float32),  # GQA + 3 segments
        ((64, 100, 92), True, 2, jnp.float32),
        ((80, 120), True, 1, jnp.bfloat16),
        ((37, 91), False, 1, jnp.float32),     # ragged: S=128, odd boundaries
        ((60, 61), True, 2, jnp.float32),      # ragged total (121 -> pad)
    ],
)
def test_segment_flash_matches_per_segment_reference(seg_lengths, causal, group, dt):
    _check_packed_case(seg_lengths, causal, group, dt, qb=64, kb=64)


def test_flash_backward_parity_no_segments():
    """Acceptance: the Pallas backward (no segments) matches the jnp oracle
    within 1e-5 (f32) / 1e-3 (bf16), relative to gradient magnitude."""
    for dt, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 1e-3)):
        q, k, v, dy = _inputs(jax.random.PRNGKey(1), 1, 4, 2, 256, 256, dt)
        flash = lambda q, k, v: flash_attention(
            q, k, v, causal=True, q_block=128, kv_block=128, interpret=True
        )
        ref = lambda q, k, v: attention_reference(q, k, v, causal=True)
        for g_p, g_r in zip(_grads(flash, q, k, v, dy), _grads(ref, q, k, v, dy)):
            assert _rel_err(g_p, g_r) < tol


def test_tile_skip_counts_follow_segments():
    """Non-overlapping (q_tile, kv_tile) pairs are skipped: executed tiles
    track Σ len_i², not S²."""
    window = 512
    lengths = [256, 128, 128]
    windows = pack_documents(lengths, window=window, p=2.0)
    seg = segment_id_batch(windows, window)  # one window
    executed, total = attention_tile_counts(
        seg, seg, q_block=128, kv_block=128, causal=False
    )
    assert total == 16
    # 256-doc -> 2x2 tiles, two 128-docs -> 1 tile each = 6 exact-aligned
    assert executed == 6
    frac_flops = packed_load(lengths, 2.0) / window**2
    assert abs(executed / total - frac_flops) < 1e-9  # aligned case: exact

    # unaligned boundaries stay conservative: never fewer tiles than flops
    lengths = [200, 180, 132]
    windows = pack_documents(lengths, window=window, p=2.0)
    seg = segment_id_batch(windows, window)
    executed, total = attention_tile_counts(
        seg, seg, q_block=128, kv_block=128, causal=False
    )
    assert executed / total >= packed_load(lengths, 2.0) / window**2
    assert executed < total  # but some pairs do get skipped


def test_tile_skip_matches_kernel_output():
    """Skipping must be output-invariant: a fully-disjoint layout computes
    the same values as the dense oracle (skipped tiles contribute nothing)."""
    seg_lengths = (128, 128)
    q, k, v, _ = _inputs(jax.random.PRNGKey(2), 1, 2, 2, 256, 256, jnp.float32)
    seg = _segments(seg_lengths, 1)
    o = flash_attention(
        q, k, v, seg, seg, causal=False, q_block=128, kv_block=128, interpret=True
    )
    o_ref = attention_reference(
        q, k, v, causal=False, q_segment_ids=seg, kv_segment_ids=seg
    )
    assert _rel_err(o, o_ref) < 1e-5
    executed, total = attention_tile_counts(
        seg, seg, q_block=128, kv_block=128, causal=False
    )
    assert (executed, total) == (2, 4)


def test_padding_only_tiles_skipped():
    """Padding on both sides: tiles that hold only padding rows or only
    padding columns are skipped (and counted so), real rows stay exact and
    padded rows read zero output and zero gradients."""
    s, real = 512, 200
    ids = np.full((1, s), -1, np.int32)
    ids[0, :real] = 0
    seg = jnp.asarray(ids)
    q, k, v, dy = _inputs(jax.random.PRNGKey(8), 1, 2, 2, s, s, jnp.float32)
    flash = lambda q, k, v: flash_attention(
        q, k, v, seg, seg, causal=False, q_block=128, kv_block=128, interpret=True
    )
    ref = lambda q, k, v: attention_reference(
        q, k, v, causal=False, q_segment_ids=seg, kv_segment_ids=seg
    )
    o = flash(q, k, v)
    assert _rel_err(o, ref(q, k, v)) < 1e-5
    assert float(jnp.max(jnp.abs(o[:, :, real:]))) == 0.0
    for g_p, g_r in zip(_grads(flash, q, k, v, dy), _grads(ref, q, k, v, dy)):
        assert _rel_err(g_p, g_r) < 1e-5
        assert float(jnp.max(jnp.abs(g_p[:, :, real:]))) == 0.0
    # q tiles 0-1 against kv tiles 0-1 hold real pairs; the 12 others hold
    # padding only on one side or both
    assert attention_tile_counts(
        seg, seg, q_block=128, kv_block=128, causal=False
    ) == (4, 16)


# -- shape-chosen tiles at the Wan buckets ------------------------------------

WAN_LENGTHS = (1024, 1560, 3600, 4680, 7800)
WAN_TEXT = 512
# (sq, skv) -> (q_block, sq padded, kv_block, skv padded), bf16, bidirectional
WAN_TILES = {
    (1024, 1024): (1024, 1024, 1024, 1024),
    (1560, 1560): (784, 1568, 1560, 1560),
    (3600, 3600): (400, 3600, 3600, 3600),
    (4680, 4680): (432, 4752, 4680, 4680),
    (7800, 7800): (224, 7840, 7800, 7800),
    (1024, 512): (1024, 1024, 512, 512),
    (1560, 512): (1560, 1560, 512, 512),
    (3600, 512): (3600, 3600, 512, 512),
    (4680, 512): (2352, 4704, 512, 512),
    (7800, 512): (3904, 7808, 512, 512),
}


@pytest.mark.parametrize(
    "sq,skv",
    [(s, s) for s in WAN_LENGTHS] + [(s, WAN_TEXT) for s in WAN_LENGTHS],
    ids=lambda x: str(x),
)
def test_tile_rule_at_wan_buckets(sq, skv):
    """Self-attention at every bucket of the benchmark's cells and
    cross-attention to the text tokens: the rule's tiles, at most 1/8 of
    the grid steps of 128x128 tiles, and an executed tile area (padding-only
    tiles skipped) within 1.15x the real Sq*Skv."""
    qb, sq_p, kb, skv_p = choose_tiles(sq, skv, jnp.dtype(jnp.bfloat16), False)
    assert (qb, sq_p, kb, skv_p) == WAN_TILES[(sq, skv)]
    assert sq_p % qb == 0 and skv_p % kb == 0
    assert qb % 16 == 0 or qb == sq_p
    assert kb % 128 == 0 or kb == skv_p == skv
    steps = (sq_p // qb) * (skv_p // kb)
    assert 8 * steps <= -(-sq // 128) * -(-skv // 128)
    qs = np.full((1, sq_p), -1, np.int32)
    qs[0, :sq] = 0
    ks = np.full((1, skv_p), -1, np.int32)
    ks[0, :skv] = 0
    executed, total = attention_tile_counts(
        qs, ks, q_block=qb, kv_block=kb, causal=False
    )
    assert total == steps
    assert executed * qb * kb <= 1.15 * sq * skv


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_shape_chosen_tiles_parity_ragged(dt):
    """A ragged Wan length (S=1560) under the shape-chosen tiles: forward
    and all three gradients match the oracle at the acceptance tolerances."""
    tol = 1e-5 if dt == jnp.float32 else 1e-3
    q, k, v, dy = _inputs(jax.random.PRNGKey(9), 1, 1, 1, 1560, 1560, dt)
    flash = lambda q, k, v: flash_attention(q, k, v, causal=False, interpret=True)
    ref = lambda q, k, v: attention_reference(q, k, v, causal=False)
    assert _rel_err(flash(q, k, v), ref(q, k, v)) < tol
    for name, g_p, g_r in zip("qkv", _grads(flash, q, k, v, dy), _grads(ref, q, k, v, dy)):
        err = _rel_err(g_p, g_r)
        assert err < tol, f"d{name} rel err {err} >= {tol}"


# -- blocked_attention (jnp oracle path) -------------------------------------


def test_blocked_attention_segments_match_reference():
    seg_lengths = (50, 78)
    b, h, s = 2, 2, 128
    q, k, v, _ = _inputs(jax.random.PRNGKey(3), b, h, h, s, s, jnp.float32)
    seg = _segments(seg_lengths, b)
    # blocked_attention uses [B, S, H, dh] layout
    qs, ks, vs = (x.swapaxes(1, 2) for x in (q, k, v))
    for causal in (False, True):
        o_b = blocked_attention(
            qs, ks, vs, causal=causal, kv_block=32,
            q_segment_ids=seg, kv_segment_ids=seg,
        ).swapaxes(1, 2)
        o_r = attention_reference(
            q, k, v, causal=causal, q_segment_ids=seg, kv_segment_ids=seg
        )
        assert _rel_err(o_b, o_r) < 1e-5


def test_blocked_attention_odd_kv_length_no_degenerate_block():
    """skv % kv_block != 0 must pad+mask, not fall back to one giant block."""
    b, s, h = 1, 100, 2  # 100 % 64 != 0
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (b, s, h, DH))
    k = jax.random.normal(ks[1], (b, s, h, DH))
    v = jax.random.normal(ks[2], (b, s, h, DH))
    for causal in (False, True):
        o_b = blocked_attention(q, k, v, causal=causal, kv_block=64)
        o_r = attention_reference(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), causal=causal
        ).swapaxes(1, 2)
        assert _rel_err(o_b, o_r) < 1e-5


def test_local_attention_respects_segment_boundaries():
    """Sliding-window attention must also stop at document boundaries."""
    from repro.models.attention import local_attention

    b, s, h, w = 1, 96, 2, 32
    seg_lengths = (40, 56)
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (b, s, h, DH))
    k = jax.random.normal(ks[1], (b, s, h, DH))
    v = jax.random.normal(ks[2], (b, s, h, DH))
    seg = _segments(seg_lengths, b)
    out = local_attention(q, k, v, window=w, segment_ids=seg)
    # per-document independent runs are the ground truth
    off = 0
    for n in seg_lengths:
        sl = slice(off, off + n)
        out_doc = local_attention(q[:, sl], k[:, sl], v[:, sl], window=w)
        assert _rel_err(out[:, sl], out_doc) < 1e-5
        off += n


def test_packed_microbatch_labels_stop_at_boundaries():
    from repro.data.pipeline import materialize_packed_windows

    mbs = materialize_packed_windows(
        [60, 33, 20, 70], window=128, p=2.0, vocab=256, seed=1
    )
    for mb in mbs:
        seg, labels, tokens = mb["segment_ids"], mb["labels"], mb["tokens"]
        # padding carries label 0 and token 0
        assert (labels[seg < 0] == 0).all() and (tokens[seg < 0] == 0).all()
        # a document's last token never predicts the next document
        boundary = seg[:, :-1] != seg[:, 1:]
        assert (labels[:, :-1][boundary] == 0).all()
        # interior labels are the shifted tokens
        interior = (~boundary) & (seg[:, :-1] >= 0)
        np.testing.assert_array_equal(
            labels[:, :-1][interior], tokens[:, 1:][interior]
        )


def test_packed_microbatch_load_single_intercept():
    from repro.core.cost_model import CostModel
    from repro.data.pipeline import materialize_packed_windows

    cm = CostModel(a=1.0, b=1e-6, p=2.0, r2=1.0)
    mbs = materialize_packed_windows(
        [60, 33, 20, 70], window=128, p=2.0, vocab=256,
        batch_windows=4, cost_model=cm,
    )
    (mb,) = mbs
    lens = [n for w in mb["windows"] for n in w.lengths]
    # the intercept appears once, however many windows are batched
    assert mb["load"] == pytest.approx(cm.a + cm.b * packed_load(lens, 2.0))


def test_pad_segment_id_constants_agree():
    """The -1 padding contract is declared in three jax-layering-separated
    modules; they must never drift."""
    from repro.data import packing as P
    from repro.kernels.flash_attention import ops as O
    from repro.models import attention as A

    assert P.PAD_SEGMENT_ID == O.PAD_SEGMENT_ID == A.PAD_SEGMENT_ID == -1


def test_segment_arg_pairs_enforced():
    q = jnp.zeros((1, 8, 1, DH))
    seg = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="both"):
        blocked_attention(q, q, q, q_segment_ids=seg)
    with pytest.raises(ValueError, match="both"):
        flash_attention(
            jnp.zeros((1, 1, 128, DH)), jnp.zeros((1, 1, 128, DH)),
            jnp.zeros((1, 1, 128, DH)), seg, None, interpret=True,
        )


def test_attention_dispatcher_rejects_ungroupable_heads():
    from repro import kernels as K

    q = jnp.zeros((1, 8, 6, DH))
    kv = jnp.zeros((1, 8, 4, DH))
    with pytest.raises(ValueError, match="Hq % Hkv"):
        K.attention(q, kv, kv, causal=True)


def test_ragged_padding_uses_lane_granule():
    """With no blocks given the tiles follow the length: sq=300 pads its q
    side only to the sublane granule and its kv side only to the lane
    granule (or not at all, as one whole-length kv tile), never to a
    mostly-padding block; values stay exact either way."""
    q, k, v, _ = _inputs(jax.random.PRNGKey(7), 1, 1, 1, 300, 300, jnp.float32)
    o = flash_attention(q, k, v, causal=True, interpret=True)  # default blocks
    o_r = attention_reference(q, k, v, causal=True)
    assert o.shape == q.shape
    assert _rel_err(o, o_r) < 1e-5


def test_explicit_blocks_pad_to_lane_granule():
    """A caller's blocks are kept: sq=300 with 256-blocks pads both sides to
    384 and runs 128-tiles, the padded kv columns masked by segment ids the
    wrapper makes; values and gradients stay exact."""
    q, k, v, dy = _inputs(jax.random.PRNGKey(10), 1, 1, 1, 300, 300, jnp.float32)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=False, q_block=256, kv_block=256, interpret=True
    )
    ref = lambda q, k, v: attention_reference(q, k, v, causal=False)
    assert _rel_err(flash(q, k, v), ref(q, k, v)) < 1e-5
    for g_p, g_r in zip(_grads(flash, q, k, v, dy), _grads(ref, q, k, v, dy)):
        assert _rel_err(g_p, g_r) < 1e-5


def test_pack_documents_rejects_oversize_docs():
    with pytest.raises(ValueError, match="chunk or drop"):
        pack_documents([1500, 100], window=1024, p=2.0)


def test_packed_microbatch_token_load_fallback():
    """p=None packing records zero loads; the microbatch falls back to token
    count so LPT/knapsack dispatch still has a signal."""
    from repro.data.pipeline import materialize_packed_windows

    mbs = materialize_packed_windows([60, 33, 20, 70], window=128, vocab=256)
    assert all(m["load"] > 0 for m in mbs)
    assert mbs[0]["load"] == sum(w.tokens for w in mbs[0]["windows"])


def test_segment_relative_positions():
    seg = jnp.asarray([[0, 0, 0, 1, 1, 2, -1, -1]], jnp.int32)
    pos = segment_relative_positions(seg)
    assert pos.tolist() == [[0, 1, 2, 0, 1, 0, 0, 1]]


def test_window_segment_ids_layout():
    windows = pack_documents([5, 3, 2], window=8, p=2.0)
    assert [w.lengths for w in windows] == [(5, 3), (2,)]
    ids = window_segment_ids(windows[0], 8)
    assert ids.dtype == np.int32
    assert ids.tolist() == [0, 0, 0, 0, 0, 1, 1, 1]
    ids2 = window_segment_ids(windows[1], 8)
    assert ids2.tolist() == [0, 0, -1, -1, -1, -1, -1, -1]  # -1 = padding
    for w in windows:
        assert w.load == packed_load(w.lengths, 2.0)


# -- fused_adaln divisor-selection satellite ---------------------------------


def test_adaln_block_helper_never_exceeds_target():
    from repro.kernels.fused_adaln.ops import _divisor_block
    from repro.kernels.fused_adaln.adaln import DEFAULT_D_BLOCK, DEFAULT_SEQ_BLOCK

    for n in (8, 40, 96, 97, 128, 640, 12289, 50000):
        for target in (DEFAULT_SEQ_BLOCK, DEFAULT_D_BLOCK):
            blk = _divisor_block(n, target)
            assert blk <= target and n % blk == 0
    assert _divisor_block(97, DEFAULT_SEQ_BLOCK) == 97  # below the target:
    # itself VMEM-safe.  Prime above the target: the old code fell back to n
    # (12289-row blocks); now degenerate -> 1, and callers fall back to the
    # jnp ref instead
    assert _divisor_block(12289, DEFAULT_SEQ_BLOCK) == 1
    assert _divisor_block(12289, DEFAULT_D_BLOCK) == 1


def test_adaln_prime_seq_falls_back_to_ref():
    from repro.kernels.fused_adaln.ops import adaln_modulate
    from repro.kernels.fused_adaln.ref import adaln_reference

    b, s, d = 2, 131, 256  # prime S above the seq target: no usable divisor
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (b, s, d))
    sc = jax.random.normal(ks[1], (b, d)) * 0.1
    sh = jax.random.normal(ks[2], (b, d)) * 0.1
    y = adaln_modulate(x, sc, sh, interpret=True)
    assert _rel_err(y, adaln_reference(x, sc, sh)) < 1e-5


# -- property-based sweep (skips when hypothesis is absent) ------------------


@settings(
    max_examples=8,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow],
)
@given(
    seg_lengths=st.lists(st.integers(16, 80), min_size=1, max_size=3),
    causal=st.booleans(),
    group=st.sampled_from([1, 2]),
)
def test_property_segment_flash_fwd_bwd(seg_lengths, causal, group):
    """Property (ISSUE 2 satellite): segment-masked flash attention —
    forward and backward — matches per-segment independent reference across
    causal/bidirectional, GQA group sizes, and ragged final tiles."""
    _check_packed_case(
        tuple(seg_lengths), causal, group, jnp.float32, qb=64, kb=64,
        seed=sum(seg_lengths),
    )
