"""Ring segment-aware flash attention: sharded-vs-single-device parity.

One long packed window spans k ranks: each rank holds a contiguous Q
shard and KV rotates around the ring (``ppermute``), with the segment-id
tile skip pricing remote KV blocks exactly like local ones.  These tests
gate the ring lowering (both the Pallas kernel and the jnp reference)
against the single-device packed kernel: forward AND backward, causal and
bidirectional, ragged (-1-padded) segment layouts, f32 <= 1e-5 and bf16
<= 1e-3 relative L2.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.flash_attention.flash import flash_attention_fwd_pallas
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ring import (
    ring_attention_ref,
    ring_flash_attention,
)


def _rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _segments(s: int, lengths) -> jnp.ndarray:
    ids = np.concatenate(
        [np.full(n, i, np.int32) for i, n in enumerate(lengths)]
    )
    ids = np.concatenate([ids, np.full(s - len(ids), -1, np.int32)])
    return jnp.asarray(ids[None])


def _run_case(kranks, s, lengths, causal, dt, *, pallas: bool):
    if jax.device_count() < kranks:
        pytest.skip(f"needs {kranks} devices")
    b, hq, hkv, dh = 1, 2, 1, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, hq, s, dh), jnp.float32).astype(dt)
    k = jax.random.normal(ks[1], (b, hkv, s, dh), jnp.float32).astype(dt)
    v = jax.random.normal(ks[2], (b, hkv, s, dh), jnp.float32).astype(dt)
    dy = jax.random.normal(ks[3], (b, hq, s, dh), jnp.float32)
    seg = _segments(s, lengths)

    mesh = Mesh(np.array(jax.devices()[:kranks]), ("seq",))
    if pallas:
        def ring_fn(q_, k_, v_, qs, kvs):
            return ring_flash_attention(
                q_, k_, v_, qs, kvs, axis_name="seq", causal=causal,
                interpret=True,
            )
    else:
        def ring_fn(q_, k_, v_, qs, kvs):
            return ring_attention_ref(
                q_, k_, v_, qs, kvs, axis_name="seq", causal=causal
            )
    sharded = jax.shard_map(
        ring_fn,
        mesh=mesh,
        in_specs=(P(None, None, "seq", None),) * 3 + (P(None, "seq"),) * 2,
        out_specs=P(None, None, "seq", None),
        check_vma=False,
    )

    out_ring = sharded(q, k, v, seg, seg)
    out_ref = flash_attention_fwd_pallas(
        q, k, v, seg, seg, causal=causal, interpret=True
    )[0]
    e_fwd = _rel(out_ring, out_ref)

    def ring_loss(q_, k_, v_):
        return jnp.sum(sharded(q_, k_, v_, seg, seg).astype(jnp.float32) * dy)

    def oracle_loss(q_, k_, v_):
        # ops.flash_attention carries the differentiable single-device
        # reference VJP (the fwd-only Pallas kernel has none)
        o = flash_attention(q_, k_, v_, seg, seg, causal=causal, interpret=True)
        return jnp.sum(o.astype(jnp.float32) * dy)

    g_ring = jax.grad(ring_loss, (0, 1, 2))(q, k, v)
    g_ref = jax.grad(oracle_loss, (0, 1, 2))(q, k, v)
    e_bwd = max(_rel(a, b_) for a, b_ in zip(g_ring, g_ref))
    tol = 1e-5 if dt == jnp.float32 else 1e-3
    assert e_fwd < tol, f"fwd rel-L2 {e_fwd:.2e} >= {tol}"
    assert e_bwd < tol, f"bwd rel-L2 {e_bwd:.2e} >= {tol}"


CASES = [
    (2, 512, [300, 150, 62], True),
    (2, 512, [300, 150, 50], False),
    (4, 1024, [700, 200, 100], True),
    (4, 1024, [500, 24], True),  # heavy ragged padding tail
]


class TestRingPallas:
    @pytest.mark.parametrize("kranks,s,lengths,causal", CASES)
    def test_f32_parity(self, kranks, s, lengths, causal):
        _run_case(kranks, s, lengths, causal, jnp.float32, pallas=True)

    @pytest.mark.parametrize(
        "kranks,s,lengths", [(2, 512, [300, 150, 62]), (4, 1024, [700, 200, 100])]
    )
    def test_bf16_parity(self, kranks, s, lengths):
        _run_case(kranks, s, lengths, True, jnp.bfloat16, pallas=True)


class TestRingReference:
    @pytest.mark.parametrize(
        "kranks,s,lengths,causal",
        [(2, 512, [300, 150, 62], True), (4, 1024, [700, 200, 100], False)],
    )
    def test_f32_parity(self, kranks, s, lengths, causal):
        _run_case(kranks, s, lengths, causal, jnp.float32, pallas=False)

    def test_bf16_parity(self):
        _run_case(2, 512, [300, 150, 62], True, jnp.bfloat16, pallas=False)


class TestRingAxisSize:
    def test_single_device_degenerates_to_packed(self):
        # k=1 "ring": no rotation, must equal the packed kernel bit-for-bit
        s = 256
        seg = _segments(s, [200, 30])
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 2, s, 128), jnp.float32)
        k = jax.random.normal(ks[1], (1, 1, s, 128), jnp.float32)
        v = jax.random.normal(ks[2], (1, 1, s, 128), jnp.float32)
        mesh = Mesh(np.array(jax.devices()[:1]), ("seq",))
        out = jax.shard_map(
            lambda q_, k_, v_, a, b_: ring_flash_attention(
                q_, k_, v_, a, b_, axis_name="seq", causal=True, interpret=True
            ),
            mesh=mesh,
            in_specs=(P(None, None, "seq", None),) * 3 + (P(None, "seq"),) * 2,
            out_specs=P(None, None, "seq", None),
            check_vma=False,
        )(q, k, v, seg, seg)
        ref = flash_attention_fwd_pallas(
            q, k, v, seg, seg, causal=True, interpret=True
        )[0]
        assert _rel(out, ref) < 1e-6
