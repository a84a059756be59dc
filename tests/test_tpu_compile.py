"""Compile the training-path Pallas kernels for a described TPU v5e chip.

The Mosaic compiler runs here without a chip: it refuses what a chip's
would (block shapes off the (8, 128) tiling, 1-D blocks, layouts it cannot
relayout), which interpret mode never checks.  Shapes are Wan-2.1-1.3B's
widths (D=1536, 12 heads of 128, S=4096, 512 text tokens, bf16, batch 2).

All such compiles live in this one file: the topology is described in a
module-scoped fixture, so only the worker that runs this file loads the
TPU library (see the fixture).  Nothing here runs a kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.paged import paged_attention_pallas
from repro.kernels.fused_adaln.ops import adaln_modulate
from repro.kernels.fused_rmsnorm.ops import gated_rms_norm, rms_norm

B, S, D, H, DH, TEXT = 2, 4096, 1536, 12, 128, 512


@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e:2x2 host.  Made here, never at
    import: only one process at a time may load the TPU library."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args) -> int:
    """Compile for the chip; returns the number of Pallas kernels in it."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


def _grads(fn, argnums):
    def loss(*a):
        return fn(*a).astype(jnp.float32).sum()

    return jax.grad(loss, argnums)


def test_adaln_fwd_bwd_batch_2(one_chip):
    x = jax.ShapeDtypeStruct((B, S, D), jnp.bfloat16, sharding=one_chip)
    mod = jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=one_chip)
    # forward, then the backward's dx and dmod kernels next to a forward
    assert _compile(adaln_modulate, x, mod, mod) == 1
    assert _compile(_grads(adaln_modulate, (0, 1, 2)), x, mod, mod) == 3


@pytest.mark.parametrize("gated", [False, True], ids=["rms", "gated_rms"])
def test_rmsnorm_fwd_bwd(one_chip, gated):
    w = jax.ShapeDtypeStruct((DH,), jnp.float32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((B, S, H, DH), jnp.bfloat16, sharding=one_chip)
    if gated:
        n = _compile(_grads(gated_rms_norm, (0, 1, 2)), x, w, x)
    else:  # per-head q/k norm, as every MMDiT block calls it
        n = _compile(_grads(rms_norm, (0, 1)), x, w)
    assert n == 3  # forward, dx, dw


FLASH_CASES = {
    # name: (kv length, segment ids, causal)
    "self": (S, False, False),
    "self_segments": (S, True, False),
    "self_segments_causal": (S, True, True),
    "cross_text": (TEXT, False, False),
    "cross_text_segments": (TEXT, True, False),
    "self_ragged": (S - 100, False, False),  # padded to the tile grid
}


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_fwd_bwd(one_chip, case):
    skv, segments, causal = FLASH_CASES[case]
    sq = skv if case == "self_ragged" else S

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q = sds((B, H, sq, DH))
    kv = sds((B, H, skv, DH))
    args = [q, kv, kv]
    if segments:
        args += [sds((B, sq), jnp.int32), sds((B, skv), jnp.int32)]

    def attn(q, k, v, *seg):
        return flash_attention(q, k, v, *seg, causal=causal)

    assert _compile(attn, *args) == 1
    assert _compile(_grads(attn, (0, 1, 2)), *args) == 3  # fwd, dq, dkv


@pytest.mark.parametrize(
    "b,s,skv",
    [(1, 7800, 7800), (5, 1560, 1560), (1, 7800, TEXT), (5, 1560, TEXT)],
    ids=["self_7800", "self_1560", "cross_7800", "cross_1560"],
)
def test_flash_fwd_bwd_wan_buckets(one_chip, b, s, skv):
    """The shape-chosen tiles at the longest and the batched Wan bucket,
    12 heads, bf16: a tile whose kernels overflow VMEM fails here."""
    q = jax.ShapeDtypeStruct((b, H, s, DH), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, H, skv, DH), jnp.bfloat16, sharding=one_chip)

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=False)

    assert _compile(attn, q, kv, kv) == 1
    assert _compile(_grads(attn, (0, 1, 2)), q, kv, kv) == 3  # fwd, dq, dkv


@pytest.mark.parametrize("hq,hkv", [(16, 4), (4, 2)], ids=["gqa4", "gqa2"])
def test_paged_decode(one_chip, hq, hkv):
    b, ps, pool, pages_max = 8, 16, 257, 64

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pages = sds((pool, ps, hkv, DH))
    n = _compile(
        paged_attention_pallas,
        sds((b, hq, DH)), pages, pages,
        sds((b, pages_max), jnp.int32), sds((b,), jnp.int32),
    )
    assert n == 1
