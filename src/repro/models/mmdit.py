"""Wan-2.1-style video diffusion transformer (the paper's home architecture).

Block layout (Wan 2.1 / DiT-with-cross-attn, AdaLN conditioning):

    m = t_emb-derived modulation (6 x [B, d]: shift/scale/gate x 2)
    x = x + gate1 * self_attn( adaln_modulate(x, scale1, shift1) )   <- paper kernel
    x = x + cross_attn( norm3(x), text )
    x = x + gate2 * mlp( adaln_modulate(x, scale2, shift2) )         <- paper kernel

``adaln_modulate`` routes through ``repro.kernels`` — the fused
LayerNorm-Modulate op that is the paper's second contribution.  QK-Norm is
the fused q/k RMSNorm (paper §4.4).  Each sub-layer runs under a
``jax.named_scope`` (``adaln``, ``self_attn``, ``cross_attn``, ``mlp``), so a
profiler trace names the device ops of each.

Training objective: rectified flow (x_t = (1-t) x0 + t eps, predict v = eps - x0),
matching Wan 2.1's flow-matching setup.

Sequences are the variable-length visual token streams produced by the
bucketing pipeline: one compiled train_step per bucket shape.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import kernels as K

from .config import ModelConfig
from .layers import dense_init, mlp_params, apply_mlp, norm_params, apply_norm

Params = dict[str, Any]


def timestep_embedding(t: jax.Array, dim: int, max_period: float = 10_000.0):
    """Sinusoidal embedding of diffusion time t in [0, 1] -> [B, dim]."""
    half = dim // 2
    freqs = jnp.exp(-jnp.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = t.astype(jnp.float32)[:, None] * 1000.0 * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def _block_params(key, cfg: ModelConfig, dtype) -> Params:
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    return {
        "wqkv": dense_init(ks[0], d, 3 * h * dh, dtype),
        "wo": dense_init(ks[1], h * dh, d, dtype),
        "qnorm": jnp.ones((dh,), jnp.float32),
        "knorm": jnp.ones((dh,), jnp.float32),
        "xq": dense_init(ks[2], d, h * dh, dtype),
        "xkv": dense_init(ks[3], d, 2 * h * dh, dtype),
        "xo": dense_init(ks[4], h * dh, d, dtype),
        "norm3": norm_params(d, "layernorm"),
        "mlp": mlp_params(ks[5], d, cfg.d_ff, dtype),
        # per-block learned bias on the 6 shared modulation signals (Wan-style)
        "mod_bias": jnp.zeros((6, d), jnp.float32),
    }


def init_params(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    in_dim = cfg.in_channels * 4  # 1x2x2 latent patchify
    params: Params = {
        "x_in": dense_init(ks[0], in_dim, d, dtype),
        "txt_in": dense_init(ks[1], 4096, d, dtype),  # umt5-xxl width
        "t_mlp1": dense_init(ks[2], 256, d, dtype),
        "t_mlp2": dense_init(ks[3], d, 6 * d, dtype),
        "final_mod": dense_init(ks[4], d, 2 * d, dtype),
        "x_out": dense_init(ks[5], d, in_dim, dtype),
    }
    blocks = jax.vmap(lambda k: _block_params(k, cfg, dtype))(
        jax.random.split(ks[6], cfg.n_layers)
    )
    params["blocks"] = blocks
    return params


def _block(bp: Params, x, txt, mod, cfg: ModelConfig, policy=None,
           segment_ids=None, text_segment_ids=None):
    """mod: [B, 6, d] modulation signals (shared t-emb + per-block bias).

    ``segment_ids`` ([B, S] int32, -1 = padding) scope self-attention to
    packed-window segments.  ``text_segment_ids`` ([B, S_txt] int32, -1 =
    padding) additionally scope cross-attention: a multi-clip packed video
    window carries one prompt per clip, and each clip's visual tokens must
    attend only to *their own* prompt's text states — ids match the visual
    ``segment_ids`` (clip j -> id j on both sides).  Without them the text
    stream is shared and cross-attention stays unsegmented.
    """
    b, s, d = x.shape
    h, dh = cfg.n_heads, cfg.head_dim
    if policy is not None:
        # sequence-parallel residual: AdaLN/projections/MLP run local on the
        # model axis; only attention k/v get gathered (EXPERIMENTS.md §Perf
        # wan iteration)
        x = policy.constrain(x, "resid")
    m = mod + bp["mod_bias"][None]
    shift1, scale1, gate1 = m[:, 0], m[:, 1], m[:, 2]
    shift2, scale2, gate2 = m[:, 3], m[:, 4], m[:, 5]

    # --- self attention with fused AdaLN-modulate
    with jax.named_scope("adaln"):
        hmod = K.adaln_modulate(x, scale1, shift1)
    with jax.named_scope("self_attn"):
        qkv = hmod @ bp["wqkv"]
        q = qkv[..., : h * dh].reshape(b, s, h, dh)
        k = qkv[..., h * dh : 2 * h * dh].reshape(b, s, h, dh)
        v = qkv[..., 2 * h * dh :].reshape(b, s, h, dh)
        q, k = K.qk_norm(q, k, bp["qnorm"], bp["knorm"])
        if policy is not None:
            q = policy.constrain(q, "attn_q")
            k = policy.constrain(k, "attn_kv")
            v = policy.constrain(v, "attn_kv")
        ctx = K.attention(  # full bidirectional; flash kernel on TPU backends
            q, k, v, causal=False,
            q_segment_ids=segment_ids, kv_segment_ids=segment_ids,
        )
        x = x + gate1[:, None, :].astype(x.dtype) * (ctx.reshape(b, s, h * dh) @ bp["wo"])

    # --- cross attention to text (segment-scoped for packed windows)
    with jax.named_scope("cross_attn"):
        hn = apply_norm(bp["norm3"], x, "layernorm", cfg.norm_eps)
        qx = (hn @ bp["xq"]).reshape(b, s, h, dh)
        n = txt.shape[1]
        kvx = txt @ bp["xkv"]
        kx = kvx[..., : h * dh].reshape(b, n, h, dh)
        vx = kvx[..., h * dh :].reshape(b, n, h, dh)
        ctx2 = K.attention(
            qx, kx, vx, causal=False,
            q_segment_ids=segment_ids if text_segment_ids is not None else None,
            kv_segment_ids=text_segment_ids,
        )
        x = x + ctx2.reshape(b, s, h * dh) @ bp["xo"]

    # --- MLP with fused AdaLN-modulate
    with jax.named_scope("adaln"):
        hmod2 = K.adaln_modulate(x, scale2, shift2)
    with jax.named_scope("mlp"):
        x = x + gate2[:, None, :].astype(x.dtype) * apply_mlp(bp["mlp"], hmod2)
    return x


def forward(
    params: Params,
    cfg: ModelConfig,
    latents,  # [B, S_vis, in_channels*4] patchified latent tokens
    text,  # [B, S_txt, 4096] precomputed text-encoder states (stub)
    t,  # [B] diffusion time in [0, 1]
    *,
    policy=None,
    remat: bool = True,
    unroll: bool = False,
    segment_ids=None,  # [B, S_vis] int32: packed-window doc ids (-1 = pad)
    text_segment_ids=None,  # [B, S_txt] int32: per-clip prompt ids (-1 = pad)
):
    if text_segment_ids is not None and segment_ids is None:
        raise ValueError(
            "text_segment_ids scope cross-attention per packed clip, which "
            "needs the visual segment_ids to match against; pass both"
        )
    x = latents @ params["x_in"]
    txt = text.astype(x.dtype) @ params["txt_in"]
    temb = timestep_embedding(t, 256).astype(x.dtype)
    temb = jax.nn.silu(temb @ params["t_mlp1"])
    mod = (temb @ params["t_mlp2"]).reshape(-1, 6, cfg.d_model).astype(jnp.float32)

    def superblock(x, bp):
        return _block(
            bp, x, txt, mod, cfg, policy=policy, segment_ids=segment_ids,
            text_segment_ids=text_segment_ids,
        ), None

    body = jax.checkpoint(superblock) if remat else superblock
    x, _ = jax.lax.scan(body, x, params["blocks"], unroll=unroll)

    fm = (temb @ params["final_mod"]).reshape(-1, 2, cfg.d_model).astype(jnp.float32)
    with jax.named_scope("adaln"):
        x = K.adaln_modulate(x, fm[:, 0], fm[:, 1])
    return x @ params["x_out"]


def rectified_flow_loss(
    params: Params,
    cfg: ModelConfig,
    x0,  # clean latent tokens [B, S, in_dim]
    text,
    rng,
    *,
    policy=None,
    unroll: bool = False,
    segment_ids=None,
    text_segment_ids=None,
):
    b = x0.shape[0]
    k1, k2 = jax.random.split(rng)
    t = jax.random.uniform(k1, (b,), jnp.float32)
    eps = jax.random.normal(k2, x0.shape, jnp.float32).astype(x0.dtype)
    xt = ((1.0 - t)[:, None, None] * x0 + t[:, None, None] * eps).astype(x0.dtype)
    v_target = (eps.astype(jnp.float32) - x0.astype(jnp.float32))
    v_pred = forward(
        params, cfg, xt, text, t,
        policy=policy, unroll=unroll, segment_ids=segment_ids,
        text_segment_ids=text_segment_ids,
    )
    return jnp.mean((v_pred.astype(jnp.float32) - v_target) ** 2)
