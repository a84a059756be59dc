"""The Wan-2.1 MMDiT family: its widths, its work counts, its plain
reference model, and how the program is configured and fed for it.

A configuration file names its family (``"family": "wan"``) and
``catalog.find_cell`` loads ``families/<family>.py`` by path.  A family
file gives:

* ``dims_of(config)``: the widths of a configuration file, in the
  program's terms;
* ``model_flops(dims, b, s)`` and ``step_kernel_seconds(dims,
  microbatches, peak_flops, peak_bytes)``: what the step needs, counted
  from shapes (``chipbench/work.py`` counts one kernel call);
* ``init_params``, ``make_batch`` and ``loss``: the plain reference model,
  which ``chipbench.reference.Reference`` drives;
* ``program_model(config)`` and ``program_batch(key, b, s, cfg)``: the
  program's model config at the configuration's depth, every width checked
  against the file, and one microbatch made by the program's own data
  maker.  These two are the only functions here that import the program.

Shapes follow the model as implemented (see the config files'
``departures``): a gated three-matrix MLP, one ``txt_in`` linear, per-head
q/k RMSNorm on self-attention only, cross-attention to ``text_len`` text
tokens, no RoPE.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench import reference as R
from chipbench.work import adaln_work, attention_flops, flash_work, least_seconds, matmul_flops

F32 = jnp.float32
BF16 = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class Dims:
    """Widths of an MMDiT configuration, in the program's terms."""

    d: int  # hidden size
    heads: int
    head_dim: int
    ffn: int  # MLP inner width (each of w1, w3)
    layers: int
    text_len: int  # cross-attention text tokens per sample
    text_dim: int  # text-encoder width fed to txt_in
    patch_in: int  # latent channels x patch volume, one token's input width
    freq_dim: int  # sinusoidal timestep embedding width

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim


def dims_of(config: dict) -> Dims:
    """The widths of a Wan configuration file, in the program's terms
    (one token's input width is the VAE's channels times the patch)."""
    pt, ph, pw = config["patch_size"]
    return Dims(
        d=config["dim"],
        heads=config["num_heads"],
        head_dim=config["dim"] // config["num_heads"],
        ffn=config["ffn_dim"],
        layers=config["num_layers"],
        text_len=config["text_len"],
        text_dim=config["text_dim"],
        patch_in=config["in_dim"] * pt * ph * pw,
        freq_dim=config["freq_dim"],
    )


# -- work counts ------------------------------------------------------------------


def model_flops(dims: Dims, b: int, s: int) -> int:
    """Forward + backward FLOPs of one microbatch of ``b`` samples of ``s``
    latent tokens: every matmul, self-attention at ``s^2``,
    cross-attention to the text tokens and the text-KV projections.
    Elementwise work (norms, modulation, activations) is not counted."""
    D, F, I, T = dims.d, dims.ffn, dims.inner, dims.text_len
    mm = matmul_flops
    per_sample = (
        mm(s, dims.patch_in, D, False)  # x_in (input: data)
        + mm(T, dims.text_dim, D, False)  # txt_in (input: data)
        + mm(1, dims.freq_dim, D, False)  # t_mlp1 (input: sinusoid)
        + mm(1, D, 6 * D, True)  # t_mlp2
        + mm(1, D, 2 * D, True)  # final_mod
        + mm(s, D, dims.patch_in, True)  # x_out
    )
    per_layer = (
        mm(s, D, 3 * I, True)  # wqkv
        + mm(s, I, D, True)  # wo
        + mm(s, D, I, True)  # xq
        + mm(T, D, 2 * I, True)  # xkv on the text tokens
        + mm(s, I, D, True)  # xo
        + 2 * mm(s, D, F, True)  # w1, w3
        + mm(s, F, D, True)  # w2
        + sum(attention_flops(1, dims.heads, s, s, dims.head_dim))
        + sum(attention_flops(1, dims.heads, s, T, dims.head_dim))
    )
    return b * (per_sample + dims.layers * per_layer)


def step_kernel_seconds(dims: Dims, microbatches, peak_flops: float,
                        peak_bytes: float) -> dict:
    """Least seconds of the flash and AdaLN calls of ``microbatches``
    (``[(b, s), ...]``): per block one self- and one cross-attention and
    two LayerNorm-Modulates, and one more LayerNorm-Modulate at the head."""
    flash = adaln = 0.0
    for b, s in microbatches:
        self_attn = flash_work(b, dims.heads, s, s, dims.head_dim)
        cross = flash_work(b, dims.heads, s, dims.text_len, dims.head_dim)
        flash += dims.layers * (
            least_seconds(self_attn, peak_flops, peak_bytes)
            + least_seconds(cross, peak_flops, peak_bytes)
        )
        adaln += (2 * dims.layers + 1) * least_seconds(
            adaln_work(b, s, dims.d), peak_flops, peak_bytes
        )
    return {"flash": flash, "adaln": adaln}


# -- the plain reference model ----------------------------------------------------
#
# It imports nothing of the program.  From the seed it makes the same
# initial weights, data and noise as the program's ``init_state``,
# ``make_diffusion_batch`` and ``rectified_flow_loss`` do (the draws are
# specified here, not borrowed).  Parameters are held in the type the
# configuration states (``param_dtype`` matrices, fp32 norms and biases)
# and every computation on them is in fp32.


def _dense(key, d_in: int, d_out: int, dtype):
    return (jax.random.normal(key, (d_in, d_out), F32) * d_in**-0.5).astype(dtype)


def _block_init(key, dims: Dims, dtype) -> dict:
    d, i = dims.d, dims.inner
    ks = jax.random.split(key, 8)
    k1, k2, k3 = jax.random.split(ks[5], 3)
    return {
        "wqkv": _dense(ks[0], d, 3 * i, dtype),
        "wo": _dense(ks[1], i, d, dtype),
        "qnorm": jnp.ones((dims.head_dim,), F32),
        "knorm": jnp.ones((dims.head_dim,), F32),
        "xq": _dense(ks[2], d, i, dtype),
        "xkv": _dense(ks[3], d, 2 * i, dtype),
        "xo": _dense(ks[4], i, d, dtype),
        "norm3": {"w": jnp.ones((d,), F32), "b": jnp.zeros((d,), F32)},
        "mlp": {
            "w1": _dense(k1, d, dims.ffn, dtype),
            "w3": _dense(k2, d, dims.ffn, dtype),
            "w2": _dense(k3, dims.ffn, d, dtype),
        },
        "mod_bias": jnp.zeros((6, d), F32),
    }


def init_params(key, dims: Dims, dtype=BF16) -> dict:
    """The initial parameters: each matrix N(0, 1/fan_in) drawn in fp32 and
    stored in ``dtype``, norm scales 1, biases 0 in fp32; blocks stacked on
    a leading layer axis, one key each."""
    d = dims.d
    ks = jax.random.split(key, 8)
    return {
        "x_in": _dense(ks[0], dims.patch_in, d, dtype),
        "txt_in": _dense(ks[1], dims.text_dim, d, dtype),
        "t_mlp1": _dense(ks[2], dims.freq_dim, d, dtype),
        "t_mlp2": _dense(ks[3], d, 6 * d, dtype),
        "final_mod": _dense(ks[4], d, 2 * d, dtype),
        "x_out": _dense(ks[5], d, dims.patch_in, dtype),
        "blocks": jax.vmap(lambda k: _block_init(k, dims, dtype))(
            jax.random.split(ks[6], dims.layers)
        ),
    }


def make_batch(key, b: int, s: int, dims: Dims, dtype=BF16) -> dict:
    """One microbatch: unit-Gaussian latent tokens and text states, drawn in
    fp32 and stored in ``dtype``."""
    k1, k2 = jax.random.split(key)
    return {
        "latents": jax.random.normal(k1, (b, s, dims.patch_in), F32).astype(dtype),
        "text": jax.random.normal(k2, (b, dims.text_len, dims.text_dim), F32).astype(dtype),
    }


def _block(es, dims: Dims, eps: float, x, txt, mod, bp):
    b, s, d = x.shape
    h, dh, i = dims.heads, dims.head_dim, dims.inner
    w = jax.tree.map(lambda a: a.astype(F32), bp)
    m = mod + w["mod_bias"][None]
    shift1, scale1, gate1, shift2, scale2, gate2 = (m[:, j] for j in range(6))

    qkv = es("bsd,de->bse", R.modulate(x, scale1, shift1, eps), w["wqkv"])
    q = R.rms_norm(qkv[..., :i].reshape(b, s, h, dh), w["qnorm"], eps)
    k = R.rms_norm(qkv[..., i:2 * i].reshape(b, s, h, dh), w["knorm"], eps)
    v = qkv[..., 2 * i:].reshape(b, s, h, dh)
    ctx = R.attention(es, q, k, v).reshape(b, s, i)
    x = x + gate1[:, None] * es("bsi,id->bsd", ctx, w["wo"])

    hn = R.layer_norm(x, eps) * w["norm3"]["w"] + w["norm3"]["b"]
    qx = es("bsd,de->bse", hn, w["xq"]).reshape(b, s, h, dh)
    kvx = es("bnd,de->bne", txt, w["xkv"])
    n = txt.shape[1]
    kx = kvx[..., :i].reshape(b, n, h, dh)
    vx = kvx[..., i:].reshape(b, n, h, dh)
    x = x + es("bsi,id->bsd", R.attention(es, qx, kx, vx).reshape(b, s, i), w["xo"])

    hm = R.modulate(x, scale2, shift2, eps)
    mlp = w["mlp"]
    hid = jax.nn.silu(es("bsd,df->bsf", hm, mlp["w1"])) * es("bsd,df->bsf", hm, mlp["w3"])
    return x + gate2[:, None] * es("bsf,fd->bsd", hid, mlp["w2"])


def forward(params, dims: Dims, latents, text, t, *, matmul: str = "fp32",
            eps: float = 1e-6):
    """Velocity prediction [B, S, patch_in] in fp32; each block is
    rematerialised in the backward pass."""
    es = R.einsum(matmul)
    f = lambda name: params[name].astype(F32)  # noqa: E731
    x = es("bsp,pd->bsd", latents.astype(F32), f("x_in"))
    txt = es("bnk,kd->bnd", text.astype(F32), f("txt_in"))
    temb = jax.nn.silu(es("bf,fd->bd", R.timestep_embedding(t, dims.freq_dim), f("t_mlp1")))
    mod = es("bd,de->be", temb, f("t_mlp2")).reshape(-1, 6, dims.d)

    @jax.checkpoint
    def body(x, bp):
        return _block(es, dims, eps, x, txt, mod, bp), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    fm = es("bd,de->be", temb, f("final_mod")).reshape(-1, 2, dims.d)
    x = R.modulate(x, fm[:, 0], fm[:, 1], eps)
    return es("bsd,dp->bsp", x, f("x_out"))


def loss(params, dims: Dims, batch: dict, rng, *, matmul: str = "fp32",
         half_rows: bool = False):
    """Rectified-flow loss of one microbatch: ``t ~ U(0, 1)`` per sample,
    ``eps ~ N(0, 1)`` rounded to the latents' bf16, ``x_t = (1 - t) x0 +
    t eps`` rounded to bf16, target ``eps - x0``; the mean squared error
    over every element.  ``half_rows`` plants a fault: the mean is taken
    over the first half of the microbatch's tokens only."""
    x0 = batch["latents"]
    k1, k2 = jax.random.split(rng)
    t = jax.random.uniform(k1, (x0.shape[0],), F32)
    eps = jax.random.normal(k2, x0.shape, F32).astype(x0.dtype)
    x0f, epsf = x0.astype(F32), eps.astype(F32)
    xt = ((1.0 - t)[:, None, None] * x0f + t[:, None, None] * epsf).astype(x0.dtype)
    err = (forward(params, dims, xt, batch["text"], t, matmul=matmul) - (epsf - x0f)) ** 2
    if half_rows:
        rows = err.reshape(-1, err.shape[-1])
        return rows[: rows.shape[0] // 2].mean()
    return err.mean()


# -- the program, configured and fed for this family ----------------------------


def program_model(config: dict):
    """The program's ``ModelConfig`` named by the file's
    ``program.config``, at the file's depth; every width of the file is
    checked against it (``ValueError`` where they disagree)."""
    import importlib

    dims = dims_of(config)
    module, fn = config["program"]["config"].split(":")
    cfg = getattr(importlib.import_module(module), fn)()
    want = {
        "d_model": dims.d, "n_heads": dims.heads, "n_kv_heads": dims.heads,
        "head_dim": dims.head_dim, "d_ff": dims.ffn, "text_len": dims.text_len,
        "in_channels": config["in_dim"], "dtype": config["param_dtype"],
        "opt_state_dtype": config["optimizer"]["state_dtype"], "norm_eps": config["eps"],
    }
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"config file and program disagree: {want} != {got}")
    return dataclasses.replace(cfg, n_layers=dims.layers)


def program_batch(key, b: int, s: int, cfg) -> dict:
    """One microbatch as the program's data maker makes it."""
    from repro.data.synthetic import make_diffusion_batch

    return make_diffusion_batch(key, b, s, cfg)
