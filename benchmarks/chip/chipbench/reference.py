"""Plain reference of the benchmark's training step, in float32.

It imports nothing of the program.  From the seed it makes the same
initial weights, data and noise as the program's ``init_state``,
``make_diffusion_batch`` and ``rectified_flow_loss`` do (the draws are
specified here, not borrowed), then runs the MMDiT forward pass, the
rectified-flow loss, its gradient, the pool mean and AdamW in
straightforward ``jax.numpy`` at ``highest`` matmul precision.

The model is the one the program implements, which departs from the
public Wan 2.1 (see each config file's ``departures``): a gated
three-matrix MLP, one ``txt_in`` linear, per-head q/k RMSNorm on
self-attention only, no RoPE.  Parameters are held in the type the
configuration states (``param_dtype`` matrices, fp32 norms and biases)
and every computation on them is in fp32.

To fit one chip beside nothing else, each block is rematerialised and
attention runs in blocks of query rows.  ``matmul`` selects the control:
``"fp8"`` rounds every matmul operand to float8 e4m3 with a per-tensor
scale, and every gradient flowing back through one to e5m2.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .work import Dims

F32 = jnp.float32
BF16 = jnp.bfloat16
#: bytes of attention scores one query block may hold
_SCORE_BLOCK_BYTES = 128 * 2**20


# -- weights and data ---------------------------------------------------------


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


# -- matmul precision ----------------------------------------------------------


def _fp8_round(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _q8(x):
    return _fp8_round(x, jnp.float8_e4m3fn)


_q8.defvjp(
    lambda x: (_fp8_round(x, jnp.float8_e4m3fn), None),
    lambda _, g: (_fp8_round(g, jnp.float8_e5m2),),
)


def _einsum(matmul: str):
    if matmul == "fp32":
        return functools.partial(jnp.einsum, precision="highest")
    if matmul == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, _q8(a), _q8(b), precision="highest")
    raise ValueError(f"matmul must be 'fp32' or 'fp8', got {matmul!r}")


# -- forward and loss -----------------------------------------------------------


def _layer_norm(x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _modulate(x, scale, shift, eps):
    return _layer_norm(x, eps) * (1.0 + scale[:, None, :]) + shift[:, None, :]


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _attention(es, q, k, v):
    """Softmax attention, q [B, S, H, dh], k/v [B, N, H, dh], in blocks of
    query rows (each rematerialised in the backward pass)."""
    b, s, h, dh = q.shape
    n = k.shape[1]
    rows = _SCORE_BLOCK_BYTES // (b * h * n * 4)
    rows = max(8, min(s, 2 ** int(math.log2(max(rows, 1)))))
    n_blk = -(-s // rows)
    qp = jnp.pad(q, ((0, 0), (0, n_blk * rows - s), (0, 0), (0, 0)))
    qb = qp.reshape(b, n_blk, rows, h, dh).swapaxes(0, 1)

    @jax.checkpoint
    def one(qi):
        p = jax.nn.softmax(es("bqhd,bkhd->bhqk", qi, k) * dh**-0.5, axis=-1)
        return es("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one, qb).swapaxes(0, 1).reshape(b, n_blk * rows, h, dh)
    return out[:, :s]


def timestep_embedding(t, dim: int, max_period: float = 10_000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=F32) / half)
    ang = t[:, None] * 1000.0 * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def _block(es, dims: Dims, eps: float, x, txt, mod, bp):
    b, s, d = x.shape
    h, dh, i = dims.heads, dims.head_dim, dims.inner
    w = jax.tree.map(lambda a: a.astype(F32), bp)
    m = mod + w["mod_bias"][None]
    shift1, scale1, gate1, shift2, scale2, gate2 = (m[:, j] for j in range(6))

    qkv = es("bsd,de->bse", _modulate(x, scale1, shift1, eps), w["wqkv"])
    q = _rms_norm(qkv[..., :i].reshape(b, s, h, dh), w["qnorm"], eps)
    k = _rms_norm(qkv[..., i:2 * i].reshape(b, s, h, dh), w["knorm"], eps)
    v = qkv[..., 2 * i:].reshape(b, s, h, dh)
    ctx = _attention(es, q, k, v).reshape(b, s, i)
    x = x + gate1[:, None] * es("bsi,id->bsd", ctx, w["wo"])

    hn = _layer_norm(x, eps) * w["norm3"]["w"] + w["norm3"]["b"]
    qx = es("bsd,de->bse", hn, w["xq"]).reshape(b, s, h, dh)
    kvx = es("bnd,de->bne", txt, w["xkv"])
    n = txt.shape[1]
    kx = kvx[..., :i].reshape(b, n, h, dh)
    vx = kvx[..., i:].reshape(b, n, h, dh)
    x = x + es("bsi,id->bsd", _attention(es, qx, kx, vx).reshape(b, s, i), w["xo"])

    hm = _modulate(x, scale2, shift2, eps)
    mlp = w["mlp"]
    hid = jax.nn.silu(es("bsd,df->bsf", hm, mlp["w1"])) * es("bsd,df->bsf", hm, mlp["w3"])
    return x + gate2[:, None] * es("bsf,fd->bsd", hid, mlp["w2"])


def forward(params, dims: Dims, latents, text, t, *, matmul: str = "fp32",
            eps: float = 1e-6):
    """Velocity prediction [B, S, patch_in] in fp32."""
    es = _einsum(matmul)
    f = lambda name: params[name].astype(F32)  # noqa: E731
    x = es("bsp,pd->bsd", latents.astype(F32), f("x_in"))
    txt = es("bnk,kd->bnd", text.astype(F32), f("txt_in"))
    temb = jax.nn.silu(es("bf,fd->bd", timestep_embedding(t, dims.freq_dim), f("t_mlp1")))
    mod = es("bd,de->be", temb, f("t_mlp2")).reshape(-1, 6, dims.d)

    @jax.checkpoint
    def body(x, bp):
        return _block(es, dims, eps, x, txt, mod, bp), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    fm = es("bd,de->be", temb, f("final_mod")).reshape(-1, 2, dims.d)
    x = _modulate(x, fm[:, 0], fm[:, 1], eps)
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


# -- optimizer -------------------------------------------------------------------


def adamw(params, grad_sum, n, m, v, step, opt: dict):
    """AdamW as the configuration states it on the pool-mean gradient
    ``grad_sum / n``: global-norm clipping, fp32 moments with bias
    correction (``step`` counts from 0), decoupled weight decay on every
    leaf of rank 2 or more as stored, new parameters rounded to their
    stored type."""
    grads = jax.tree.map(lambda g: g / n, grad_sum)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = opt["beta1"], opt["beta2"]
    bc1, bc2 = 1.0 - b1 ** (step + 1), 1.0 - b2 ** (step + 1)

    def one(p, g, mi, vi):
        g = g * clip
        mi = b1 * mi + (1 - b1) * g
        vi = b2 * vi + (1 - b2) * g * g
        delta = (mi / bc1) / (jnp.sqrt(vi / bc2) + opt["eps"])
        pf = p.astype(F32)
        if p.ndim >= 2:
            delta = delta + opt["weight_decay"] * pf
        return (pf - opt["lr"] * delta).astype(p.dtype), mi, vi

    out = jax.tree.map(one, params, grads, m, v)
    pick = lambda j: jax.tree.map(lambda t: t[j], out, is_leaf=lambda t: isinstance(t, tuple))  # noqa: E731
    return pick(0), pick(1), pick(2)


# -- per-leaf norms ----------------------------------------------------------------


def leaf_norms(tree, base=None) -> dict[str, float]:
    """fp32 norm of every leaf of ``tree`` (of ``tree - base`` where ``base``
    is given), stacked block leaves one per layer, keyed by path.  A small
    jitted reduction a leaf at a time: the leaves stay on the device."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    bases = jax.tree.leaves(base) if base is not None else [None] * len(flat)
    out = {}
    for (path, leaf), b in zip(flat, bases):
        name = jax.tree_util.keystr(path)
        axes = tuple(range(1, leaf.ndim)) if name.startswith("['blocks']") else None
        n = np.asarray(_norm(leaf, b, axes))
        if n.ndim:
            out.update({f"{name}[{j}]": float(x) for j, x in enumerate(n)})
        else:
            out[name] = float(n)
    return out


@functools.partial(jax.jit, static_argnums=2)
def _norm(x, base, axes):
    x = x.astype(F32) if base is None else x.astype(F32) - base.astype(F32)
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))


# -- the reference run -------------------------------------------------------------


class Reference:
    """Follows a program's first steps: from the seed's weights, for each
    step a pool of microbatches ``[(key, b, s), ...]`` and its step key,
    the pool-mean loss and gradient, then AdamW.

    Returns the readings the comparison needs: each step's loss, the first
    step's gradient as the optimizer sees it (first moment over
    ``1 - beta1``) per leaf, and each leaf's change over all the steps."""

    def __init__(self, dims: Dims, opt: dict, *, dtype=BF16, matmul: str = "fp32",
                 half_rows: bool = False):
        self.dims, self.opt, self.dtype = dims, opt, jnp.dtype(dtype)
        lf = functools.partial(loss, dims=dims, matmul=matmul, half_rows=half_rows)
        self._grad = jax.jit(
            lambda p, batch, rng: jax.value_and_grad(lambda q: lf(q, batch=batch, rng=rng))(p)
        )
        self._acc = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g), donate_argnums=0)
        self._update = jax.jit(
            lambda p, g, n, m, v, step: adamw(p, g, n, m, v, step, opt),
            donate_argnums=(0, 3, 4),
        )
        self._init = jax.jit(init_params, static_argnums=(1, 2))
        self._batch = jax.jit(make_batch, static_argnums=(1, 2, 3, 4))

    def run(self, init_key, steps) -> dict:
        """``steps``: ``[(step_key, [(batch_key, b, s), ...]), ...]``."""
        with jax.default_matmul_precision("highest"):
            params = self._init(init_key, self.dims, self.dtype)
            m = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
            v = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
            losses, grad_norms = [], None
            for i, (step_key, pool) in enumerate(steps):
                acc, total = None, 0.0
                for j, (bkey, b, s) in enumerate(pool):
                    batch = self._batch(bkey, b, s, self.dims, self.dtype)
                    lo, g = self._grad(params, batch, jax.random.fold_in(step_key, j))
                    del batch
                    total += float(lo)
                    acc = g if acc is None else self._acc(acc, g)
                    del g
                n = len(pool)
                params, m, v = self._update(
                    params, acc, jnp.float32(n), m, v, jnp.float32(i)
                )
                del acc
                losses.append(total / n)
                if i == 0:
                    b1 = self.opt["beta1"]
                    grad_norms = {k: x / (1 - b1) for k, x in leaf_norms(m).items()}
            del m, v
            change = leaf_norms(params, self._init(init_key, self.dims, self.dtype))
        return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}

