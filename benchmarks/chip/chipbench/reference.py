"""Plain reference of the benchmark's training step, in float32: the
pieces every family's reference model is built from, AdamW, and the run
that follows a program's first steps.

It imports nothing of the program.  A family file
(``chipbench/families/<family>.py``) gives the model: ``init_params``,
``make_batch`` and ``loss``, built from the blocks here in straightforward
``jax.numpy`` at ``highest`` matmul precision.  :class:`Reference` draws
the weights and data from the seed, takes the pool-mean loss and gradient
of each step's microbatches and applies AdamW.

To fit one chip beside nothing else, attention runs in blocks of query
rows, each rematerialised.  ``matmul`` selects the control: ``"fp8"``
rounds every matmul operand to float8 e4m3 with a per-tensor scale, and
every gradient flowing back through one to e5m2.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16
#: bytes of attention scores one query block may hold
_SCORE_BLOCK_BYTES = 128 * 2**20


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


def einsum(matmul: str):
    """``jnp.einsum`` at ``highest`` precision, or the float8 control."""
    if matmul == "fp32":
        return functools.partial(jnp.einsum, precision="highest")
    if matmul == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, _q8(a), _q8(b), precision="highest")
    raise ValueError(f"matmul must be 'fp32' or 'fp8', got {matmul!r}")


# -- blocks of the forward pass ----------------------------------------------------


def layer_norm(x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def modulate(x, scale, shift, eps):
    return layer_norm(x, eps) * (1.0 + scale[:, None, :]) + shift[:, None, :]


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def attention(es, q, k, v):
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
    the pool-mean loss and gradient, then AdamW.  ``model`` is the family
    (``init_params``, ``make_batch``, ``loss``) and ``dims`` its widths.

    Returns the readings the comparison needs: each step's loss, the first
    step's gradient as the optimizer sees it (first moment over
    ``1 - beta1``) per leaf, and each leaf's change over all the steps."""

    def __init__(self, model, dims, opt: dict, *, dtype=BF16, matmul: str = "fp32",
                 half_rows: bool = False):
        self.dims, self.opt, self.dtype = dims, opt, jnp.dtype(dtype)
        lf = functools.partial(model.loss, dims=dims, matmul=matmul, half_rows=half_rows)
        self._grad = jax.jit(
            lambda p, batch, rng: jax.value_and_grad(lambda q: lf(q, batch=batch, rng=rng))(p)
        )
        self._acc = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g), donate_argnums=0)
        self._update = jax.jit(
            lambda p, g, n, m, v, step: adamw(p, g, n, m, v, step, opt),
            donate_argnums=(0, 3, 4),
        )
        self._init = jax.jit(model.init_params, static_argnums=(1, 2))
        self._batch = jax.jit(model.make_batch, static_argnums=(1, 2, 3, 4))

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
