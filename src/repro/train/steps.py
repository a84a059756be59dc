"""Step-function factories: train / prefill / decode for every arch family.

These are the functions the launcher jits (and the dry-run lowers).  They are
pure: ``state``/``caches`` in, new ones out.  Sharding enters only through
the optional ``ShardingPolicy`` (activation constraints) and the jit
in/out_shardings the launcher attaches.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import mmdit as M
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim.adamw import OptimizerConfig, adamw_update, init_opt_state

Params = Any


# -- state ---------------------------------------------------------------------


def init_state(key, cfg: ModelConfig, opt: OptimizerConfig) -> dict:
    if cfg.family == "mmdit":
        params = M.init_params(key, cfg)
    else:
        params = T.init_params(key, cfg)
    return {
        "params": params,
        "opt": init_opt_state(params, opt),
        "step": jnp.zeros((), jnp.int32),
    }


def state_shapes(cfg: ModelConfig, opt: OptimizerConfig) -> dict:
    """ShapeDtypeStruct pytree of the train state (no allocation)."""
    return jax.eval_shape(
        lambda: init_state(jax.random.PRNGKey(0), cfg, opt)
    )


# -- train -----------------------------------------------------------------------


def make_loss_fn(cfg: ModelConfig, policy=None, unroll: bool = False) -> Callable:
    n_groups = policy.n_dispatch_groups if policy is not None else 1

    def loss_fn(params, batch, rng):
        # packed variable-length microbatches carry segment ids (-1 = pad);
        # attention is then scoped per document and RoPE restarts per doc
        seg = batch.get("segment_ids") if isinstance(batch, dict) else None
        if cfg.family == "mmdit":
            # multi-clip packed windows additionally carry per-clip text
            # segment ids so cross-attention is scoped to each clip's prompt
            tseg = (
                batch.get("text_segment_ids") if isinstance(batch, dict)
                else None
            )
            return M.rectified_flow_loss(
                params, cfg, batch["latents"], batch["text"], rng, policy=policy,
                unroll=unroll, segment_ids=seg, text_segment_ids=tseg,
            )
        memory = batch.get("memory") if isinstance(batch, dict) else None
        return T.lm_loss(
            params,
            cfg,
            batch["tokens"],
            batch["labels"],
            memory=memory,
            policy=policy,
            n_groups=n_groups,
            unroll=unroll,
            segment_ids=seg,
        )

    return loss_fn


def make_pool_grad_step(cfg: ModelConfig, policy=None) -> Callable:
    """One pool microbatch's gradient step — the SINGLE definition every
    executor shares (``oracle_step``, ``PlanExecutor``, ``EmulatedEngine``).

    RNG derivation is the parity-critical part: ``fold_in(step_key,
    pool_index)`` with the pool enumerated rank-major.  Keeping it defined
    once means the <=1e-5 engine-vs-oracle gates can never drift because
    one copy changed its rng or enumeration order.
    """
    loss_fn = make_loss_fn(cfg, policy)

    def grad_step(params, batch, step_key, pool_index):
        rng = jax.random.fold_in(step_key, pool_index)
        return jax.value_and_grad(loss_fn)(params, batch, rng)

    return grad_step


def make_pool_update(opt: OptimizerConfig) -> Callable:
    """The optimizer update after a pool's gradients are summed: AdamW on
    the pool-mean gradient (fp32) and the pool-mean loss.  Shared by
    ``EmulatedEngine`` (jitted, state donated) and ``oracle_step``."""

    def update(state, grad_sum, loss_sum, n):
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) / n, grad_sum)
        new_params, new_opt, stats = adamw_update(
            state["params"], grads, state["opt"], state["step"], opt
        )
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        return new_state, {"loss": loss_sum / n, **stats}

    return update


def make_sp_loss_fn(cfg: ModelConfig, policy=None, *, seq_axis: str = "seq",
                    unroll: bool = False) -> Callable:
    """Per-shard loss for a sequence-parallel split microbatch.

    The batch is this rank's contiguous S shard of ONE packed window
    (tokens/labels/segment_ids sliced, ``positions`` globally computed so
    RoPE does not restart at the shard boundary).  Returns the LOCAL mean
    token loss; with equal shard widths the pool-level mean over the
    ``seq`` axis equals the full window's mean-token loss exactly.
    """
    if cfg.family != "dense":
        raise ValueError(
            f"sequence parallelism supports the dense transformer LM path "
            f"only (got family={cfg.family!r})"
        )
    n_groups = policy.n_dispatch_groups if policy is not None else 1

    def loss_fn(params, batch, rng):
        del rng  # the LM path is deterministic given the batch
        return T.lm_loss(
            params, cfg, batch["tokens"], batch["labels"],
            policy=policy, n_groups=n_groups, unroll=unroll,
            segment_ids=batch.get("segment_ids"),
            positions=batch["positions"], seq_axis=seq_axis,
        )

    return loss_fn


def make_sp_pool_grad_step(cfg: ModelConfig, policy=None, *,
                           seq_axis: str = "seq") -> Callable:
    """The per-device body of a split bucket's gradient step.

    Call from inside ``shard_map`` over mesh axis ``seq_axis``; every rank
    of the group returns the SAME (loss, grads) — the full window's mean
    token loss and its exact parameter gradient (per-shard grads meet in
    one psum; cross-shard attention terms travel through the ring's
    ``ppermute`` transposes).  RNG derivation matches
    :func:`make_pool_grad_step` so a split entry folds into the pool
    enumeration exactly like an unsplit one.
    """
    loss_fn = make_sp_loss_fn(cfg, policy, seq_axis=seq_axis)

    def grad_step(params, batch, step_key, pool_index):
        rng = jax.random.fold_in(step_key, pool_index)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
        k = jax.lax.psum(1, seq_axis)
        loss = jax.lax.psum(loss, seq_axis) / k
        grads = jax.tree.map(
            lambda g: jax.lax.psum(g, seq_axis) / k, grads
        )
        return loss, grads

    return grad_step


def make_train_step(cfg: ModelConfig, opt: OptimizerConfig, policy=None,
                    unroll: bool = False) -> Callable:
    loss_fn = make_loss_fn(cfg, policy, unroll)

    def train_step(state, batch, rng):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch, rng)
        new_params, new_opt, stats = adamw_update(
            state["params"], grads, state["opt"], state["step"], opt
        )
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        return new_state, {"loss": loss, **stats}

    return train_step


# -- serve -----------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig, cache_cap: int, policy=None,
                      unroll: bool = False) -> Callable:
    n_groups = policy.n_dispatch_groups if policy is not None else 1

    def prefill_step(params, tokens, memory=None):
        return T.prefill(
            params, cfg, tokens, cache_cap,
            memory=memory, policy=policy, n_groups=n_groups, unroll=unroll,
        )

    return prefill_step


def make_decode_step(cfg: ModelConfig, policy=None, unroll: bool = False) -> Callable:
    n_groups = policy.n_dispatch_groups if policy is not None else 1

    def decode_step(params, caches, token, pos):
        return T.decode_step(
            params, cfg, caches, token, pos, policy=policy, n_groups=n_groups,
            unroll=unroll,
        )

    return decode_step


def make_paged_prefill_step(cfg: ModelConfig, policy=None,
                            unroll: bool = False) -> Callable:
    """Prefill into paged KV pools (continuous-batching serving): run the
    padded prompts, scatter their caches into pool pages, and return the
    logits at each request's true last token."""
    n_groups = policy.n_dispatch_groups if policy is not None else 1

    def paged_prefill_step(params, tokens, true_len, page_table, pools):
        return T.paged_prefill(
            params, cfg, tokens, true_len, page_table, pools,
            policy=policy, n_groups=n_groups, unroll=unroll,
        )

    return paged_prefill_step


def make_paged_decode_step(cfg: ModelConfig, policy=None,
                           unroll: bool = False) -> Callable:
    """One decode wave over paged pools: every slot carries its own
    position (``kv_lens``), so one compiled step serves requests at
    arbitrary mixed depths — the iteration unit of continuous batching."""
    n_groups = policy.n_dispatch_groups if policy is not None else 1

    def paged_decode_step(params, pools, page_table, kv_lens, token):
        return T.paged_decode_step(
            params, cfg, pools, page_table, kv_lens, token,
            policy=policy, n_groups=n_groups, unroll=unroll,
        )

    return paged_decode_step


def make_denoise_step(cfg: ModelConfig, policy=None) -> Callable:
    """MMDiT serving: one velocity evaluation (the unit of diffusion
    sampling; a sampler chains these).  The optional segment ids scope
    attention per clip so the continuous-batching engine can pad mixed
    clip lengths into one wave (-1 = padding)."""

    def denoise_step(params, latents, text, t, segment_ids=None,
                     text_segment_ids=None):
        return M.forward(
            params, cfg, latents, text, t, policy=policy, remat=False,
            segment_ids=segment_ids, text_segment_ids=text_segment_ids,
        )

    return denoise_step
