"""Keys made from a run's ``--seed``, shared by the harness and the
reference so that both see the same weights, data and noise.

A seed may exceed 32 bits, so it enters JAX as two 32-bit words.
"""

from __future__ import annotations

import jax

_INIT, _TRAINER, _DATA, _FIRST = range(4)


def _root(seed: int):
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init_key(seed: int):
    """Key of the initial parameters."""
    return jax.random.fold_in(_root(seed), _INIT)


def trainer_key(seed: int):
    """Key handed to ``Trainer.run`` for the first step; each step splits
    it into ``(next, step_key)``."""
    return jax.random.fold_in(_root(seed), _TRAINER)


def batch_key(seed: int, draw: int, first: bool = False):
    """Key of one microbatch's latents and text.  ``draw`` is the number
    the loader drew for it; the first step's batches, one of every bucket
    shape, use a stream of their own, numbered by bucket."""
    return jax.random.fold_in(
        jax.random.fold_in(_root(seed), _FIRST if first else _DATA), draw
    )


def step_keys(seed: int, n: int) -> list:
    """The step keys of a run's first ``n`` steps, as ``Trainer.run``
    derives them from :func:`trainer_key`."""
    rng = trainer_key(seed)
    out = []
    for _ in range(n):
        rng, sub = jax.random.split(rng)
        out.append(sub)
    return out
