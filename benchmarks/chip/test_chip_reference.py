"""The plain reference (the Wan family's model) makes the program's
weights, data and noise from the seed and agrees with the program's loss
at small size on the CPU; its float8 control and the half-batch fault fail
the 1.3B cell's limits."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference, seeds, verdict
from chipbench.tiny import tiny_cell

CELL = tiny_cell()
WAN = CELL.family
DIMS = CELL.dims
OPT = harness.optimizer_dict(CELL.config)


def _program_cfg(dtype):
    from repro.configs.wan2_1_mmdit import smoke_config

    return dataclasses.replace(smoke_config(), n_layers=DIMS.layers, dtype=dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_weights_and_data_are_the_programs(dtype):
    from repro.data.synthetic import make_diffusion_batch
    from repro.models.mmdit import init_params

    cfg = _program_cfg(dtype)
    key = seeds.init_key(2**33 + 1)
    ours = WAN.init_params(key, DIMS, jnp.dtype(dtype))
    theirs = init_params(key, cfg)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    bkey = seeds.batch_key(5, 123)
    ours = WAN.make_batch(bkey, 2, 64, DIMS, jnp.dtype(dtype))
    theirs = make_diffusion_batch(bkey, 2, 64, cfg)
    for k in ("latents", "text"):
        np.testing.assert_array_equal(np.asarray(ours[k], np.float32),
                                      np.asarray(theirs[k], np.float32))


def test_loss_and_gradient_match_the_program_in_fp32():
    from repro import kernels as K
    from repro.models.mmdit import init_params, rectified_flow_loss

    prev = K.get_backend()
    K.set_backend("ref")
    try:
        _compare_with_program(init_params, rectified_flow_loss)
    finally:
        K.set_backend(prev)


def _compare_with_program(init_params, rectified_flow_loss):
    cfg = _program_cfg("float32")
    params = init_params(seeds.init_key(3), cfg)
    batch = WAN.make_batch(seeds.batch_key(3, 1), 2, 64, DIMS, jnp.float32)
    rng = jax.random.PRNGKey(9)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: rectified_flow_loss(p, cfg, batch["latents"], batch["text"], rng)
        )(params)
        lr, gr = jax.value_and_grad(lambda p: WAN.loss(p, DIMS, batch, rng))(params)
    assert float(lr) == pytest.approx(float(lp), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-6)


@pytest.mark.parametrize("fault", [{"matmul": "fp8"}, {"half_rows": True}],
                         ids=["fp8_control", "half_batch"])
def test_control_and_fault_fail_the_limits(fault):
    seed = 2**32 + 77
    steps = harness.check_steps(CELL, seed)
    ref = reference.Reference(WAN, DIMS, OPT, dtype=jnp.float32).run(seeds.init_key(seed), steps)
    other = reference.Reference(WAN, DIMS, OPT, dtype=jnp.float32, **fault).run(
        seeds.init_key(seed), steps
    )
    nums = verdict.numbers(other, ref)
    correct, _ = verdict.judge(nums, CELL.limits)
    assert not correct, nums
    same, _ = verdict.judge(verdict.numbers(ref, ref), CELL.limits)
    assert same


@pytest.mark.parametrize("name", ["tiny", "wan13b.mix", "wan14b.mix", "wan13b.image"])
def test_the_first_compared_step_holds_every_bucket_shape(name):
    from chipbench import catalog

    cell = CELL if name == "tiny" else catalog.find_cell(name)
    steps = harness.check_steps(cell, 2**31 + 5)
    shapes = [(b.batch_size, b.seq_len) for b in harness.TrainCell(cell, 0).buckets]
    assert len(steps) == harness.CHECK_STEPS
    assert [(b, s) for _, b, s in steps[0][1]] == shapes
    assert len(shapes) > 1
    keys = [np.asarray(jax.random.key_data(k)).tobytes() for _, pool in steps for k, _, _ in pool]
    assert len(set(keys)) == len(keys), "every compared microbatch has rows of its own"
