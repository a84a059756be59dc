"""A whole run on the CPU at small size, past the look for a chip: sound,
``correct`` comes out true; with the timed path broken underneath, false.

The training cells can have two of the faults: a step that returns its
state unchanged, and half of each microbatch left out with the mean taken
over the rest.  (They exchange nothing between chips and emit no tokens.)
"""

from __future__ import annotations

import importlib.util
import json
import types

import jax
import pytest

from chipbench import catalog
from chipbench.tiny import tiny_cell

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _run_module():
    spec = importlib.util.spec_from_file_location("chipbench_run", catalog.BENCH_DIR / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _unchanged_state(opt):
    def update(state, grad_sum, loss_sum, n):
        return state, {"loss": loss_sum / n}

    return update


def _half_batch(cfg, policy=None):
    from repro.train.steps import make_pool_grad_step

    step = make_pool_grad_step(cfg, policy)

    def grad_step(params, batch, step_key, pool_index):
        s = batch["latents"].shape[1]
        return step(params, {**batch, "latents": batch["latents"][:, : s // 2]},
                    step_key, pool_index)

    return grad_step


FAULTS = {
    "sound": None,
    "state_unchanged": ("make_pool_update", _unchanged_state),
    "half_batch": ("make_pool_grad_step", _half_batch),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_run_is_correct_only_when_sound(fault, monkeypatch, capsys):
    import repro.train.engine as engine

    if FAULTS[fault] is not None:
        name, broken = FAULTS[fault]
        monkeypatch.setattr(engine, name, broken)
    run = _run_module()
    args = types.SimpleNamespace(seed=2**31 + 9, seconds=0.2, trace=0)
    run.run_cell(args, tiny_cell(), jax.devices(), PEAKS)
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is (fault == "sound"), result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"tokens_per_s", "mfu", "setup_s"}
    assert result["device"]["count"] == len(jax.devices())
    lines = out.err.strip().splitlines()[-3:]
    assert [ln.split()[1] for ln in lines] == ["loss_gap", "grad_gap", "change_gap"]
    assert all(" limit " in ln for ln in lines)
