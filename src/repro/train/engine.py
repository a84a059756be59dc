"""Execution engines: one Trainer backend contract, two implementations.

Before this layer existed, ``Trainer.run`` was two hardcoded, divergent
code paths (``_emulated_step`` / ``_mesh_step``) with different gradient
semantics, different telemetry, and executor internals wired through
``Trainer.__init__`` flags.  Now every backend implements one interface:

* :meth:`ExecutionEngine.place_state` — put a train state wherever the
  backend computes (replicated across the mesh, or a donation-shielding
  copy on the default device).  Idempotent.
* :meth:`ExecutionEngine.execute_step` — run ONE optimizer step for a
  planned per-rank fan-out and return ``(new_state, StepOutcome)``.
* :meth:`ExecutionEngine.timing_records` — the step's per-microbatch
  ``WorkerStepRecord`` telemetry.  Deliberately a separate call: an async
  backend dispatches everything without host blocking, the trainer stages
  the NEXT step's data in the gap, and only then joins the timing
  observers — so telemetry stops living on the critical path.
* :meth:`ExecutionEngine.prepare` — optional H2D double-buffer hook: stage
  step ``i+1``'s batches while step ``i`` computes.

Both engines mark ``execute_step`` as an ``engine.step`` profiler span and
every host block on the devices (a microbatch's loss, the timer join) as
an ``engine.sync`` span, beside the trainer's ``train.*`` spans.

Both engines implement the SAME gradient semantics as
:func:`repro.distributed.plan_exec.oracle_step`: every microbatch in the
step's global pool contributes the gradient of its own mean-token loss
(RNG = ``fold_in(step_key, pool_index)``, pool enumerated rank-major), and
ONE optimizer update consumes the mean over the pool.  That is what makes
the engines interchangeable — the emulated backend is now a true
data-parallel emulation rather than a sequential-SGD approximation, and
one parity suite gates both against the same oracle.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import SplitShard, merge_split_worker_steps
from repro.core.telemetry import WorkerStepRecord
from repro.distributed.plan_exec import PlanExecutor, worker_steps_digest
from repro.models.config import ModelConfig
from repro.optim.adamw import OptimizerConfig
from repro.train.steps import make_pool_grad_step, make_pool_update

WorkerSteps = Sequence[Sequence[tuple[Any, dict]]]  # [rank][(bucket, batch)]


@dataclasses.dataclass
class StepOutcome:
    """What one executed step reports back to the driver.

    ``loss`` may still be a device scalar (async backends); the trainer
    converts with ``float()`` after the step's sentinel is blocked on.
    ``compiled`` is True iff any microbatch paid a fresh jit compile — the
    driver records such steps as events and excludes them from throughput.
    """

    loss: Any
    compiled: bool = False


class ExecutionEngine:
    """Backend contract for ``Trainer.run`` (see module docstring)."""

    #: True if ``execute_step`` returns before device work completes, so the
    #: driver can overlap next-step data fetch + H2D behind compute.
    async_dispatch: bool = False

    def place_state(self, state):
        """Prepare a train state for this backend (idempotent)."""
        return state

    def prepare(self, worker_steps: WorkerSteps) -> None:
        """Stage a FUTURE step's batches (H2D double-buffer). Optional."""

    def execute_step(self, state, worker_steps: WorkerSteps, *, step_key,
                     step: int) -> tuple[Any, StepOutcome]:
        raise NotImplementedError

    def timing_records(self) -> list[WorkerStepRecord]:
        """Per-microbatch telemetry for the last executed step (may block
        on the backend's timing observers)."""
        return []

    def heartbeat_ranks(self) -> list[int]:
        """Ranks that demonstrably completed work in the last executed
        step — what the trainer feeds the fault-tolerance heartbeat
        monitor each step.  Default: every rank of the last fan-out (an
        engine whose collective completed heard from all of them)."""
        return list(getattr(self, "_last_ranks", []))

    def set_time_scale(self, worker: int, scale: float) -> None:
        """Scale rank ``worker``'s *recorded* compute times from now on —
        the chaos harness's slowdown injection point: a degraded device
        shows up in telemetry (and trips the scheduler's straggler /
        capacity paths) without needing degradable hardware.  Engines
        without per-rank telemetry ignore it."""


class EmulatedEngine(ExecutionEngine):
    """Single-host emulation: every DP rank's microbatches run serially on
    the default device with oracle gradient semantics (grad accumulation
    over the whole pool, one update per step).

    Telemetry is recorded per worker and per microbatch — each microbatch
    blocks on its own loss, so the cost-model refit sees honest ``(B, S,
    t)`` pairs and straggler detection sees every rank.  ``worker_time_scale``
    scales rank ``w``'s *recorded* times to model degraded hardware
    (exercises the scheduler's straggler path end to end in tests).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        opt: OptimizerConfig,
        *,
        policy=None,
        donate: bool = True,
        worker_time_scale: Mapping[int, float] | None = None,
    ):
        self._donate = donate
        self._worker_time_scale = dict(worker_time_scale or {})
        # one jitted callable (the shared pool grad step — same
        # rng/enumeration semantics as PlanExecutor and oracle_step); jax
        # retraces per batch-shape signature, so each shape compiles
        # exactly once (freshness is tracked so compile executions never
        # enter telemetry)
        self._grad_step = jax.jit(make_pool_grad_step(cfg, policy))
        self._acc_add = jax.jit(
            lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,)
        )

        self._update = jax.jit(
            make_pool_update(opt), donate_argnums=(0,) if donate else ()
        )
        self._seen_signatures: set = set()
        self._records: list[WorkerStepRecord] = []

    def set_time_scale(self, worker: int, scale: float) -> None:
        if scale <= 0:
            raise ValueError("time scale must be positive")
        self._worker_time_scale[int(worker)] = float(scale)

    def place_state(self, state):
        if not self._donate:
            return state
        # the update donates its state input; copy so stepping never
        # silently deletes the caller's original arrays
        return jax.tree.map(lambda x: jnp.array(x, copy=True), state)

    @staticmethod
    def _signature(batch) -> tuple:
        return tuple(
            sorted((k, tuple(v.shape), str(v.dtype)) for k, v in batch.items())
        )

    def execute_step(self, state, worker_steps, *, step_key, step):
        with jax.profiler.TraceAnnotation("engine.step"):
            return self._execute_step(state, worker_steps, step_key, step)

    def _execute_step(self, state, worker_steps, step_key, step):
        self._records = []
        self._last_ranks = list(range(len(worker_steps)))
        # sequence-parallel split fan-outs collapse back to their logical
        # whole-window form (this backend has no ring to shard over); the
        # merged entry sits at shard 0's pool position so RNG/enumeration
        # match the mesh path exactly
        had_splits = any(
            isinstance(b, SplitShard)
            for share in worker_steps
            for b, _batch in share
        )
        if had_splits:
            worker_steps = merge_split_worker_steps(worker_steps)
        compiled = False
        acc = None
        loss_sum = None
        pool_index = 0
        for w, share in enumerate(worker_steps):
            if not share:
                if had_splits:
                    # this rank's whole share was sibling shards of split
                    # groups owned by lower ranks — nothing left to run
                    continue
                # same contract as PlanExecutor: an engine must never
                # silently swallow an input its sibling backend rejects
                raise ValueError(
                    f"rank {w} received an empty microbatch list"
                )
            scale = self._worker_time_scale.get(w, 1.0)
            for bucket, batch in share:
                sig = self._signature(batch)
                fresh = sig not in self._seen_signatures
                self._seen_signatures.add(sig)
                compiled = compiled or fresh
                t0 = time.perf_counter()
                loss, grads = self._grad_step(
                    state["params"], batch, step_key, np.int32(pool_index)
                )
                with jax.profiler.TraceAnnotation("engine.sync"):
                    loss.block_until_ready()
                dt = time.perf_counter() - t0
                if not fresh:  # compile executions poison telemetry
                    self._records.append(
                        WorkerStepRecord(
                            step=step, worker=w,
                            batch_size=bucket.batch_size,
                            seq_len=bucket.seq_len,
                            compute_time=dt * scale,
                            ring_ranks=getattr(bucket, "n_ranks", 1),
                        )
                    )
                acc = grads if acc is None else self._acc_add(acc, grads)
                loss_sum = loss if loss_sum is None else loss_sum + loss
                pool_index += 1
        if acc is None:
            raise ValueError("execute_step received an empty fan-out")
        new_state, metrics = self._update(
            state, acc, loss_sum.astype(jnp.float32), np.float32(pool_index)
        )
        return new_state, StepOutcome(loss=metrics["loss"], compiled=compiled)

    def timing_records(self) -> list[WorkerStepRecord]:
        return self._records


class MeshEngine(ExecutionEngine):
    """SPMD execution: rank ``r``'s microbatches run on mesh device ``r``
    via :class:`~repro.distributed.plan_exec.PlanExecutor` — grads meet in
    one psum, one update per step.

    ``measure``:

    * ``False`` — no telemetry (fastest; nothing blocks per rank).
    * ``"async"`` (alias ``True``) — per-rank device-completion timing:
      ranks dispatch without host blocking and :meth:`timing_records`
      joins the tail-sentinel observers, so honest ``WorkerStepRecord``
      telemetry coexists with async dispatch.
    * ``"serial"`` — legacy host-clock mode that blocks per microbatch
      (kept as the benchmark baseline; it serializes ranks).
    """

    def __init__(
        self,
        mesh,
        cfg: ModelConfig,
        opt: OptimizerConfig,
        *,
        policy=None,
        donate: bool = True,
        measure: bool | str = False,
        check_agreement: bool = False,
        worker_time_scale: Mapping[int, float] | None = None,
    ):
        if measure is True:
            measure = "async"
        if measure not in (False, "serial", "async"):
            raise ValueError(
                f"measure must be False, 'serial', or 'async'; got {measure!r}"
            )
        self.executor = PlanExecutor(
            mesh, cfg, opt, policy=policy, donate=donate
        )
        # serial measuring blocks per microbatch inside execute_step, so
        # there is no in-flight compute left for the driver to hide the
        # next step's fetch/H2D behind — advertise async dispatch only
        # when execute_step actually returns before device work completes
        self.async_dispatch = measure != "serial"
        self._measure = measure
        self._check_agreement = check_agreement
        self._scale = dict(worker_time_scale or {})
        self._time_scale: Callable[[int], float] = (
            lambda w: self._scale.get(w, 1.0)
        )
        self._records: list[WorkerStepRecord] = []
        self._timers = None

    def set_time_scale(self, worker: int, scale: float) -> None:
        if scale <= 0:
            raise ValueError("time scale must be positive")
        self._scale[int(worker)] = float(scale)

    def place_state(self, state):
        if self.executor.is_placed(state):
            return state
        return self.executor.place_state(state)

    def prepare(self, worker_steps) -> None:
        self.executor.stage(worker_steps)

    def execute_step(self, state, worker_steps, *, step_key, step):
        with jax.profiler.TraceAnnotation("engine.step"):
            self._last_ranks = list(range(len(worker_steps)))
            digests = None
            if self._check_agreement:
                # single-process: every rank's digest derives from the same
                # local fan-out (multi-host deployments pass their own)
                digest = worker_steps_digest(worker_steps)
                digests = [digest] * self.executor.n_ranks
            state, out = self.executor.execute(
                state,
                worker_steps,
                step_key=step_key,
                step=step,
                digests=digests,
                measure=self._measure,
                time_scale=self._time_scale,
            )
            self._records = out.get("records", [])
            self._timers = out.get("timers")
        return state, StepOutcome(loss=out["loss"], compiled=out["compiled"])

    def timing_records(self) -> list[WorkerStepRecord]:
        if self._timers is not None:
            with jax.profiler.TraceAnnotation("engine.sync"):
                self._records, _ = self._timers.join()
            self._timers = None
        return self._records


__all__ = [
    "EmulatedEngine",
    "ExecutionEngine",
    "MeshEngine",
    "StepOutcome",
    "WorkerSteps",
]
