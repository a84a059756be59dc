"""Training loop: bucketed steps + closed-loop scheduling + fault tolerance.

The loop is backend-agnostic: ``Trainer.run`` drives ONE
:class:`~repro.train.engine.ExecutionEngine` and never branches on
executor internals.  Two engines ship:

* :class:`~repro.train.engine.EmulatedEngine` (default) — this host plays
  every DP rank serially with oracle gradient semantics (pool-mean
  gradient, one update per step); telemetry is recorded **per worker and
  per microbatch**, so the cost-model refit sees honest ``(B, S, t)``
  pairs and ``straggler_workers()`` sees every rank.
* :class:`~repro.train.engine.MeshEngine` (``mesh=``) — real SPMD via
  ``distributed.plan_exec.PlanExecutor``: rank ``r``'s microbatches run on
  mesh device ``r``, grads meet in one ``psum``, one update per step.
  With a scheduler attached the engine measures in **async** mode:
  per-rank device-completion timing instead of host-blocking per
  microbatch, so telemetry no longer serializes the ranks it measures.

The driver overlaps the data path with compute when the engine dispatches
asynchronously: while step ``i`` runs on the devices, step ``i+1`` is
pulled from the loader and its batches staged H2D
(``engine.prepare``) — the double-buffer that keeps devices from waiting
on the host.

Each step runs under ``jax.profiler.StepTraceAnnotation("train.step")``;
its host sync on the new state and loss is a ``train.sync`` span and every
pull from the loader a ``train.fetch`` span, and the engines and loaders
add ``engine.*`` and ``loader.*`` spans of their own.  Inside a
``jax.profiler`` session these land in the trace on the device ops' clock;
outside one they record nothing.

The loop consumes either a single-rank stream (``BucketedLoader``: each
item is one ``list[(bucket, batch)]``) or a planner-driven multi-rank
stream (``ShardedBucketedLoader``: each item is per-worker lists from one
global dispatch decision).  Jit compiles are shape-cached inside the
engines; a first-compile step is recorded as a ``compile@i`` event and
excluded from ``TrainHistory.throughput`` (mirroring the telemetry
exclusion), so a shape mix costs one compile per bucket and never skews
reported throughput.

**Fault tolerance & resume.**  With ``ft=`` attached the driver runs the
full closed loop behind the engine interface: every step it (1) heartbeats
the engine's completed ranks into the monitor, (2) offers the cadence a
checkpoint — the save carries a *run-state* blob (trainer RNG key + next
step, plus whatever ``run_state_of`` contributes: loader snapshot,
scheduler state) in the manifest so weights and plan-stream state commit
atomically, and (3) on dead ranks performs emergency-save ->
``recovery_plan`` -> ``on_resize`` (elastic loader/scheduler shrink) and
keeps training on the surviving mesh.  ``Trainer.run(start_step=,
rng=)`` resumes the step numbering and RNG stream exactly, so a
killed-and-resumed run replays byte-identical plan digests and matching
parameters versus the uninterrupted run (``tests/test_resume.py`` pins
this for both engines).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import group_worker_steps
from repro.core.scheduler import AdaptiveLoadScheduler
from repro.data.pipeline import SnapshotUnavailable
from repro.distributed.chaos import ChaosContext, ChaosSchedule
from repro.distributed.fault_tolerance import FaultTolerantRunner
from repro.models.config import ModelConfig
from repro.optim.adamw import OptimizerConfig
from repro.train.engine import EmulatedEngine, ExecutionEngine, MeshEngine

RUN_STATE_VERSION = 1


def serialize_rng_key(key) -> list[int]:
    """A jax PRNG key as JSON-serializable uint32 words (typed keys are
    stored as their key data; the default raw uint32 keys round-trip
    bit-exactly, which is what resume parity needs)."""
    if hasattr(key, "dtype") and jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return np.asarray(jax.device_get(key), dtype=np.uint32).tolist()


def deserialize_rng_key(words) -> jax.Array:
    return jnp.asarray(np.asarray(words, dtype=np.uint32))


@dataclasses.dataclass
class TrainHistory:
    losses: list[float] = dataclasses.field(default_factory=list)
    step_times: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] = dataclasses.field(default_factory=list)
    events: list[str] = dataclasses.field(default_factory=list)
    # steps that paid a jit compile: kept in step_times (the wall-clock
    # record stays complete) but excluded from throughput — a handful of
    # compile-polluted samples would understate steady-state tok/s exactly
    # the way they used to poison the telemetry refit
    compile_steps: list[int] = dataclasses.field(default_factory=list)
    #: True iff the run ended early on a graceful-preemption drain (the
    #: handoff checkpoint is already on disk; relaunch with resume)
    preempted: bool = False

    @property
    def throughput(self) -> float:
        skip = set(self.compile_steps)
        if len(skip) >= len(self.step_times):  # nothing but compile steps
            skip = set()
        t = sum(dt for i, dt in enumerate(self.step_times) if i not in skip)
        tok = sum(tk for i, tk in enumerate(self.tokens) if i not in skip)
        return tok / t if t > 0 else 0.0


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        opt: OptimizerConfig,
        *,
        policy=None,
        scheduler: AdaptiveLoadScheduler | None = None,
        ft: FaultTolerantRunner | None = None,
        donate: bool = True,
        worker_time_scale: Mapping[int, float] | None = None,
        mesh=None,
        measure_ranks: bool | str | None = None,
        check_agreement: bool = False,
        engine: ExecutionEngine | None = None,
        run_state_of: Callable[[int], dict] | None = None,
        chaos: ChaosSchedule | None = None,
    ):
        self.cfg = cfg
        self.opt = opt
        self.policy = policy
        self.scheduler = scheduler
        self.ft = ft
        # deterministic chaos injection: events fire at the plan boundary
        # after each completed step, through the same monitor/runner/engine
        # hooks a real cluster manager would drive
        self.chaos = chaos
        # elastic "remap" mode (set_physical_ranks): the logical fan-out
        # width stays fixed — churn only regroups logical shares onto the
        # current physical fleet, keeping the plan stream digest-stable
        self._n_physical: int | None = None
        self._physical_caps: list[float] | None = None
        # run_state_of(held) -> dict merged into every checkpoint's
        # run-state blob.  ``held`` is how many data items the driver has
        # popped but not yet executed (the prefetch double-buffer) — a
        # loader snapshot must rewind by that many plans so the resumed
        # run regenerates them.
        self.run_state_of = run_state_of
        #: run-state blob as of the END of the last completed ``run`` —
        #: what a launcher persists with its final checkpoint
        self.last_run_state: dict | None = None
        if engine is not None:
            if mesh is not None:
                raise ValueError("pass engine= or mesh=, not both")
            self.engine = engine
        elif mesh is not None:
            # measure_ranks: False | "serial" | "async" (True = "async");
            # default: measure only when a scheduler consumes the records
            measure = (
                measure_ranks
                if measure_ranks is not None
                else (scheduler is not None)
            )
            self.engine = MeshEngine(
                mesh, cfg, opt, policy=policy, donate=donate,
                measure=measure, check_agreement=check_agreement,
                worker_time_scale=worker_time_scale,
            )
        else:
            self.engine = EmulatedEngine(
                cfg, opt, policy=policy, donate=donate,
                worker_time_scale=worker_time_scale,
            )

    def set_physical_ranks(
        self, n: int, capacities: Mapping[int, float] | list | None = None
    ) -> None:
        """Elastic *remap*: run the fixed-width logical plan stream on
        ``n`` physical ranks.

        The loader/planner keep drawing at their original logical width —
        the churn-stable choice: pool sizes, plan digests, and (because
        logical shares are merged contiguously, preserving rank-major pool
        enumeration) every microbatch's gradient RNG stay byte-identical
        to an uninterrupted run.  This is the ``on_resize`` target for
        kill-then-rejoin churn; permanent capacity changes that should
        change the plan stream itself use ``loader.resize`` instead.

        ``n`` larger than a fan-out's logical width is clamped to it (a
        physical rank can hold at minimum one logical share).
        ``capacities`` optionally weights the physical ranks."""
        if n < 1:
            raise ValueError("need at least one physical rank")
        self._n_physical = int(n)
        if capacities is None:
            self._physical_caps = None
        elif isinstance(capacities, Mapping):
            self._physical_caps = [
                float(capacities.get(r, 1.0)) for r in range(n)
            ]
        else:
            caps = [float(c) for c in capacities]
            if len(caps) != n:
                raise ValueError(f"{len(caps)} capacities for {n} ranks")
            self._physical_caps = caps

    def _to_physical(self, worker_steps):
        """Apply the remap (identity when inactive or already narrower)."""
        n = self._n_physical
        if n is None or n >= len(worker_steps):
            return worker_steps
        return group_worker_steps(worker_steps, n, self._physical_caps)

    @staticmethod
    def _as_worker_steps(step) -> list[list[tuple[Any, Any]]]:
        """Normalize a data item to per-worker microbatch lists.

        ``BucketedLoader`` yields ``[(bucket, batch), ...]`` (one rank);
        ``ShardedBucketedLoader`` yields ``[[(bucket, batch), ...], ...]``
        (one list per rank)."""
        if step and isinstance(step[0], list):
            return step
        return [step]

    @staticmethod
    def _fetch(data_iter):
        with jax.profiler.TraceAnnotation("train.fetch"):
            return next(data_iter)

    def _run_state(self, next_step: int, rng, held: int) -> dict:
        """The resumable run-state blob for a checkpoint taken between
        step ``next_step - 1`` and ``next_step``."""
        rs = {
            "version": RUN_STATE_VERSION,
            "step": int(next_step),
            "trainer": {"rng": serialize_rng_key(rng)},
        }
        if self.run_state_of is not None:
            rs.update(self.run_state_of(held) or {})
        return rs

    def _failure_run_state(self, next_step: int, rng, held: int) -> dict:
        """Run state for an EMERGENCY save: if the loader cannot snapshot
        right now (resize in flight), degrade to weights + trainer RNG
        rather than losing the save — an imminent crash makes a partial
        run state strictly better than none."""
        try:
            return self._run_state(next_step, rng, held)
        except SnapshotUnavailable:
            return {
                "version": RUN_STATE_VERSION,
                "step": int(next_step),
                "trainer": {"rng": serialize_rng_key(rng)},
            }

    def run(
        self,
        state,
        data_iter,
        n_steps: int,
        *,
        rng=None,
        start_step: int = 0,
        log_every: int = 50,
        on_metrics: Callable[[int, dict], None] | None = None,
    ):
        """Drive ``n_steps`` optimizer steps ``start_step..start_step +
        n_steps - 1``.  A resumed run passes the checkpoint's ``step`` as
        ``start_step`` and its restored trainer RNG as ``rng`` — the step
        numbering, RNG stream, and (via the loader's restored plan stream)
        the dispatched plans continue exactly where the save left off."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        hist = TrainHistory()
        engine = self.engine
        if self.ft is not None and start_step > 0:
            # the restored checkpoint IS start_step's save: count the
            # cadence from there instead of re-saving on the first step
            self.ft.note_restored(start_step)
        state = engine.place_state(state)
        item = self._fetch(data_iter) if n_steps > 0 else None
        held = 0
        for i in range(n_steps):
            step_no = start_step + i
            with jax.profiler.StepTraceAnnotation("train.step", step_num=step_no):
                worker_steps = self._as_worker_steps(item)
                t0 = time.perf_counter()
                tok = sum(
                    bucket.tokens for ws in worker_steps for bucket, _ in ws
                )
                n_micro = sum(len(ws) for ws in worker_steps)
                rng, sub = jax.random.split(rng)
                state, out = engine.execute_step(
                    state, self._to_physical(worker_steps),
                    step_key=sub, step=step_no,
                )
                held = 0
                if engine.async_dispatch and i + 1 < n_steps:
                    # devices are still computing step i: fetch step i+1 and
                    # stage its H2D transfers behind that compute
                    item = self._fetch(data_iter)
                    engine.prepare(self._to_physical(self._as_worker_steps(item)))
                    held = 1
                recs = engine.timing_records()
                with jax.profiler.TraceAnnotation("train.sync"):
                    jax.block_until_ready(state["step"])
                    dt = time.perf_counter() - t0
                    loss = float(out.loss)

                hist.losses.append(loss)
                hist.step_times.append(dt)
                hist.tokens.append(tok)
                if out.compiled:
                    hist.compile_steps.append(i)
                    hist.events.append(f"compile@{step_no}")

                if self.scheduler is not None:
                    self.scheduler.observe(recs)

                if self.chaos is not None:
                    ctx = ChaosContext(
                        monitor=self.ft.monitor if self.ft else None,
                        runner=self.ft,
                        engine=engine,
                        preemption=self.ft.preemption if self.ft else None,
                    )
                    for msg in self.chaos.fire(step_no, ctx):
                        hist.events.append(f"{msg}@{step_no}")

                if self.ft is not None:
                    # heartbeat BEFORE failure checks: a rank that completed
                    # this step is alive, whatever the wall clock says
                    for w in engine.heartbeat_ranks():
                        self.ft.monitor.heartbeat(w)
                    # run_state is a thunk: the snapshot work (loader rewind,
                    # RNG serialization) only happens on steps that save.
                    # ``step_no + 1`` = steps completed = the step a resume
                    # starts from; ``held`` rewinds the loader snapshot past
                    # the item the double-buffer already popped.
                    run_state = lambda: self._run_state(step_no + 1, rng, held)  # noqa: B023,E731
                    try:
                        if self.ft.maybe_checkpoint(
                            state, step_no + 1, dt, run_state=run_state
                        ):
                            hist.events.append(f"ckpt@{step_no}")
                    except SnapshotUnavailable:
                        # a resize re-emitted the boundary plan: no replayable
                        # snapshot THIS step.  Transient — the cadence check
                        # re-fires next step, where a fresh draw is snapshotted
                        hist.events.append(f"ckpt-deferred@{step_no}")
                    failure = self.ft.handle_failures(
                        state, step_no + 1,
                        run_state=lambda: self._failure_run_state(  # noqa: B023
                            step_no + 1, rng, held
                        ),
                    )
                    if failure is not None:
                        hist.events.append(f"failure@{step_no}:{failure['plan']}")
                    try:
                        join = self.ft.handle_joins(
                            state, step_no + 1, run_state=run_state
                        )
                        if join is not None:
                            hist.events.append(
                                f"join@{step_no}:{join['joined']}"
                                f"->{join['plan'].get('data_parallel')}"
                            )
                    except SnapshotUnavailable:
                        # mid-drain (a resize just re-emitted the boundary
                        # plan): the join stays queued and is admitted at the
                        # next snapshotable boundary
                        hist.events.append(f"join-deferred@{step_no}")
                    preempt = self.ft.handle_preemption(
                        state, step_no + 1,
                        run_state=lambda: self._failure_run_state(  # noqa: B023
                            step_no + 1, rng, held
                        ),
                    )
                    for ev in self.ft.drain_events():
                        hist.events.append(f"{ev}@{step_no}")
                    if preempt is not None:
                        # grace drain complete: in-flight microbatches done,
                        # full run state on disk — hand off cleanly
                        hist.events.append(f"preempt@{step_no}")
                        hist.preempted = True
                        break

                if not engine.async_dispatch and i + 1 < n_steps:
                    # sync engines fetch AFTER the fault-tolerance block: the
                    # checkpoint then sits exactly on a plan boundary (nothing
                    # popped-but-unexecuted to rewind)
                    item = self._fetch(data_iter)

                if on_metrics is not None:
                    on_metrics(step_no, {"loss": loss, "time": dt, "tokens": tok})
                if log_every and i % log_every == 0:
                    print(
                        f"step {step_no:5d}  loss {loss:.4f}  "
                        f"{tok/dt:,.0f} tok/s  ({n_micro} microbatches, "
                        f"{len(worker_steps)} ranks)"
                    )
        # degraded variant: an end-of-run loader that cannot snapshot
        # (e.g. a resize still draining) must not crash a finished run —
        # the launcher then persists weights + trainer RNG.  A preempted
        # run counts only its completed steps, and ``held`` rewinds the
        # item an async double-buffer already popped for the step that
        # never ran.
        self.last_run_state = self._failure_run_state(
            start_step + len(hist.losses), rng, held if hist.preempted else 0
        )
        return state, hist
