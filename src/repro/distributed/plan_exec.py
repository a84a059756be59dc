"""Mesh execution of StepPlans: the SPMD dispatch layer (ROADMAP item).

PR 1's ``StepPlanner`` decides *who runs what* each optimizer step; until
now one host emulated every DP rank serially, so the plan's 0.37→0.04
compute-CV win existed only in the simulator.  ``PlanExecutor`` lowers a
plan onto a real ``jax`` mesh:

* **per-rank streams** — rank ``r``'s microbatches execute on mesh device
  ``r``.  Each bucket shape gets ONE jitted gradient step (shape-cached, so
  a shape compiles once no matter which rank runs it); ranks accumulate
  grads locally while running *different* shape sequences — the KnapFormer
  production shape of heterogeneous-bucket data parallelism.
* **one collective per step** — per-rank grad sums meet in a single
  ``shard_map`` ``psum`` over the ``data`` axis (sums + microbatch counts,
  so the reduced gradient is the exact mean over the step's global pool),
  followed by one optimizer update on the replicated state.
* **plan agreement** — every host derives its plan independently from the
  shared seed + telemetry snapshot (no central prefetch thread); a
  32-byte plan digest is all-gathered across the mesh and any divergence
  raises :class:`PlanAgreementError` *before* a mismatched collective can
  deadlock or silently skew gradients.
* **async measured mode** — ``measure="async"`` keeps every rank's
  dispatch non-blocking and observes completion through per-rank
  :class:`RankTimers` (device-completion deltas, tail-sentinel join), so
  honest per-microbatch telemetry no longer serializes the ranks it
  measures; ``measure="serial"`` (the old host-clock mode) is kept as the
  benchmark baseline.  :meth:`PlanExecutor.stage` pre-places a future
  step's batches on their rank devices (H2D double-buffering behind the
  current step's compute).

Gradient semantics match the single-device oracle (:func:`oracle_step`):
each microbatch contributes the gradient of its own mean-token loss, and
the update consumes the mean over all microbatches in the step's pool —
regardless of how the plan scattered them across ranks.

CPU note: with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` the
same code runs N virtual devices on one host, which is how the tier-1 mesh
tests and ``bench_dispatch --mesh`` exercise this path.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Sequence

import hashlib
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.dispatch import (
    SplitShard,
    merge_split_worker_steps,
    microbatch_key,
)
from repro.core.telemetry import WorkerStepRecord
from repro.models.config import ModelConfig
from repro.optim.adamw import OptimizerConfig, adamw_update
from repro.train.steps import (
    make_pool_grad_step,
    make_pool_update,
    make_sp_pool_grad_step,
)

WorkerSteps = Sequence[Sequence[tuple[Any, dict]]]  # [rank][(bucket, batch)]


class PlanAgreementError(RuntimeError):
    """Hosts derived different StepPlans for the same optimizer step."""


class RankTimers:
    """Per-rank device-completion observers for async measured execution.

    Serial measured mode blocks the host per microbatch, which serializes
    ranks and makes the telemetry destroy the parallelism it measures.
    Here every rank's microbatches are dispatched without host blocking;
    one daemon thread per rank then walks that rank's losses in order,
    blocking on each as a device-completion sentinel.  Within a rank,
    execution is in-order on one device, so each readiness timestamp is
    that microbatch's completion and consecutive deltas are honest
    per-microbatch compute times — while the *other* ranks keep running
    concurrently.  ``join()`` is the per-rank tail-sentinel block: step
    wall-clock becomes max-over-ranks instead of the serial sum.  Compile
    executions are excluded from telemetry exactly as in serial mode.
    """

    def __init__(
        self,
        step: int,
        rank_jobs: Sequence[tuple[int, float, list[tuple[Any, Any, bool]]]],
        time_scale: Callable[[int], float] | None = None,
    ):
        self._step = step
        self._time_scale = time_scale
        self._records: dict[int, list[WorkerStepRecord]] = {}
        self._rank_times: dict[int, float] = {}
        self._threads: list[threading.Thread] = []
        for rank, t0, jobs in rank_jobs:
            t = threading.Thread(
                target=self._observe, args=(rank, t0, jobs), daemon=True
            )
            self._threads.append(t)
            t.start()

    def _observe(self, rank: int, t0: float, jobs) -> None:
        scale = self._time_scale(rank) if self._time_scale else 1.0
        recs: list[WorkerStepRecord] = []
        prev = t0
        for bucket, loss, fresh in jobs:
            loss.block_until_ready()
            now = time.perf_counter()
            dt = now - prev
            prev = now
            if not fresh:  # compile executions poison telemetry
                recs.append(
                    WorkerStepRecord(
                        step=self._step,
                        worker=rank,
                        batch_size=bucket.batch_size,
                        seq_len=bucket.seq_len,
                        compute_time=dt * scale,
                        timing="device",
                        ring_ranks=getattr(bucket, "n_ranks", 1),
                    )
                )
        self._records[rank] = recs
        self._rank_times[rank] = (prev - t0) * scale

    def join(self) -> tuple[list[WorkerStepRecord], list[float]]:
        """Block on every rank's tail sentinel; returns (records, rank_times)."""
        for t in self._threads:
            t.join()
        ranks = sorted(self._rank_times)
        records = [r for rank in ranks for r in self._records[rank]]
        return records, [self._rank_times[r] for r in ranks]


def data_axis_devices(mesh: Mesh, axis: str = "data") -> list:
    """Mesh devices ordered along the data axis (other axes must be 1)."""
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no {axis!r} axis")
    for name in mesh.axis_names:
        if name != axis and mesh.shape[name] != 1:
            raise ValueError(
                f"plan execution shards microbatches over {axis!r} only; "
                f"axis {name!r} has size {mesh.shape[name]} (use a pure "
                f"data-parallel mesh, e.g. launch.mesh.make_data_mesh)"
            )
    return list(mesh.devices.reshape(-1))


def worker_steps_digest(worker_steps: WorkerSteps) -> bytes:
    """Content hash of a materialized per-rank fan-out.

    The loader-facing sibling of ``core.dispatch.plan_digest``: when a host
    only holds its plan's *materialized* form (bucket, batch) — e.g. out of
    ``ShardedBucketedLoader`` — this hashes the rank-major microbatch
    identities, which is exactly what execution order depends on."""
    h = hashlib.sha256()
    for share in worker_steps:
        for bucket, _batch in share:
            h.update(repr(microbatch_key(bucket)).encode())
        h.update(b"|")
    return h.digest()


def digest_to_row(digest: bytes) -> np.ndarray:
    """sha256 digest -> [8] uint32 row (the all-gather wire format)."""
    if len(digest) != 32:
        raise ValueError(f"expected a 32-byte digest, got {len(digest)}")
    return np.frombuffer(digest, dtype=np.uint8).view(np.uint32).copy()


class PlanExecutor:
    """Executes one optimizer step's worth of planned microbatches on a mesh.

    Construction compiles nothing; jitted per-shape gradient steps and the
    psum/update step are built lazily and cached.  ``state`` must be placed
    on the mesh first via :meth:`place_state` (fully replicated)."""

    def __init__(
        self,
        mesh: Mesh,
        cfg: ModelConfig,
        opt: OptimizerConfig,
        *,
        policy=None,
        check_agreement: bool = True,
        donate: bool = True,
    ):
        self.mesh = mesh
        self.devices = data_axis_devices(mesh)
        self.n_ranks = len(self.devices)
        self.cfg = cfg
        self.opt = opt
        self.check_agreement = check_agreement
        self._donate = donate
        self._replicated = NamedSharding(mesh, P())
        self._stacked = NamedSharding(mesh, P("data"))
        # ONE jitted callable (the shared pool grad step, so RNG/enumeration
        # semantics can never drift from the oracle); jax retraces per
        # batch-shape signature and per execution device, so each
        # (shape, rank) pair compiles exactly once and the steady state
        # pays zero retrace.
        self._policy = policy
        self._grad_step = jax.jit(make_pool_grad_step(cfg, policy))
        # sequence-parallel split buckets: per contiguous rank group
        # (r0, k), a ("data", "seq") sub-mesh carved from the same
        # devices plus the jitted shard_map'd SP grad step (built lazily;
        # one compile per (group, shard shape))
        self._sp_steps: dict[tuple[int, int], tuple[Mesh, Any]] = {}
        self._acc_add = jax.jit(
            lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,)
        )
        # zero grad tree for mesh devices idled by an elastic shrink; the
        # committed zero scalar pins execution to the idle device (shard
        # views alone are uncommitted and would run on the default device)
        self._zeros = jax.jit(
            lambda p, z: jax.tree.map(lambda x: jnp.zeros_like(x) + z, p)
        )
        # [*] -> [1, *] fp32: the per-rank shard shape the data-axis stack
        # expects (accumulation happens in the grads' native dtype; the
        # cross-rank reduction always runs at fp32)
        self._lift = jax.jit(
            lambda t: jax.tree.map(lambda g: g[None].astype(jnp.float32), t)
        )
        self._gather_digests = jax.jit(
            jax.shard_map(
                lambda d: jax.lax.all_gather(d[0], "data", axis=0),
                mesh=mesh,
                in_specs=P("data"),
                out_specs=P(),
                check_vma=False,  # all_gather output replication isn't inferred
            )
        )
        self._update = None  # built lazily (needs the state tree structure)
        self._seen_signatures: set = set()
        # H2D double-buffer: stage() pre-places a FUTURE step's batches on
        # their rank devices while the current step computes; execute()
        # picks the placed copies up by host-object identity.  Entry:
        # (device, pinned host batch, placed device batch) — the pinned
        # object keeps the id() key from ever being reused by a new dict
        self._staged: dict[int, tuple[Any, Any, Any]] = {}

    # -- placement ---------------------------------------------------------

    def place_state(self, state) -> Any:
        """Replicate a train state across every mesh device.

        Copies before placing: ``device_put`` may alias the source buffer
        on host platforms, and the update step *donates* its state input —
        without the copy, stepping would silently delete the caller's
        original arrays (e.g. the oracle's reference state)."""
        state = jax.tree.map(lambda x: jnp.array(x, copy=True), state)
        return jax.device_put(state, self._replicated)

    def is_placed(self, state) -> bool:
        """True if ``state`` already lives replicated on this mesh."""
        sh = getattr(state["step"], "sharding", None)
        return isinstance(sh, NamedSharding) and sh.mesh == self.mesh

    def _rank_view(self, tree, rank: int):
        """Rank ``rank``'s zero-copy single-device view of a replicated tree."""
        dev = self.devices[rank]

        def view(x):
            for s in x.addressable_shards:
                if s.device == dev:
                    return s.data
            raise ValueError(f"state is not addressable on device {dev}")

        return jax.tree.map(view, tree)

    def _rank_views(self, tree) -> list:
        """Every rank's view of a replicated tree in ONE pass over shards.

        ``_rank_view`` per rank would rescan each leaf's shard list per
        rank (O(n_ranks² x n_leaves) host work per step); this walks each
        leaf's shards once and unflattens a per-rank tree list."""
        dev_index = {d: i for i, d in enumerate(self.devices)}
        leaves, treedef = jax.tree.flatten(tree)
        per_rank = [[] for _ in range(self.n_ranks)]
        for x in leaves:
            row = [None] * self.n_ranks
            for s in x.addressable_shards:
                i = dev_index.get(s.device)
                if i is not None:
                    row[i] = s.data
            if any(r is None for r in row):
                raise ValueError(
                    "state is not addressable on every mesh device"
                )
            for r in range(self.n_ranks):
                per_rank[r].append(row[r])
        return [jax.tree.unflatten(treedef, pl) for pl in per_rank]

    # -- agreement ---------------------------------------------------------

    def verify_agreement(self, digests: Sequence[bytes]) -> None:
        """All-gather per-host plan digests across the mesh and require
        unanimity.  ``digests[r]`` is what host ``r`` independently derived;
        a real deployment passes each host's local digest, the single-host
        emulation passes ``[plan.digest()] * n_ranks``."""
        if len(digests) != self.n_ranks:
            raise ValueError(
                f"{len(digests)} digests for {self.n_ranks} ranks"
            )
        rows = [digest_to_row(d) for d in digests]
        arr = jax.make_array_from_single_device_arrays(
            (self.n_ranks, 8),
            self._stacked,
            [
                jax.device_put(r[None], dev)
                for r, dev in zip(rows, self.devices)
            ],
        )
        gathered = np.asarray(self._gather_digests(arr))
        ref = gathered[0]
        bad = [r for r in range(self.n_ranks) if not (gathered[r] == ref).all()]
        if bad:
            raise PlanAgreementError(
                f"plan digests diverge across hosts: ranks {bad} disagree "
                f"with rank 0 — refusing to step (a mismatched plan means "
                f"mismatched collectives: deadlock or silent grad skew)"
            )

    # -- warmup ------------------------------------------------------------

    def warmup(self, state, batches: Sequence[dict]) -> None:
        """Compile every batch signature on every mesh device.

        Benchmarks and latency-sensitive loops call this once so no
        measured step ever pays a compile (the executor also tracks
        freshness itself and drops compile executions from telemetry, but
        a fully-warm cache keeps wall-clock CV honest too)."""
        for rank in range(self.n_ranks):
            dev = self.devices[rank]
            params_r = self._rank_view(state["params"], rank)
            key_r = jax.device_put(jax.random.PRNGKey(0), dev)
            idx_r = jax.device_put(np.int32(0), dev)
            outs = []
            for batch in batches:
                batch_r = jax.device_put(batch, dev)
                self._seen_signatures.add(self._signature(dev, batch_r))
                outs.append(self._grad_step(params_r, batch_r, key_r, idx_r)[0])
            for o in outs:
                o.block_until_ready()

    def time_batch(
        self, state, batch: dict, *, rank: int = 0, reps: int = 3
    ) -> list[float]:
        """Measure one microbatch's gradient-step wall time on one device.

        Runs an untimed warmup execution first (compile + cache effects),
        then ``reps`` timed executions — the shape-benchmark primitive the
        mesh dispatch bench calibrates its cost model with."""
        dev = self.devices[rank]
        params_r = self._rank_view(state["params"], rank)
        key_r = jax.device_put(jax.random.PRNGKey(0), dev)
        idx_r = jax.device_put(np.int32(0), dev)
        batch_r = jax.device_put(batch, dev)
        self._seen_signatures.add(self._signature(dev, batch_r))
        self._grad_step(params_r, batch_r, key_r, idx_r)[0].block_until_ready()
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            loss, _ = self._grad_step(params_r, batch_r, key_r, idx_r)
            loss.block_until_ready()
            times.append(time.perf_counter() - t0)
        return times

    # -- H2D staging -------------------------------------------------------

    def stage(self, worker_steps: WorkerSteps) -> None:
        """Pre-place a future step's batches on their rank devices.

        Transfers are enqueued asynchronously, so they overlap whatever the
        devices are currently computing (the double-buffered H2D leg of the
        overlapped execution engine).  Entries are keyed by the host batch
        object's identity AND pin the object itself (so a freed dict's id
        can never be reused into a stale hit); a fan-out that changed
        between stage and execute (elastic resize) simply misses the cache
        and pays a fresh ``device_put`` — staging is an optimization,
        never a correctness dependency."""
        self._staged.clear()
        for rank, share in enumerate(worker_steps[: self.n_ranks]):
            dev = self.devices[rank]
            for _bucket, batch in share:
                self._staged[id(batch)] = (dev, batch, jax.device_put(batch, dev))

    def _take_staged(self, batch, dev):
        entry = self._staged.pop(id(batch), None)
        if entry is not None and entry[0] == dev and entry[1] is batch:
            return entry[2]
        return jax.device_put(batch, dev)

    @staticmethod
    def _signature(dev, batch) -> tuple:
        return (
            dev.id,
            tuple(
                sorted(
                    (k, tuple(v.shape), str(v.dtype))
                    for k, v in batch.items()
                )
            ),
        )

    # -- sequence-parallel split buckets -----------------------------------

    def _collect_split_groups(self, worker_steps: WorkerSteps) -> dict:
        """Index and validate the fan-out's split-bucket groups.

        Returns ``{id(base): {"k", "r0", "entries": {shard: (rank, bucket,
        batch)}}}``.  A group must be complete (shards 0..k-1, each once),
        sit on contiguous ascending ranks (shard s on rank r0+s — the
        contract the planner's contiguous-window placement guarantees and
        the ring's ppermute topology assumes), fit the mesh, and carry
        equal-width shard batches with globally computed ``positions``."""
        groups: dict[int, dict] = {}
        for rank, share in enumerate(worker_steps):
            for bucket, batch in share:
                if not isinstance(bucket, SplitShard):
                    continue
                g = groups.setdefault(
                    id(bucket.base), {"k": bucket.n_ranks, "entries": {}}
                )
                if bucket.n_ranks != g["k"] or bucket.shard in g["entries"]:
                    raise ValueError(
                        "malformed split group: sibling shards disagree on "
                        "ring size or repeat a shard index"
                    )
                g["entries"][bucket.shard] = (rank, bucket, batch)
        for g in groups.values():
            k = g["k"]
            if sorted(g["entries"]) != list(range(k)):
                raise ValueError(
                    f"incomplete split group: shards {sorted(g['entries'])} "
                    f"present, expected 0..{k - 1}"
                )
            r0 = g["entries"][0][0]
            if r0 + k > self.n_ranks:
                raise ValueError(
                    f"split group needs ranks {r0}..{r0 + k - 1} but the "
                    f"mesh has {self.n_ranks} data-axis devices"
                )
            widths = set()
            for s in range(k):
                rank, _bucket, batch = g["entries"][s]
                if rank != r0 + s:
                    raise ValueError(
                        "split shards must occupy contiguous ascending "
                        f"ranks (shard {s} on rank {rank}, expected {r0 + s})"
                    )
                if "positions" not in batch:
                    raise ValueError(
                        "split shard batches need globally computed "
                        "'positions' (RoPE must not restart at the shard "
                        "boundary)"
                    )
                widths.add(batch["tokens"].shape[1])
            if len(widths) != 1:
                raise ValueError(
                    f"split shard widths differ: {sorted(widths)}"
                )
            g["r0"] = r0
        return groups

    def _sp_step(self, r0: int, k: int):
        """The jitted SP grad step for the contiguous rank group
        [r0, r0+k): a ``("data", "seq")`` sub-mesh (data dim 1) over those
        devices, running :func:`make_sp_pool_grad_step` under shard_map —
        every group rank returns the whole window's loss/grad, replicated."""
        key = (r0, k)
        if key not in self._sp_steps:
            devs = np.array(self.devices[r0 : r0 + k]).reshape(1, k)
            submesh = Mesh(devs, ("data", "seq"))
            sp = make_sp_pool_grad_step(self.cfg, self._policy)

            def body(params, tokens, labels, seg, pos, step_key, idx):
                batch = {
                    "tokens": tokens,
                    "labels": labels,
                    "segment_ids": seg,
                    "positions": pos,
                }
                return sp(params, batch, step_key, idx)

            fn = jax.jit(
                jax.shard_map(
                    body,
                    mesh=submesh,
                    in_specs=(P(),) + (P(None, "seq"),) * 4 + (P(), P()),
                    out_specs=(P(), P()),
                    check_vma=False,  # psum/ppermute defeat rep inference
                )
            )
            self._sp_steps[key] = (submesh, fn)
        return self._sp_steps[key]

    def _device_view(self, tree, dev):
        """One device's committed view of a tree of mesh-global arrays."""

        def view(x):
            for s in x.addressable_shards:
                if s.device == dev:
                    return s.data
            raise ValueError(f"array is not addressable on device {dev}")

        return jax.tree.map(view, tree)

    def _run_split_group(self, param_views, group, step_key, pool_index):
        """Dispatch one split bucket's ring step across its rank group.

        Inputs are assembled zero-copy onto the group's sub-mesh: the
        group ranks' replicated param views become one replicated sub-mesh
        array per leaf, and each rank's staged shard batch becomes the
        ``P(None, "seq")`` shard of the window's global arrays.  Returns
        ``(loss, grads, fresh)`` as sub-mesh-global (replicated) arrays —
        the caller takes per-device views (rank r0 contributes the whole
        window's gradient to the data-axis reduction; siblings contribute
        nothing, so the single pool-mean psum stays exact)."""
        r0, k = group["r0"], group["k"]
        submesh, fn = self._sp_step(r0, k)
        devs = self.devices[r0 : r0 + k]
        rep = NamedSharding(submesh, P())
        seqsh = NamedSharding(submesh, P(None, "seq"))

        def assemble_rep(*leaves):
            return jax.make_array_from_single_device_arrays(
                leaves[0].shape, rep, list(leaves)
            )

        params_g = jax.tree.map(
            assemble_rep, *[param_views[r0 + s] for s in range(k)]
        )
        shard_batches = [
            self._take_staged(group["entries"][s][2], devs[s])
            for s in range(k)
        ]
        sig = (
            "sp", r0, k,
            self._signature(devs[0], shard_batches[0]),
        )
        fresh = sig not in self._seen_signatures
        self._seen_signatures.add(sig)

        def assemble_seq(name):
            parts = [sb[name] for sb in shard_batches]
            shape = (parts[0].shape[0], sum(p.shape[1] for p in parts))
            return jax.make_array_from_single_device_arrays(
                shape, seqsh, parts
            )

        key_g = assemble_rep(*[jax.device_put(step_key, d) for d in devs])
        idx_g = assemble_rep(
            *[jax.device_put(np.int32(pool_index), d) for d in devs]
        )
        loss, grads = fn(
            params_g,
            assemble_seq("tokens"),
            assemble_seq("labels"),
            assemble_seq("segment_ids"),
            assemble_seq("positions"),
            key_g,
            idx_g,
        )
        return loss, grads, fresh

    # -- the step ----------------------------------------------------------

    def _build_update(self, state):
        opt = self.opt

        def reduce_and_update(state, stacked_grads, stacked_stats):
            def local_sum(tree):
                return jax.tree.map(
                    lambda g: jax.lax.psum(jnp.squeeze(g, 0), "data"), tree
                )

            reduce = jax.shard_map(
                local_sum,
                mesh=self.mesh,
                in_specs=P("data"),
                out_specs=P(),
            )
            grad_sum = reduce(stacked_grads)
            stat_sum = reduce(stacked_stats)  # [loss_sum, n_micro]
            n = stat_sum[1]
            grads = jax.tree.map(lambda g: g / n, grad_sum)
            new_params, new_opt, stats = adamw_update(
                state["params"], grads, state["opt"], state["step"], opt
            )
            new_state = {
                "params": new_params,
                "opt": new_opt,
                "step": state["step"] + 1,
            }
            return new_state, {"loss": stat_sum[0] / n, **stats}

        return jax.jit(
            reduce_and_update,
            donate_argnums=(0,) if self._donate else (),
        )

    def lower_update(self, state):
        """Lower the reduce-and-update program for a train state of
        ``state``'s shapes (arrays or ``ShapeDtypeStruct``s) without
        placing anything — its ``compile().memory_analysis()`` gives the
        per-device bytes of the step's largest program."""
        def on(sharding, shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        st = jax.tree.map(
            lambda x: on(self._replicated, x.shape, x.dtype), state
        )
        grads = jax.tree.map(  # what _lift stacks: [n_ranks, ...] fp32
            lambda p: on(self._stacked, (self.n_ranks,) + p.shape, jnp.float32),
            st["params"],
        )
        stats = on(self._stacked, (self.n_ranks, 2), jnp.float32)
        return self._build_update(st).lower(st, grads, stats)

    def _stack(self, per_rank_trees):
        """[rank] trees of [1, ...] device-local leaves -> one mesh array
        tree sharded along the data axis."""

        def stack(*leaves):
            shape = (self.n_ranks,) + leaves[0].shape[1:]
            return jax.make_array_from_single_device_arrays(
                shape, self._stacked, list(leaves)
            )

        return jax.tree.map(stack, *per_rank_trees)

    def execute(
        self,
        state,
        worker_steps: WorkerSteps,
        *,
        step_key,
        step: int = 0,
        digests: Sequence[bytes] | None = None,
        measure: bool | str = False,
        time_scale: Callable[[int], float] | None = None,
    ):
        """Run one planned optimizer step on the mesh.

        ``worker_steps[r]`` is rank ``r``'s ``(bucket, batch)`` list (one
        global plan's fan-out).  Microbatch RNGs derive from
        ``fold_in(step_key, pool_index)`` where ``pool_index`` enumerates
        the pool rank-major — identical to :func:`oracle_step`, so the
        reduced gradient is bit-comparable to the single-device oracle.

        Measuring modes:

        * ``measure=False`` — dispatch every rank asynchronously, block
          once at the update; no telemetry.
        * ``measure="async"`` (alias ``True``, matching ``MeshEngine``) —
          dispatch exactly like ``measure=False``, then observe completion
          through per-rank :class:`RankTimers` (device-completion deltas,
          tail-sentinel join).  Telemetry and parallelism coexist:
          ``out["timers"].join()`` yields the same ``WorkerStepRecord``
          stream with ``timing="device"``.
        * ``measure="serial"`` — block per microbatch for host-clock
          telemetry, each block an ``engine.sync`` profiler span.  Honest per-(B, S) samples, but ranks run one after
          another: wall-clock degenerates to the cross-rank SUM.  Kept as
          the benchmark baseline; opt in explicitly.

        Sequence-parallel split buckets (``SplitShard`` entries) are
        executed as ONE ring step per group on a ``("data", "seq")``
        sub-mesh over the group's contiguous devices: shard 0's rank
        dispatches the group, takes its device view of the replicated
        full-window gradient and contributes it as one logical microbatch
        (one ``pool_index``); sibling ranks contribute nothing, so the
        data-axis pool mean is exact.

        ``out["compiled"]`` reports whether any microbatch paid a fresh
        compile this step (the trainer excludes such steps from
        throughput).  A fan-out SMALLER than the mesh (elastic shrink
        mid-run) is legal: surplus devices idle for the step, contributing
        zero grad sums and zero counts so the reduced mean is unchanged.
        Growing past the mesh's device count raises — that needs a new
        mesh/executor.
        """
        if measure is True:
            measure = "async"
        if measure not in (False, "serial", "async"):
            raise ValueError(
                f"measure must be False, 'serial', or 'async'; got {measure!r}"
            )
        if len(worker_steps) > self.n_ranks:
            raise ValueError(
                f"plan fans out to {len(worker_steps)} ranks but the mesh "
                f"has only {self.n_ranks} data-axis devices (growing past "
                f"the mesh requires a new mesh/executor)"
            )
        if self.check_agreement and digests is not None:
            self.verify_agreement(digests)

        pool_index = 0
        compiled = False
        per_rank_grads, per_rank_stats = [], []
        rank_times: list[float] = []
        records: list[WorkerStepRecord] = []
        # async measure: (rank, t_dispatch0, [(bucket, loss, fresh), ...])
        rank_jobs: list[tuple[int, float, list]] = []
        param_views = self._rank_views(state["params"])
        split_groups = self._collect_split_groups(worker_steps)
        for rank in range(self.n_ranks):
            # elastic shrink: a plan may fan out to fewer ranks than the
            # mesh has devices — the extra devices idle this step,
            # contributing zero grad sums and zero counts (the psum mean
            # over the pool stays exact)
            share = worker_steps[rank] if rank < len(worker_steps) else []
            dev = self.devices[rank]
            params_r = param_views[rank]
            if not share:
                if rank < len(worker_steps):
                    raise ValueError(
                        f"rank {rank} received an empty microbatch list"
                    )
                zero = jax.device_put(np.zeros((), np.float32), dev)
                per_rank_grads.append(self._lift(self._zeros(params_r, zero)))
                per_rank_stats.append(
                    jax.device_put(np.zeros((1, 2), np.float32), dev)
                )
                if measure == "serial":
                    rank_times.append(0.0)
                elif measure == "async":
                    rank_jobs.append((rank, time.perf_counter(), []))
                continue
            key_r = jax.device_put(step_key, dev)
            acc = None
            loss_sum = None
            n_local = 0  # logical microbatches owned by this rank
            t_rank = 0.0
            jobs: list = []
            t_rank0 = time.perf_counter()
            for bucket, batch in share:
                if isinstance(bucket, SplitShard):
                    g = split_groups[id(bucket.base)]
                    if bucket.shard == 0:
                        # rank-major order visits shard 0 (the lowest rank
                        # of the contiguous group) first: dispatch the
                        # whole ring step here, on the group's sub-mesh
                        t0 = time.perf_counter()
                        loss_g, grads_g, fresh = self._run_split_group(
                            param_views, g, step_key, pool_index
                        )
                        compiled = compiled or fresh
                        g["fresh"] = fresh
                        loss = self._device_view(loss_g, dev)
                        grads = self._device_view(grads_g, dev)
                        if measure == "serial":
                            with jax.profiler.TraceAnnotation("engine.sync"):
                                loss.block_until_ready()
                            dt = time.perf_counter() - t0
                            g["dt"] = dt
                            if not fresh:
                                scale = (
                                    time_scale(rank) if time_scale else 1.0
                                )
                                t_rank += dt * scale
                                records.append(
                                    WorkerStepRecord(
                                        step=step,
                                        worker=rank,
                                        batch_size=bucket.batch_size,
                                        seq_len=bucket.seq_len,
                                        compute_time=dt * scale,
                                        ring_ranks=getattr(bucket, "n_ranks", 1),
                                    )
                                )
                        elif measure == "async":
                            # one completion sentinel per group device so
                            # sibling ranks' timers observe the ring too
                            g["sentinels"] = [
                                self._device_view(loss_g, d)
                                for d in self.devices[
                                    g["r0"] : g["r0"] + g["k"]
                                ]
                            ]
                            jobs.append((bucket, loss, fresh))
                        acc = (
                            grads if acc is None else self._acc_add(acc, grads)
                        )
                        loss_sum = loss if loss_sum is None else loss_sum + loss
                        pool_index += 1
                        n_local += 1
                    else:
                        # sibling shard: the group's psum already folded
                        # this device's compute into shard 0's gradient
                        # view — contribute nothing to the data-axis
                        # reduction, only account for the ring time
                        if measure == "serial":
                            if not g["fresh"]:
                                scale = (
                                    time_scale(rank) if time_scale else 1.0
                                )
                                dt = g["dt"] * scale
                                t_rank += dt
                                records.append(
                                    WorkerStepRecord(
                                        step=step,
                                        worker=rank,
                                        batch_size=bucket.batch_size,
                                        seq_len=bucket.seq_len,
                                        compute_time=dt,
                                        ring_ranks=getattr(bucket, "n_ranks", 1),
                                    )
                                )
                        elif measure == "async":
                            jobs.append(
                                (
                                    bucket,
                                    g["sentinels"][bucket.shard],
                                    g["fresh"],
                                )
                            )
                    continue
                batch_r = self._take_staged(batch, dev)
                idx_r = jax.device_put(np.int32(pool_index), dev)
                sig = self._signature(dev, batch_r)
                fresh = sig not in self._seen_signatures
                self._seen_signatures.add(sig)
                compiled = compiled or fresh
                t0 = time.perf_counter()
                loss, grads = self._grad_step(params_r, batch_r, key_r, idx_r)
                if measure == "serial":
                    with jax.profiler.TraceAnnotation("engine.sync"):
                        loss.block_until_ready()
                    dt = time.perf_counter() - t0
                    if not fresh:  # compile executions poison telemetry
                        scale = time_scale(rank) if time_scale else 1.0
                        t_rank += dt * scale
                        records.append(
                            WorkerStepRecord(
                                step=step,
                                worker=rank,
                                batch_size=bucket.batch_size,
                                seq_len=bucket.seq_len,
                                compute_time=dt * scale,
                                ring_ranks=getattr(bucket, "n_ranks", 1),
                            )
                        )
                elif measure == "async":
                    jobs.append((bucket, loss, fresh))
                acc = grads if acc is None else self._acc_add(acc, grads)
                loss_sum = loss if loss_sum is None else loss_sum + loss
                pool_index += 1
                n_local += 1
            if acc is None:
                # every entry on this rank was a sibling shard of some
                # split group — its compute already lives inside shard 0's
                # gradient view, so this rank reduces zeros (exactly like
                # an idle rank; the pool mean stays exact)
                zero = jax.device_put(np.zeros((), np.float32), dev)
                per_rank_grads.append(self._lift(self._zeros(params_r, zero)))
                per_rank_stats.append(
                    jax.device_put(np.zeros((1, 2), np.float32), dev)
                )
            else:
                per_rank_grads.append(self._lift(acc))
                stats = jnp.stack(
                    [loss_sum.astype(jnp.float32), jnp.float32(n_local)]
                )
                per_rank_stats.append(self._lift(stats))
            if measure == "serial":
                rank_times.append(t_rank)
            elif measure == "async":
                rank_jobs.append((rank, t_rank0, jobs))

        self._staged.clear()  # anything unclaimed this step is stale
        timers = (
            RankTimers(step, rank_jobs, time_scale)
            if measure == "async"
            else None
        )
        stacked_grads = self._stack(per_rank_grads)
        stacked_stats = self._stack(per_rank_stats)
        if self._update is None:
            self._update = self._build_update(state)
        new_state, metrics = self._update(state, stacked_grads, stacked_stats)
        out = {"loss": metrics["loss"], "records": records, "compiled": compiled}
        if measure == "serial":
            out["rank_times"] = rank_times
        elif measure == "async":
            out["timers"] = timers
        return new_state, out


def oracle_step(cfg: ModelConfig, opt: OptimizerConfig, state, worker_steps,
                *, step_key, policy=None):
    """Single-device reference: the gradient/update a non-distributed
    trainer computes for the same global pool (rank-major enumeration,
    identical per-microbatch RNG derivation).  The mesh path must match
    this to ~float32 resolution — the parity gate in the tier-1 tests.

    Split fan-outs are merged first: a split bucket's k sibling shards
    collapse back into the full packed window at shard 0's pool position,
    so one oracle definition covers split and unsplit plans."""
    worker_steps = merge_split_worker_steps(worker_steps)
    grad_fn = jax.jit(make_pool_grad_step(cfg, policy))
    acc = None
    loss_sum = 0.0
    n = 0
    for share in worker_steps:
        for _bucket, batch in share:
            loss, grads = grad_fn(state["params"], batch, step_key, np.int32(n))
            acc = (
                grads
                if acc is None
                else jax.tree.map(jnp.add, acc, grads)
            )
            loss_sum = loss_sum + loss
            n += 1
    return make_pool_update(opt)(state, acc, loss_sum, n)


def rel_l2(a, b) -> float:
    """Relative L2 distance between two pytrees (the parity metric)."""
    num = 0.0
    den = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        xf = np.asarray(x, dtype=np.float64)
        yf = np.asarray(y, dtype=np.float64)
        num += float(((xf - yf) ** 2).sum())
        den += float((yf**2).sum())
    return float(np.sqrt(num / max(den, 1e-30)))
