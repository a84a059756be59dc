"""Training launcher.

CPU-scale real training on reduced configs (the example path), or the full
production config when pointed at a real mesh:

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --steps 50 --batch 8 --seq 256

**Resume.**  ``--steps`` is the TOTAL step count of the run; ``--resume``
restores the latest checkpoint under ``--ckpt-dir`` — weights AND the
run-state blob (trainer RNG, loader/planner RNG streams, next step) — and
trains the remaining steps.  A killed-and-resumed run therefore emits
byte-identical plan digests and matching parameters versus the
uninterrupted run; ``--digest-log`` appends each consumed plan's digest to
a file so CI can ``cmp`` the two streams.

:func:`train` is the path after config resolution (loader -> ``Trainer``
-> checkpoint); ``chip_smoke.py`` drives it at chip-scale shapes.
"""

from __future__ import annotations

import argparse
from pathlib import Path

import jax
import numpy as np

from repro.configs.registry import get_config, get_optimizer, get_smoke_config
from repro.core.bucketing import BucketingPolicy, DataShape
from repro.core.dispatch import DISPATCH_STRATEGIES
from repro.data.pipeline import BucketedLoader, ShardedBucketedLoader
from repro.data.synthetic import make_diffusion_batch, make_lm_batch
from repro.distributed.chaos import ChaosSchedule
from repro.distributed.fault_tolerance import (
    CheckpointCadence,
    FaultTolerantRunner,
    HeartbeatMonitor,
    PreemptionNotice,
)
from repro.launch.mesh import make_data_mesh
from repro.optim.adamw import OptimizerConfig
from repro.train.loop import Trainer, deserialize_rng_key
from repro.train.steps import init_state
from repro.checkpoint import store
from repro.launch.cache import enable_compilation_cache

#: the launcher's bucketed stream at CPU scale: seq lens stay <= 512 so LM
#: archs fit a single softmax-xent chunk
CPU_SHAPES = (
    DataShape(1, 256, 256, 16),
    DataShape(9, 192, 192, 16),
    DataShape(17, 192, 192, 16),
)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=30,
                    help="TOTAL steps for the run (a resumed run trains "
                         "steps..--steps from the checkpoint)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--resume", action="store_true",
                    help="restore weights + full run state (plan stream, "
                         "RNGs) from the latest checkpoint")
    ap.add_argument("--keep", type=int, default=3,
                    help="checkpoint retention: newest K survive")
    ap.add_argument("--ckpt-every", type=int, default=10,
                    help="min steps between periodic checkpoints")
    ap.add_argument("--digest-log", default=None, metavar="PATH",
                    help="append each consumed plan's sha256 digest (one "
                         "hex line per step; resume-parity evidence)")
    ap.add_argument("--adaptive", action="store_true",
                    help="bucketed AdaptiveLoad data (variable shapes)")
    ap.add_argument("--workers", type=int, default=1,
                    help="DP ranks fed from one global step plan")
    ap.add_argument("--dispatch", default="lpt", choices=DISPATCH_STRATEGIES,
                    help="step-level microbatch dispatch strategy (§4.5)")
    ap.add_argument("--mesh", action="store_true",
                    help="execute the step plan SPMD on a data mesh (one "
                         "device per rank; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N) instead "
                         "of emulating ranks serially")
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped execution: knapsack-swap plan "
                         "refinement runs behind the previous step's "
                         "compute (requires --dispatch knapsack)")
    ap.add_argument("--deterministic-refine", action="store_true",
                    help="fixed-round digest-seeded refinement: adoption "
                         "is a pure function of the plan, so overlapped "
                         "runs stay resumable and multi-host safe "
                         "(requires --overlap)")
    ap.add_argument("--refine-rounds", type=int, default=16,
                    help="exchange rounds for --deterministic-refine")
    ap.add_argument("--sp-max-ranks", type=int, default=1,
                    help="sequence parallelism: let the planner split one "
                         "long packed window across up to K contiguous "
                         "ranks (ring segment-aware attention); 1 = never "
                         "split.  Only packed variable-length microbatches "
                         "are eligible")
    ap.add_argument("--elastic", default="remap", choices=("remap", "replan"),
                    help="how rank-count changes (failures, joins) land: "
                         "'remap' keeps the plan stream at its logical "
                         "width and contiguously regroups shares onto the "
                         "surviving physical ranks (digest-stable under "
                         "churn); 'replan' resizes the loader itself "
                         "(plans re-packed for the new width)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault injection, e.g. "
                         "'kill@4:2,3;join@8:2;preempt@12' (see "
                         "repro.distributed.chaos)")
    ap.add_argument("--preempt-flag", default=None, metavar="PATH",
                    help="poll this path each step; its appearance (or "
                         "SIGTERM) triggers a graceful preemption: full "
                         "run-state save, then clean exit")
    return ap


def main() -> None:
    ap = build_parser()
    args = ap.parse_args()
    if args.workers > 1 and not args.adaptive:
        ap.error("--workers > 1 requires --adaptive (the fixed-shape stream "
                 "has no planner to shard)")
    if args.mesh and not args.adaptive:
        ap.error("--mesh requires --adaptive (mesh execution consumes the "
                 "planner's per-rank streams)")
    if args.overlap and args.dispatch != "knapsack":
        ap.error("--overlap refines knapsack plans; pass --dispatch knapsack")
    if args.overlap and not (args.mesh or args.workers > 1):
        ap.error("--overlap requires the planner-driven stream "
                 "(--workers > 1 or --mesh)")
    if args.deterministic_refine and not args.overlap:
        ap.error("--deterministic-refine configures the overlapped refiner; "
                 "pass --overlap (the synchronous knapsack pass is already "
                 "deterministic)")
    if args.resume and args.overlap and not args.deterministic_refine:
        ap.error("--resume with --overlap needs --deterministic-refine: "
                 "wall-clock adoption makes the plan stream unreplayable")
    if args.chaos and not (args.adaptive and args.workers > 1):
        ap.error("--chaos injects rank-level faults; pass --adaptive "
                 "--workers N (N > 1)")
    if args.sp_max_ranks < 1:
        ap.error("--sp-max-ranks must be >= 1")
    if args.sp_max_ranks > 1 and not (args.mesh or args.workers > 1):
        ap.error("--sp-max-ranks > 1 needs the planner-driven multi-rank "
                 "stream (--workers N > 1, usually with --mesh)")

    enable_compilation_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    train(args, cfg, CPU_SHAPES)


def bucketing_policy(batch: int) -> BucketingPolicy:
    """The dual constraint for ``--batch``: ``batch * 1024`` tokens of
    memory, ``2e7`` of ``B * S^2`` compute."""
    return BucketingPolicy(m_mem=batch * 1024, m_comp=2.0e7, p=2.0)


def optimizer_for(args: argparse.Namespace, cfg) -> OptimizerConfig:
    """The run's AdamW: the arch's peak LR, held constant over --steps."""
    opt = get_optimizer(args.arch)
    return OptimizerConfig(
        peak_lr=opt.peak_lr, schedule="constant", warmup=0,
        total_steps=args.steps, state_dtype=cfg.opt_state_dtype,
    )


def train(args: argparse.Namespace, cfg, shapes):
    """Train ``cfg`` for the run ``args`` (parsed by :func:`build_parser`)
    describes; with ``--adaptive`` the stream draws the media ``shapes``
    under :func:`bucketing_policy`.  Returns ``(state, history)``, or
    ``None`` when a resumed checkpoint already covers ``--steps``."""
    opt = optimizer_for(args, cfg)

    # the initial state lives on the host: the engine places its own device
    # copy, so the device never holds two copies of the state
    state = jax.device_get(init_state(jax.random.PRNGKey(0), cfg, opt))
    start = 0
    run_state = None
    if args.resume:
        latest = store.latest_step(args.ckpt_dir)
        if latest is not None:
            state = store.restore(args.ckpt_dir, state)
            run_state = store.load_run_state(args.ckpt_dir)
            start = run_state["step"] if run_state is not None else latest
            print(f"resumed from step {start}"
                  + ("" if run_state else " (weights-only checkpoint: "
                     "fresh run state)"))
    n_run = args.steps - start
    if n_run <= 0:
        print(f"nothing to do: checkpoint already at step {start} "
              f">= --steps {args.steps}")
        return None

    rng = np.random.default_rng(0)

    if args.adaptive:
        # variable-shape bucketed stream with the dual constraint
        policy = bucketing_policy(args.batch)
        buckets = policy.make_buckets(shapes)
    else:
        buckets = None

    def make_batch(rng_np, bucket):
        key = jax.random.PRNGKey(int(rng_np.integers(2**31)))
        if cfg.family == "mmdit":
            b = bucket.batch_size if bucket else args.batch
            s = bucket.seq_len if bucket else args.seq
            return make_diffusion_batch(key, b, s, cfg)
        b = bucket.batch_size if bucket else args.batch
        s = bucket.seq_len if bucket else args.seq
        return make_lm_batch(key, b, s, cfg.vocab, cfg)

    if buckets is not None:
        if args.mesh or args.workers > 1:
            # global step plan: one pool per step, packed across ranks by
            # quadratic load, instead of independent per-rank draws
            loader = ShardedBucketedLoader(
                buckets, None, make_batch,
                n_workers=args.workers,
                budget=float(args.batch * args.seq),
                budget_of=lambda b: float(b.tokens),
                load_of=lambda b: b.load(policy.p),
                strategy=args.dispatch,
                overlap=args.overlap,
                deterministic_refine=args.deterministic_refine,
                refine_rounds=args.refine_rounds,
                sp_max_ranks=(
                    args.sp_max_ranks if args.sp_max_ranks > 1 else None
                ),
                resume_state=(run_state or {}).get("loader"),
            )
        else:
            loader = BucketedLoader(
                buckets, None, make_batch,
                budget=float(args.batch * args.seq),
                budget_of=lambda b: float(b.tokens),
            )
        data_iter = iter(loader)
    else:
        class _Fixed:
            def __iter__(self):
                return self

            def __next__(self):
                class _B:  # fixed-shape pseudo-bucket
                    batch_size, seq_len = args.batch, args.seq
                    tokens = args.batch * args.seq
                return [(_B(), make_batch(rng, None))]

        data_iter = iter(_Fixed())

    def run_state_of(held: int) -> dict:
        if isinstance(loader, ShardedBucketedLoader):
            return {"loader": loader.state_dict(rewind=held)}
        return {}

    preemption = PreemptionNotice(flag_file=args.preempt_flag)
    preemption.install_signal_handler()
    ft = FaultTolerantRunner(
        ckpt_dir=args.ckpt_dir,
        cadence=CheckpointCadence(ckpt_cost_s=0.5, mtbf_s=3600.0,
                                  min_interval_steps=args.ckpt_every),
        monitor=HeartbeatMonitor(n_workers=args.workers, timeout_s=1e9),
        keep=args.keep,
        preemption=preemption,
    )
    chaos = ChaosSchedule.from_spec(args.chaos) if args.chaos else None
    mesh = make_data_mesh(args.workers) if args.mesh else None
    trainer = Trainer(cfg, opt, ft=ft, mesh=mesh, run_state_of=run_state_of,
                      chaos=chaos)
    if args.elastic == "remap":
        # plan stream stays at logical width --workers; rank changes only
        # regroup shares onto the surviving/grown physical fleet, so the
        # consumed digest stream is byte-identical under churn
        ft.on_resize = trainer.set_physical_ranks
    elif isinstance(loader, ShardedBucketedLoader):
        ft.on_resize = loader.resize
    trainer_rng = (
        deserialize_rng_key(run_state["trainer"]["rng"])
        if run_state is not None else jax.random.PRNGKey(1)
    )
    state, hist = trainer.run(
        state, data_iter, n_run, rng=trainer_rng, start_step=start,
        log_every=10,
    )
    n_done = len(hist.losses)  # < n_run when a preemption broke the loop
    if args.digest_log and isinstance(loader, ShardedBucketedLoader):
        # the consumed prefix of the emitted plan stream, one step per line
        # (the producer runs ahead by the prefetch depth; those plans
        # belong to the NEXT run segment)
        # append only when the run ACTUALLY resumed mid-stream — a
        # --resume with no checkpoint found starts at step 0 and must
        # truncate, or stale digests from an earlier attempt poison the
        # parity comparison
        with open(args.digest_log, "a" if start > 0 else "w") as f:
            for p in loader.plans[:n_done]:
                f.write(p.digest().hex() + "\n")
        print(f"plan digests for steps {start}..{start + n_done - 1} -> "
              f"{args.digest_log}")
    if buckets is not None:
        loader.close()
    if hist.preempted:
        # the runner already saved weights + run state inside the grace
        # window; a second save here would advance past the handoff point
        print(
            f"preempted after step {start + n_done - 1}: run state saved, "
            f"resume with --resume to train the remaining "
            f"{args.steps - start - n_done} steps"
        )
        return state, hist
    print(
        f"done: {n_run} steps ({start}..{args.steps - 1}), "
        f"final loss {hist.losses[-1]:.4f}, "
        f"throughput {hist.throughput:,.0f} tok/s, events={hist.events}"
    )
    store.save(state, args.steps, args.ckpt_dir, keep=args.keep,
               run_state=trainer.last_run_state)
    print(f"checkpoint (weights + run state) at step {args.steps} -> "
          f"{Path(args.ckpt_dir)}")
    return state, hist


if __name__ == "__main__":
    main()
