"""End-to-end driver: train a ~100M-parameter Wan-style MMDiT with the full
AdaptiveLoad stack — bucketed mixed image/video stream, dual-constraint
batch sizes, global step-planned dispatch across emulated DP ranks,
closed-loop scheduler, fault-tolerant checkpointing.

    PYTHONPATH=src python examples/train_wan_adaptiveload.py --steps 200

(Defaults are CPU-sized: ~100M params, a few hundred steps, synthetic
latents.  --steps 10 for a smoke run.  --workers 2 --dispatch lpt emulates
two DP ranks fed from one global plan; --straggler 1.5 degrades the last
rank to exercise the derate path.)
"""

import argparse

import jax
import numpy as np

from repro.checkpoint import store
from repro.core import (
    AdaptiveLoadScheduler,
    AnalyticDeviceModel,
    ModelDims,
    SchedulerConfig,
    fit_cost_model,
    run_analytic_benchmark,
    sweep_grid,
)
from repro.core.bucketing import DataShape
from repro.core.dispatch import DISPATCH_STRATEGIES
from repro.data.pipeline import ShardedBucketedLoader
from repro.data.synthetic import make_diffusion_batch
from repro.distributed.fault_tolerance import (
    CheckpointCadence,
    FaultTolerantRunner,
    HeartbeatMonitor,
)
from repro.launch.cache import enable_compilation_cache
from repro.models.config import ModelConfig
from repro.optim.adamw import OptimizerConfig
from repro.train.loop import Trainer, deserialize_rng_key
from repro.train.steps import init_state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/wan_adaptiveload_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--workers", type=int, default=2,
                    help="emulated DP ranks fed from one global step plan")
    ap.add_argument("--dispatch", default="lpt", choices=DISPATCH_STRATEGIES)
    ap.add_argument("--straggler", type=float, default=1.0,
                    help=">1: scale the last rank's recorded compute time "
                         "to exercise the scheduler's derate path")
    args = ap.parse_args()
    if args.straggler != 1.0 and args.workers < 2:
        ap.error("--straggler needs --workers >= 2: straggler detection "
                 "compares a rank against its peers on the same shapes")
    enable_compilation_cache()

    # ~100M-param Wan-style MMDiT (18 layers, d=512 -> 101M params)
    cfg = ModelConfig(
        name="wan-100m", family="mmdit", n_layers=18, d_model=512, n_heads=8,
        n_kv_heads=8, head_dim=64, d_ff=2048, vocab=0, text_len=32,
        in_channels=16, dtype="float32",
    )
    opt = OptimizerConfig(peak_lr=1e-4, schedule="cosine", warmup=20,
                          total_steps=args.steps)

    # mixed image/video shapes at CPU scale (S from 68 to 580 tokens)
    shapes = [
        DataShape(1, 128, 128, 4),
        DataShape(9, 128, 128, 4),
        DataShape(17, 128, 128, 4),
        DataShape(17, 192, 192, 4),
    ]

    # fit a cost model on an analytic stand-in, then let the closed loop
    # recalibrate from real step telemetry as training runs
    dims = ModelDims(n_layers=cfg.n_layers, d_model=cfg.d_model, d_ff=cfg.d_ff,
                     n_heads=cfg.n_heads, head_dim=cfg.head_dim)
    dev = AnalyticDeviceModel(dims, overhead=0.2)
    model = fit_cost_model(
        run_analytic_benchmark(dev, sweep_grid([128, 256, 512], max_batch=8))
    )
    sched = AdaptiveLoadScheduler(
        SchedulerConfig(
            target_sync=model.predict(2, max(s.seq_len for s in shapes)),
            m_mem=2048.0, refit_interval=50, min_samples=64, r2_floor=0.5,
            dispatch=args.dispatch,
        ),
        shapes, initial_model=model, n_workers=args.workers,
    )
    # full run-state resume: restore the scheduler's closed-loop state
    # BEFORE building the planner/loader so the restored fit/derate shapes
    # dispatch from the first resumed step
    run_state = None
    start = 0
    if args.resume and store.latest_step(args.ckpt_dir) is not None:
        run_state = store.load_run_state(args.ckpt_dir)
        if run_state is not None:
            start = run_state["step"]
            if "scheduler" in run_state:
                sched.load_state_dict(run_state["scheduler"])

    planner = sched.make_planner(seed=0)
    print(sched.describe())

    def make_batch(rng: np.random.Generator, bucket):
        key = jax.random.PRNGKey(int(rng.integers(2**31)))
        return make_diffusion_batch(key, bucket.batch_size, bucket.seq_len, cfg)

    # one global plan per step, fanned out to per-rank queues; the loader
    # shares the scheduler's planner (which carries buckets, budget, and
    # the dispatch strategy), so every replan (refit, derate, resize)
    # reaches dispatch with no manual plumbing
    loader = ShardedBucketedLoader(
        sched.buckets, None, make_batch,
        n_workers=args.workers, planner=planner,
        resume_state=(run_state or {}).get("loader"),
    )

    ft = FaultTolerantRunner(
        ckpt_dir=args.ckpt_dir,
        cadence=CheckpointCadence(ckpt_cost_s=1.0, mtbf_s=7200.0,
                                  min_interval_steps=50),
        monitor=HeartbeatMonitor(n_workers=args.workers, timeout_s=1e9),
    )

    state = init_state(jax.random.PRNGKey(0), cfg, opt)
    n_params = sum(p.size for p in jax.tree.leaves(state["params"]))
    print(f"model: {n_params/1e6:.1f}M params")
    if args.resume and store.latest_step(args.ckpt_dir) is not None:
        state = store.restore(args.ckpt_dir, state)
        print(f"resumed from step {start} "
              f"({'full run state' if run_state else 'weights only'})")

    scale = (
        {args.workers - 1: args.straggler} if args.straggler != 1.0 else None
    )

    def run_state_of(held: int) -> dict:
        return {
            "loader": loader.state_dict(rewind=held),
            "scheduler": sched.state_dict(),
        }

    trainer = Trainer(cfg, opt, scheduler=sched, ft=ft,
                      worker_time_scale=scale, run_state_of=run_state_of)

    seen_updates = 0

    def log_plan_updates(step: int, metrics: dict) -> None:
        # replans reach the shared planner automatically; just narrate them
        nonlocal seen_updates
        if len(sched.updates) > seen_updates:
            seen_updates = len(sched.updates)
            print(f"  [plan update @ step {step}] {sched.updates[-1].reason}")

    n_run = max(args.steps - start, 0)
    trainer_rng = (
        None if run_state is None
        else deserialize_rng_key(run_state["trainer"]["rng"])
    )
    state, hist = trainer.run(
        state, iter(loader), n_run, rng=trainer_rng, start_step=start,
        log_every=20, on_metrics=log_plan_updates,
    )
    store.save(state, start + n_run, args.ckpt_dir,
               run_state=trainer.last_run_state)
    loader.close()

    plans = loader.plans
    if plans:
        mean_plan_cv = float(np.mean([p.compute_cv() for p in plans]))
        print(f"\ndispatch ({args.dispatch}): mean planned compute-CV "
              f"{mean_plan_cv:.3f} over {len(plans)} recent plans")
    print(f"final loss {hist.losses[-1]:.4f} "
          f"(first {hist.losses[0]:.4f}); throughput {hist.throughput:,.0f} tok/s")
    print(f"scheduler after training: {sched.describe()}")
    print(f"events: {hist.events}")


if __name__ == "__main__":
    main()
