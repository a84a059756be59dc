"""Bring-up smoke on a TPU: train wan2.1-1.3b at its published widths.

    python chip_smoke.py                # one chip: a few training steps
    python chip_smoke.py --four-chips   # 4-chip host: data-mesh step vs oracle

One chip: the launcher's training path (``repro.launch.train.train``:
bucketed loader -> ``Trainer`` -> checkpoint) runs a few steps of a
mixed image/video stream under the dual constraint, with the Pallas
kernels compiled.  Every width of the published config is kept; depth is
cut to the largest number of layers whose state plus one step's peak fits
``BUDGET_BYTES`` by ``compiled.memory_analysis()``.

Four chips: one or two ``PlanExecutor`` steps over a 4-device data mesh,
compared with the single-device ``oracle_step`` on the same pool.

This is a bring-up smoke, not a benchmark: step times include host-side
data generation and are printed only to show the program ran.  The script
fails without a TPU, fails if any phase fails, and prints as its last line
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import tempfile
import time
import warnings
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "wan2.1-1.3b"
#: device bytes the train state plus one step's largest program may take
#: (a v5e chip has 16 GiB of HBM; the rest is headroom for the loader's
#: prefetched batches and allocator fragmentation)
BUDGET_BYTES = 14e9
#: launcher arguments: an 8 x 1024-token memory bound for the dual
#: constraint, and a 4096-token step budget (about one microbatch a step)
BATCH, SEQ = 8, 512
STEPS = 8
#: mixed image/video media shapes: 1024 to 7800 latent tokens a sample
#: (Wan's 8x temporal, 16x spatial compression), text is a separate
#: 512-token cross-attention stream
MEDIA = ((1, 512, 512), (1, 720, 1280), (17, 480, 832), (33, 480, 832))
#: updated-parameter rel-L2, mesh vs oracle: one bf16 ulp.  Parameters are
#: bf16; the mesh sums rank gradients in fp32 while the oracle accumulates
#: in bf16, so rounding may flip the sign of a near-zero gradient and move
#: such a parameter by 2 * lr.
BF16_PARITY_BOUND = 2.0**-8


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def tpu_devices():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found (JAX platform is {devices[0].platform!r})")
    return devices


def tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def compile_fitting(lowered):
    """Compile ``lowered``; ``None`` when the TPU compiler finds that the
    program cannot fit the device's memory (a depth probe's answer, not a
    failure)."""
    import jax

    try:
        return lowered.compile()
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        return None


def program_bytes(compiled) -> float:
    """Device bytes a compiled program holds while it runs (``inf`` for
    one that could not fit)."""
    if compiled is None:
        return math.inf
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    )


def largest_depth(n_layers: int, fits) -> int:
    """Largest depth in 1..n_layers for which the monotone ``fits`` holds."""
    lo, hi = 0, n_layers
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid - 1
    if lo == 0:
        fail(f"not one layer fits {BUDGET_BYTES:.3g} bytes")
    return lo


def cut(cfg, depth: int):
    return dataclasses.replace(cfg, n_layers=depth)


def grad_programs(cfg, opt, buckets):
    """AOT-compile the pool grad step for each bucket shape; returns
    ``[(bucket, compiled, seconds, device bytes with state and grads)]``.
    The persistent compilation cache hands these to the trainer's jit."""
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import make_diffusion_batch
    from repro.train.steps import make_pool_grad_step, state_shapes

    st = state_shapes(cfg, opt)
    step = jax.jit(make_pool_grad_step(cfg))
    key = jax.random.PRNGKey(0)
    out = []
    for b in buckets:
        batch = jax.eval_shape(
            lambda k, b=b: make_diffusion_batch(k, b.batch_size, b.seq_len, cfg),
            key,
        )
        t0 = time.perf_counter()
        compiled = compile_fitting(step.lower(st["params"], batch, key, jnp.int32(0)))
        dt = time.perf_counter() - t0
        # resident: the state and the accumulated grads; the program's
        # arguments are the params (counted as the grads' size) and a batch
        need = tree_bytes(st) + program_bytes(compiled)
        out.append((b, compiled, dt, need))
    return out


def single_update_bytes(cfg, opt) -> int:
    """Bytes of the engine's optimizer update (state donated)."""
    import jax
    import jax.numpy as jnp

    from repro.train.steps import make_pool_update, state_shapes

    st = state_shapes(cfg, opt)
    f32 = jax.ShapeDtypeStruct((), jnp.float32)
    update = jax.jit(make_pool_update(opt), donate_argnums=(0,))
    return program_bytes(compile_fitting(update.lower(st, st["params"], f32, f32)))


def fit_depth(cfg, opt, buckets, update_bytes):
    """Depth cut: binary search on the (cheap) update program, then step
    down until every bucket's grad step fits as well."""
    depth = largest_depth(
        cfg.n_layers, lambda d: update_bytes(cut(cfg, d)) <= BUDGET_BYTES
    )
    while True:
        progs = grad_programs(cut(cfg, depth), opt, buckets)
        need = max(
            [update_bytes(cut(cfg, depth))] + [p[3] for p in progs]
        )
        if need <= BUDGET_BYTES:
            return depth, need, progs
        if depth == 1:
            fail(f"one layer needs {need:.3g} bytes > {BUDGET_BYTES:.3g}")
        depth -= 1


def print_config(cfg, depth: int, need: int) -> None:
    print(
        f"config {cfg.name}: d_model={cfg.d_model} heads={cfg.n_heads}x"
        f"{cfg.head_dim} d_ff={cfg.d_ff} text_len={cfg.text_len} "
        f"in_channels={cfg.in_channels} dtype={cfg.dtype} (published widths)"
    )
    print(
        f"depth cut: {depth} of {cfg.n_layers} layers (largest depth whose "
        f"state + one step's peak, {need / 1e9:.3f} GB by memory_analysis, "
        f"fits {BUDGET_BYTES / 1e9:.1f} GB)"
    )


def print_programs(progs) -> None:
    for b, compiled, dt, need in progs:
        print(
            f"compile grad step B={b.batch_size} S={b.seq_len}: {dt:.1f} s, "
            f"{need / 1e9:.3f} GB with state"
        )
    n_custom = progs[0][1].as_text().count("tpu_custom_call")
    print(f"tpu_custom_call in one compiled grad step: {n_custom}")
    if n_custom == 0:
        fail("the compiled step holds no Pallas kernel")


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


def one_chip(devices) -> None:
    from repro.configs.registry import get_config
    from repro.core.bucketing import DataShape
    from repro.launch.train import (
        bucketing_policy,
        build_parser,
        optimizer_for,
        train,
    )

    shapes = [DataShape(f, h, w, 0) for f, h, w in MEDIA]
    buckets = bucketing_policy(BATCH).make_buckets(shapes)
    cfg = get_config(ARCH)
    with tempfile.TemporaryDirectory() as ckpt:
        args = build_parser().parse_args([
            "--arch", ARCH, "--adaptive", "--steps", str(STEPS),
            "--batch", str(BATCH), "--seq", str(SEQ),
            "--ckpt-dir", ckpt, "--ckpt-every", str(10 * STEPS), "--keep", "1",
        ])
        opt = optimizer_for(args, cfg)
        depth, need, progs = fit_depth(cfg, opt, buckets, lambda c: single_update_bytes(c, opt))
        print_config(cfg, depth, need)
        print_programs(progs)
        del progs
        t0 = time.perf_counter()
        result = train(args, cut(cfg, depth), shapes)
        print(f"train() wall time {time.perf_counter() - t0:.1f} s")
    if result is None:
        fail("the training path ran no step")
    _, hist = result
    for i, (loss, dt) in enumerate(zip(hist.losses, hist.step_times)):
        tag = "  (compiles)" if i in hist.compile_steps else ""
        print(f"step {i}: loss {loss:.6f}  wall {dt:.3f} s{tag}")
    if len(hist.losses) != STEPS:
        fail(f"{len(hist.losses)} of {STEPS} steps ran")
    if not all(math.isfinite(loss) for loss in hist.losses):
        fail(f"non-finite loss: {hist.losses}")
    print(f"peak_bytes_in_use: {peak_bytes(devices[0])}")


def four_chips(devices) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.registry import get_config
    from repro.core.bucketing import DataShape
    from repro.data.synthetic import make_diffusion_batch
    from repro.distributed.plan_exec import PlanExecutor, oracle_step, rel_l2
    from repro.launch.mesh import make_data_mesh
    from repro.launch.train import bucketing_policy, build_parser, optimizer_for
    from repro.train.steps import init_state, state_shapes

    if len(devices) != 4:
        fail(f"--four-chips needs 4 chips, found {len(devices)}")
    bucket = bucketing_policy(BATCH).make_buckets([DataShape(*MEDIA[0], 0)])[0]
    cfg = get_config(ARCH)
    opt = optimizer_for(build_parser().parse_args(["--arch", ARCH]), cfg)
    mesh = make_data_mesh(4)

    def mesh_update_bytes(c):
        ex = PlanExecutor(mesh, c, opt)
        return program_bytes(compile_fitting(ex.lower_update(state_shapes(c, opt))))

    depth, need, progs = fit_depth(cfg, opt, [bucket], mesh_update_bytes)
    print_config(cfg, depth, need)
    print_programs(progs)
    del progs
    cfg = cut(cfg, depth)

    # one microbatch of the 8 x 1024 image bucket per rank, host-resident
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    pool = [
        [(bucket, jax.device_get(
            make_diffusion_batch(k, bucket.batch_size, bucket.seq_len, cfg)))]
        for k in keys
    ]
    host_state = jax.device_get(init_state(jax.random.PRNGKey(0), cfg, opt))
    step_key = jax.random.PRNGKey(1)

    # the oracle first, on device 0, then to the host: the mesh then holds
    # the only device copies of the state
    t0 = time.perf_counter()
    ref_state, ref_out = oracle_step(cfg, opt, host_state, pool, step_key=step_key)
    ref_params = jax.device_get(ref_state["params"])
    ref_loss = float(ref_out["loss"])
    del ref_state, ref_out
    print(f"oracle step (1 device): loss {ref_loss:.6f}  wall "
          f"{time.perf_counter() - t0:.1f} s")

    ex = PlanExecutor(mesh, cfg, opt)
    state = jax.device_put(host_state, NamedSharding(mesh, P()))
    del host_state
    for i, key in enumerate((step_key, jax.random.fold_in(step_key, 1))):
        t0 = time.perf_counter()
        state, out = ex.execute(state, pool, step_key=key)
        loss = float(out["loss"])
        print(f"mesh step {i} (4 devices): loss {loss:.6f}  wall "
              f"{time.perf_counter() - t0:.1f} s")
        if not math.isfinite(loss):
            fail(f"non-finite mesh loss {loss}")
        if i == 0:
            err = rel_l2(jax.device_get(state["params"]), ref_params)
            print(f"updated-parameter rel-L2, mesh vs oracle: {err:.3e} "
                  f"(bound {BF16_PARITY_BOUND:.3e}, one bf16 ulp)")
            if not err <= BF16_PARITY_BOUND:
                fail("mesh step disagrees with the single-device oracle")
            del ref_params
    for d in devices:
        print(f"peak_bytes_in_use {d.id}: {peak_bytes(d)}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-mesh step and its "
                         "single-device oracle")
    args = ap.parse_args()

    devices = tpu_devices()
    print(f"device: {devices[0].device_kind} x {len(devices)}")
    sys.path.insert(0, str(SRC))
    from repro import kernels as K
    from repro.launch.cache import enable_compilation_cache

    print(f"compilation cache: {enable_compilation_cache()}")
    # a kernel falling back to its jnp twin is a failure here
    warnings.simplefilter("error", K.KernelFallbackWarning)
    if K.get_backend() != "pallas":
        fail(f"kernel backend is {K.get_backend()!r}, not the compiled kernels")

    if args.four_chips:
        four_chips(devices)
    else:
        one_chip(devices)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))


if __name__ == "__main__":
    main()
