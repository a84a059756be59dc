"""On-chip benchmark: run one cell once and print one JSON result line.

    python3 benchmarks/chip/run.py --workload wan13b.mix --seed 7 --seconds 30 --trace 0

From the root of a checkout.  The cell, its configuration, its traffic and
its per-layer metrics are found by name from ``BENCHMARK.json`` and the
files under ``benchmarks/chip/``; the configuration names its model
family, whose file gives the work counts and the reference model.  With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window's last steps.  Every run checks the steps it compared against the
plain reference (``chipbench/reference.py`` with the family's model) and
prints each compared number beside its limit, last on standard error and
last in the result line.

The run fails, and prints no result, without a TPU or with fewer chips
than the cell asks for, and when anything compiles inside the window.

``--readings SEED [SEED ...]`` prints, instead, the compared numbers of
the program's set-up against the reference, one seed after another in one
process (the lower readings the limits are set from), and ``--control
SEED [SEED ...]`` those of the reference in float8 and of the half-batch
fault (the upper readings); no window runs.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import warnings  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:8.1f}s] {msg}", file=sys.stderr, flush=True)


def fail(msg: str, code: int = 1) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--readings", type=int, nargs="+", metavar="SEED",
                    help="print the program's compared numbers on these "
                         "seeds instead of running the cell")
    ap.add_argument("--control", type=int, nargs="+", metavar="SEED",
                    help="print the control's and the faults' readings on "
                         "these seeds instead of running the cell")
    return ap.parse_args(argv)


def tpu_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX platform is {devices[0].platform!r}", 3)
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, JAX finds {len(devices)}", 3)
    return devices


def prepare_program() -> None:
    """Turn on the program's persistent compilation cache inside the
    checkout and insist on the compiled kernels."""
    import jax

    from repro import kernels as K
    from repro.launch.cache import enable_compilation_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compilation cache: {enable_compilation_cache()}")
    warnings.simplefilter("error", K.KernelFallbackWarning)
    if K.get_backend() != "pallas":
        fail(f"kernel backend is {K.get_backend()!r}, not the compiled kernels")


def finite(x: float):
    return x if math.isfinite(x) else None


def end_to_end(cell, run, peaks) -> dict:
    dims = cell.dims
    flops = sum(cell.family.model_flops(dims, b, s) for b, s in run["microbatches"])
    values = {
        "tokens_per_s": run["tokens"] / run["window_s"],
        "mfu": 100.0 * flops / (run["window_s"] * cell.chips * peaks["bf16_flops_per_s"]),
        "setup_s": run["setup_s"],
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell.end_to_end}


def per_layer(cell, run, peaks, reduced) -> dict:
    from chipbench import catalog

    record = {**run, "trace": reduced, "family": cell.family, "dims": cell.dims,
              "peaks": peaks, "chips": cell.chips}
    out = {}
    for m in cell.per_layer:
        value = catalog.metric_reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(args, cell, devices, peaks) -> None:
    """Run ``cell`` once on ``devices`` and print its result line."""
    from chipbench import harness, seeds, verdict, xtrace

    run = harness.TrainCell(cell, args.seed).run(args.seconds, bool(args.trace), T_START, log)
    log(f"window: {run['window_s']:.3f} s, {run['steps']} steps, {run['tokens']} tokens")

    reduced = None
    if args.trace:
        try:
            reduced = xtrace.reduce_trace(xtrace.load(xtrace.find_xplane(run["trace_dir"])))
        finally:
            shutil.rmtree(run["trace_dir"], ignore_errors=True)

    t0 = time.perf_counter()
    ref = reference(cell).run(seeds.init_key(args.seed), harness.check_steps(cell, args.seed))
    log(f"reference: {time.perf_counter() - t0:.1f} s")
    correct, checks = verdict.judge(verdict.numbers(run["readings"], ref), cell.limits)

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(m.get("peak_bytes_in_use", 0) for m in run["memory"]),
    }
    result = {"correct": correct, "attempted": run["steps"], "failed": 0}
    if args.trace:
        result["metrics"] = per_layer(cell, run, peaks, reduced)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(), "idle_gaps": reduced.top_gaps()}
    else:
        result["metrics"] = end_to_end(cell, run, peaks)
    result["device"] = device
    result["checks"] = {
        k: {"value": finite(c["value"]), "limit": c["limit"]} for k, c in checks.items()
    }
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def reference(cell, **control):
    """The plain reference of ``cell``'s model; ``control`` as
    ``chipbench.reference.Reference`` takes it."""
    from chipbench import harness
    from chipbench.reference import Reference

    return Reference(cell.family, cell.dims, harness.optimizer_dict(cell.config),
                     dtype=cell.config["param_dtype"], **control)


def run_seeds(args, cell) -> None:
    """Readings against the reference, one JSON line per seed, with no
    window: under ``program`` the set-up's compared steps (the state then
    freed) for the ``--readings`` seeds, under ``fp8`` and ``half_batch``
    the float8 control and the half-batch fault for the ``--control``
    seeds.  The reference runs once a seed."""
    from chipbench import harness, seeds, verdict

    readings, control = args.readings or [], args.control or []
    for seed in dict.fromkeys(readings + control):
        steps = harness.check_steps(cell, seed)
        out = {"seed": seed}
        prog = harness.TrainCell(cell, seed).readings(log) if seed in readings else None
        t0 = time.perf_counter()
        ref = reference(cell).run(seeds.init_key(seed), steps)
        log(f"seed {seed} reference: {time.perf_counter() - t0:.1f} s")
        if prog is not None:
            out["program"] = verdict.numbers(prog, ref)
        if seed in control:
            for name, kw in (("fp8", {"matmul": "fp8"}), ("half_batch", {"half_rows": True})):
                other = reference(cell, **kw).run(seeds.init_key(seed), steps)
                out[name] = verdict.numbers(other, ref)
        log(f"seed {seed}: {out}")
        print(json.dumps(out), flush=True)


def main(argv=None) -> None:
    args = parse(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))  # the program under test
    from chipbench import catalog

    try:
        cell = catalog.find_cell(args.workload, ROOT)
        from chipbench.harness import refuse_unsupported

        refuse_unsupported(cell)
    except (KeyError, FileNotFoundError, ValueError) as e:
        fail(str(e), 2)
    devices = tpu_devices(cell.chips)
    log(f"device: {devices[0].device_kind} x {len(devices)}; cell {cell.name}")
    from chipbench.peaks import peaks_for

    peaks = peaks_for(devices[0].device_kind)
    prepare_program()
    if args.readings or args.control:
        run_seeds(args, cell)
    else:
        run_cell(args, cell, devices, peaks)


if __name__ == "__main__":
    main()
