"""Rehearse a cell's depth cut against a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/fit_depth.py --workload wan14b.mix

Compiles, for a described ``v5e:2x2`` chip, the engine's optimizer update
and the pool grad step of every bucket shape of the cell's traffic, with
the Pallas kernels, at a given depth, and reports each program's device
bytes by ``compiled.memory_analysis()``: the state plus the grad step's
program (its arguments, outputs and temporaries), and the update alone.
The largest depth whose every need fits ``--budget`` is the cut that the
configuration file records, with these numbers.  Compiling is all it
does: nothing runs, and it never reports a time.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: device bytes the state plus one step's largest program may take (a v5e
#: chip has 16 GiB of HBM; the rest is headroom for the loader's prefetched
#: batches and allocator fragmentation)
BUDGET_BYTES = 14e9


def program_bytes(compiled) -> float:
    """Device bytes a compiled program holds while it runs (``inf`` for one
    the compiler refused as too large)."""
    if compiled is None:
        return math.inf
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    )


def tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def needs(cfg, opt, buckets, chip, program_batch) -> dict:
    """Bytes of the update and of each bucket's grad step (with the state)
    at ``cfg``'s depth, compiled for the described ``chip``;
    ``program_batch`` is the cell's family's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.train.steps import make_pool_grad_step, make_pool_update, state_shapes

    sh = SingleDeviceSharding(chip)

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree)

    def compile_fitting(lowered):
        try:
            return lowered.compile()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return None

    st = placed(state_shapes(cfg, opt))
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=sh)
    update = jax.jit(make_pool_update(opt), donate_argnums=(0,))
    out = {"update": program_bytes(compile_fitting(update.lower(st, st["params"], scalar, scalar)))}
    step = jax.jit(make_pool_grad_step(cfg))
    key = placed(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=sh)
    for b in buckets:
        batch = placed(jax.eval_shape(
            lambda k, b=b: program_batch(k, b.batch_size, b.seq_len, cfg),
            jax.random.PRNGKey(0),
        ))
        compiled = compile_fitting(step.lower(st["params"], batch, key, idx))
        out[f"grad B={b.batch_size} S={b.seq_len}"] = tree_bytes(st) + program_bytes(compiled)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--depths", type=int, nargs="*",
                    help="depths to report (default: search for the largest)")
    ap.add_argument("--budget", type=float, default=BUDGET_BYTES)
    args = ap.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import jax
    from jax.experimental import topologies

    from chipbench import catalog, harness
    from repro import kernels as K

    jax.config.update("jax_enable_compilation_cache", False)
    K.set_backend("pallas")
    cell = catalog.find_cell(args.workload, ROOT)
    from repro.launch.train import bucketing_policy

    cfg, opt, launch = harness.program_config(cell)
    buckets = bucketing_policy(launch.batch).make_buckets(harness.media_shapes(cell.traffic))
    chip = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
    published = cell.config["published"]["num_layers"]

    def fits(depth: int) -> bool:
        n = needs(dataclasses.replace(cfg, n_layers=depth), opt, buckets, chip,
                  cell.family.program_batch)
        print(json.dumps({"depth": depth, "bytes": n, "max": max(n.values())}), flush=True)
        return max(n.values()) <= args.budget

    if args.depths:
        for d in args.depths:
            fits(d)
        return
    lo, hi = 0, published
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid - 1
    print(json.dumps({"cut": lo, "budget": args.budget}))


if __name__ == "__main__":
    main()
