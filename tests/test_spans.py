"""The program's own profiler spans.

A traced ``Trainer.run`` writes host spans named ``<layer>.<what>`` into
the profiler's trace, on the device ops' clock: ``train.step`` (with its
``step_num``), ``train.sync`` and ``train.fetch`` from the loop,
``engine.step`` and ``engine.sync`` from the engine, ``loader.wait`` from
the loader.  Read back here with ``jax.profiler.ProfileData``; the
benchmark's per-layer readers (``benchmarks/chip/chipbench/spans.py``)
count on these names and this nesting.
"""

from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from repro.core.bucketing import Bucket, DataShape  # noqa: E402
from repro.data.pipeline import BucketedLoader  # noqa: E402
from repro.data.synthetic import make_diffusion_batch  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.optim.adamw import OptimizerConfig  # noqa: E402
from repro.train.engine import EmulatedEngine, MeshEngine  # noqa: E402
from repro.train.loop import Trainer  # noqa: E402
from repro.train.steps import init_state  # noqa: E402

CFG = ModelConfig(
    name="spans-test", family="mmdit", n_layers=1, d_model=64, n_heads=2,
    n_kv_heads=2, head_dim=32, d_ff=128, vocab=0, text_len=8,
    in_channels=4, dtype="float32",
)
OPT = OptimizerConfig(peak_lr=1e-3, schedule="constant", warmup=0)
SMALL = Bucket(DataShape(1, 64, 64), 2)
LARGE = Bucket(DataShape(1, 128, 128), 1)
STEPS = 3
#: loader seed whose first steps hold 1, 2 and 2 microbatches
LOADER_SEED = 1
PREFIXES = ("train.", "engine.", "loader.")


def _make_batch(rng, bucket):
    key = jax.random.PRNGKey(int(rng.integers(2**31)))
    return make_diffusion_batch(key, bucket.batch_size, bucket.seq_len, CFG)


class _Counted:
    """The loader, with the number of microbatches of each item it hands out."""

    def __init__(self, loader):
        self.loader = loader
        self.sizes: list[int] = []

    def __iter__(self):
        return self

    def __next__(self):
        item = next(self.loader)
        self.sizes.append(len(item))
        return item


def _program_spans(trace_dir: Path) -> list[tuple[str, int, int, dict]]:
    """``(name, start, end, stats)`` of every program span in the trace, in
    start order; all on one host thread."""
    (path,) = trace_dir.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    lines = []
    for plane in pd.planes:
        for line in plane.lines:
            found = [
                (e.name, int(e.start_ns), int(e.end_ns), dict(e.stats))
                for e in line.events if e.name.startswith(PREFIXES)
            ]
            if found:
                lines.append(found)
    assert len(lines) == 1, "the program's spans lie on one thread"
    return sorted(lines[0], key=lambda sp: (sp[1], -sp[2]))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _traced_run(engine, tmp_path):
    loader = BucketedLoader(
        [SMALL, LARGE], None, _make_batch, budget=2.0,
        budget_of=lambda b: 1.0 if b is SMALL else 2.0, seed=LOADER_SEED,
    )
    feed = _Counted(loader)
    state = init_state(jax.random.PRNGKey(0), CFG, OPT)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        with jax.profiler.trace(str(tmp_path), profiler_options=options):
            Trainer(CFG, OPT, engine=engine).run(
                state, feed, STEPS, rng=jax.random.PRNGKey(1), log_every=0
            )
    finally:
        loader.close()
    assert feed.sizes == [1, 2, 2]
    return _program_spans(tmp_path), feed.sizes


def _by_name(spans, name):
    return [sp for sp in spans if sp[0] == name]


@pytest.mark.parametrize("measure", [None, "serial", "async"],
                         ids=["emulated", "mesh_serial", "mesh_async"])
def test_traced_run_writes_the_program_spans(measure, tmp_path):
    if measure is None:
        engine = EmulatedEngine(CFG, OPT)
    else:
        if jax.device_count() < 4:
            pytest.skip("needs 4 (virtual) devices")
        engine = MeshEngine(make_data_mesh(4), CFG, OPT, measure=measure)
    spans, sizes = _traced_run(engine, tmp_path)

    steps = _by_name(spans, "train.step")
    assert [sp[3]["step_num"] for sp in steps] == list(range(STEPS))
    assert len(_by_name(spans, "train.sync")) == STEPS
    assert len(_by_name(spans, "engine.step")) == STEPS
    fetches = _by_name(spans, "train.fetch")
    waits = _by_name(spans, "loader.wait")
    assert len(fetches) == len(waits) == len(sizes)
    assert all(_inside(w, f) for w, f in zip(waits, fetches))
    # the host's blocks on the device: one per microbatch where the engine
    # blocks on each, one timer join per step in async mode
    syncs = _by_name(spans, "engine.sync")
    assert len(syncs) == (STEPS if measure == "async" else sum(sizes))

    # every span lies inside a step but the first fetch and its wait
    outside = [sp for sp in spans if sp[0] != "train.step"
               and not any(_inside(sp, st) for st in steps)]
    assert outside == [fetches[0], waits[0]]
    for st, engine_step in zip(steps, _by_name(spans, "engine.step")):
        assert _inside(engine_step, st)
    if measure != "async":  # there the join follows the engine's step
        assert all(
            any(_inside(sy, es) for es in _by_name(spans, "engine.step")) for sy in syncs
        )
