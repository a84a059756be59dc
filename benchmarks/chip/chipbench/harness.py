"""Drive the program's training path for one cell: set-up, the measured
window, the traced window, and the readings that the comparison with the
reference takes.

The path is the launcher's (``repro.launch.train.train``), wired here
because ``train()`` takes no seed and always writes a final checkpoint:
``bucketing_policy(--batch)`` makes the buckets, ``BucketedLoader`` draws
each step's microbatches under the ``--batch * --seq`` token budget, and
``Trainer.run`` drives an ``EmulatedEngine`` on one chip.  The
fault-tolerance runner is left out, since its checkpoint cadence would
write the whole state to disk inside the window.  A traffic file that asks
for anything else of the launcher (``--workers``, ``--mesh``, ...), or a
cell on more than one chip, is refused: this harness does not build the
mesh path, and would otherwise run one device and report it as many.

The state lives on the device once: ``EmulatedEngine.place_state`` copies
what it is given, and two copies of the state do not fit, so the engine
here adopts the state it is handed.  Everything else is the program's.

The window runs a fixed number of the loader's steps, read from the cell's
``windows/<cell>.json``: the loader's own seed is fixed, so every run, of
any code, trains on the same sequence of shapes, and a faster program
finishes the same work sooner rather than doing different work.
"""

from __future__ import annotations

import gc
import tempfile
import time
from pathlib import Path

import jax

from . import seeds
from .reference import leaf_norms
from .xtrace import WINDOW_SPAN

#: steps whose losses, first gradient and parameter change are compared;
#: the first holds a microbatch of every bucket shape
CHECK_STEPS = 3
#: steps at the end of a traced window that the profiler records
TRACE_STEPS = 8
#: the loader's own seed: every run draws the same sequence of shapes, so
#: that seeds change the values and not the work
LOADER_SEED = 0
#: launcher arguments the harness builds the program from; any other that
#: a traffic file sets away from its default is refused
HONOURED_ARGS = frozenset({"arch", "adaptive", "batch", "seq"})
#: JAX events that mean something was traced, lowered or compiled
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileWatch:
    """Counts traces, lowerings and compiles while armed."""

    def __init__(self):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.armed and event in _COMPILE_EVENTS:
            self.count += 1


class GcWatch:
    """Times the Python collector's pauses, ``(generation, seconds)``, while
    armed."""

    def __init__(self):
        self.armed = False
        self.pauses: list[tuple[int, float]] = []
        self._t0 = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if not self.armed:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t0))
            self._t0 = None

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)


class Feed:
    """The iterator ``Trainer.run`` consumes: ``first`` items, then the
    loader's, each ``next()`` marked as a host span
    (``bench.loader_next``)."""

    def __init__(self, loader, first=()):
        self.loader = loader
        self.first = list(first)
        self.items: list[list[tuple[int, int]]] = []  # (B, S) per microbatch
        self.on_next = None

    def __iter__(self):
        return self

    def __next__(self):
        if self.on_next is not None:
            self.on_next(len(self.items))
        with jax.profiler.TraceAnnotation("bench.loader_next"):
            item = self.first.pop(0) if self.first else next(self.loader)
        self.items.append([(b.batch_size, b.seq_len) for b, _ in item])
        return item


def _launcher_args(cell):
    from repro.launch.train import build_parser

    arch = cell.config["program"]["arch"]
    parser = build_parser()
    args = parser.parse_args(["--arch", arch, *cell.traffic["launcher"]])
    defaults = parser.parse_args(["--arch", arch])
    unrun = sorted(
        k for k, v in vars(args).items()
        if k not in HONOURED_ARGS and v != getattr(defaults, k)
    )
    if unrun:
        raise ValueError(
            f"cell {cell.name}: launcher arguments this harness does not run: "
            f"{unrun} (it builds one chip's BucketedLoader and EmulatedEngine)"
        )
    if not args.adaptive:
        raise ValueError(f"cell {cell.name}: the traffic must pass --adaptive")
    return args


def refuse_unsupported(cell) -> None:
    """Raise ``ValueError`` for a cell this harness would not run as it is
    described: more than one chip, or launcher arguments it ignores."""
    if cell.chips != 1:
        raise ValueError(
            f"cell {cell.name} asks for {cell.chips} chips; this harness runs "
            f"one chip's EmulatedEngine and has no mesh path"
        )
    _launcher_args(cell)


def window_steps(cell, seconds: float) -> int:
    """The window's number of steps: the cell's ``steps``, set so that the
    window lasts about its ``seconds``, scaled to ``seconds``."""
    w = cell.window
    return max(1, round(w["steps"] * seconds / w["seconds"]))


def program_config(cell):
    """The program's ``ModelConfig`` and ``OptimizerConfig`` for a cell, and
    the launcher arguments of its traffic.  The config file names the
    program's config of the model (``program.config``) and the launcher
    ``--arch`` whose optimizer it trains with (``program.arch``); the
    cell's family checks every width of the file against the program's
    config."""
    from repro.launch.train import optimizer_for

    c = cell.config
    cfg = cell.family.program_model(c)
    args = _launcher_args(cell)
    opt = optimizer_for(args, cfg)
    stated = c["optimizer"]
    for k in ("beta1", "beta2", "eps", "weight_decay", "clip_norm"):
        if getattr(opt, k) != stated[k]:
            raise ValueError(f"optimizer {k}: program {getattr(opt, k)} != config {stated[k]}")
    if (opt.peak_lr, opt.schedule, opt.warmup) != (stated["lr"], "constant", 0):
        raise ValueError(f"optimizer schedule differs from the config: {opt}")
    return cfg, opt, args


def media_shapes(traffic):
    from repro.core.bucketing import DataShape

    return [DataShape(f, h, w, 0) for f, h, w in traffic["media"]]


class TrainCell:
    """One run of a training cell on the chip it was given."""

    def __init__(self, cell, seed: int):
        from repro.launch.train import bucketing_policy

        refuse_unsupported(cell)
        self.cell, self.seed = cell, seed
        self.cfg, self.opt, self.args = program_config(cell)
        self.buckets = bucketing_policy(self.args.batch).make_buckets(
            media_shapes(cell.traffic)
        )

    # -- program objects ---------------------------------------------------

    def _make_batch(self, rng, bucket):
        return self.cell.family.program_batch(
            seeds.batch_key(self.seed, int(rng.integers(2**31))),
            bucket.batch_size, bucket.seq_len, self.cfg,
        )

    def _loader(self, make_batch=None):
        from repro.data.pipeline import BucketedLoader

        return BucketedLoader(
            self.buckets, self.cell.traffic["weights"], make_batch or self._make_batch,
            budget=float(self.args.batch * self.args.seq),
            budget_of=lambda b: float(b.tokens),
            seed=LOADER_SEED,
        )

    def planned_items(self):
        """The loader's items in the order a run consumes them, each a list
        of ``(draw, B, S)``, from a twin loader that draws the same
        sequence and makes no batches."""
        draws = []

        def draw_only(rng, bucket):
            draws.append((int(rng.integers(2**31)), bucket.batch_size, bucket.seq_len))
            return {}

        loader = self._loader(draw_only)
        try:
            used = 0
            while True:
                n = len(next(loader))
                yield draws[used:used + n]
                used += n
        finally:
            loader.close()

    def _first_item(self):
        """The first step: one microbatch of every bucket shape, from a
        stream of its own, so that every program the window runs compiles
        here and the comparison covers the longest sequence and a pool of
        several microbatches."""
        make = self.cell.family.program_batch
        return [
            (b, make(
                seeds.batch_key(self.seed, i, first=True), b.batch_size, b.seq_len,
                self.cfg,
            ))
            for i, b in enumerate(self.buckets)
        ]

    # -- set-up ----------------------------------------------------------------

    def setup(self, log):
        """The state made from the seed on the device, and the compared
        steps driven through the window's own ``Trainer.run`` and feed.
        Returns ``(trainer, state, feed, keys, readings)``; the caller
        closes ``feed.loader``."""
        from repro.train.engine import EmulatedEngine
        from repro.train.loop import Trainer
        from repro.train.steps import init_state

        class AdoptingEngine(EmulatedEngine):
            def place_state(self, state):
                return state

        cfg, opt = self.cfg, self.opt
        init = jax.jit(init_state, static_argnums=(1, 2))
        state = init(seeds.init_key(self.seed), cfg, opt)
        jax.block_until_ready(state)
        log("initial state made")
        trainer = Trainer(cfg, opt, engine=AdoptingEngine(cfg, opt))
        feed = Feed(self._loader(), first=[self._first_item()])
        keys = seeds.trainer_key(self.seed)
        try:
            state, h1 = trainer.run(state, feed, 1, rng=keys, log_every=0)
            keys = jax.random.split(keys)[0]
            b1 = opt.beta1
            grad_norms = {k: x / (1 - b1) for k, x in leaf_norms(state["opt"]["m"]).items()}
            state, h2 = trainer.run(state, feed, CHECK_STEPS - 1, rng=keys, log_every=0)
            for _ in range(CHECK_STEPS - 1):
                keys = jax.random.split(keys)[0]
            p0 = jax.jit(lambda k: init_state(k, cfg, opt)["params"])(seeds.init_key(self.seed))
            change = leaf_norms(state["params"], p0)
            del p0
        except BaseException:
            feed.loader.close()
            raise
        readings = {
            "losses": h1.losses + h2.losses,
            "grad_norms": grad_norms,
            "change_norms": change,
        }
        log(f"compared steps: pools (B, S) {feed.items}; losses {readings['losses']}")
        return trainer, state, feed, keys, readings

    def readings(self, log) -> dict:
        """Set-up alone: the compared steps' readings, the state freed."""
        trainer, state, feed, _, readings = self.setup(log)
        feed.loader.close()
        del trainer, state
        gc.collect()
        return readings

    # -- the run -------------------------------------------------------------

    def run(self, seconds: float, trace: bool, t_start: float, log) -> dict:
        compiles, collections = CompileWatch(), GcWatch()
        trainer, state, feed, keys, readings = self.setup(log)
        n_steps = window_steps(self.cell, seconds)
        first = len(feed.items)
        try:
            trace_dir, span = None, []
            if trace:
                trace_dir = Path(tempfile.mkdtemp(prefix="chipbench-trace-"))
                start_at = first + max(0, n_steps - TRACE_STEPS)

                def on_next(i):
                    if i == start_at:
                        jax.profiler.start_trace(str(trace_dir))
                        # a span records only if made while tracing is on
                        span.append(jax.profiler.TraceAnnotation(WINDOW_SPAN))
                        span[0].__enter__()

                feed.on_next = on_next
            # set-up's objects are kept for the run: move them out of the
            # collector's reach, so that no full collection of them lands in
            # the window
            t0 = time.perf_counter()
            heap = len(gc.get_objects())
            gc.collect()
            log(f"window: {n_steps} steps; set-up heap {heap} objects, a full "
                f"collection {time.perf_counter() - t0:.3f} s, then frozen")
            gc.freeze()
            setup_s = time.perf_counter() - t_start
            compiles.armed = collections.armed = True
            t0 = time.perf_counter()
            state, hw = trainer.run(state, feed, n_steps, rng=keys, log_every=0)
            jax.block_until_ready(state)
            window_s = time.perf_counter() - t0
            compiles.armed = collections.armed = False
            gc.unfreeze()
            if trace:
                span[0].__exit__(None, None, None)
                jax.profiler.stop_trace()
            feed.on_next = None
            if compiles.count or hw.compile_steps:
                raise RuntimeError(
                    f"the window compiled: {compiles.count} trace/lower/compile "
                    f"events, engine events {hw.events}"
                )
            if len(hw.step_times) != n_steps:
                raise RuntimeError(f"the window ran {len(hw.step_times)} of {n_steps} steps")
            window_items = feed.items[first:first + n_steps]
            memory = [d.memory_stats() or {} for d in jax.devices()[: self.cell.chips]]
        finally:
            feed.loader.close()
            collections.close()
        _log_window(log, hw.step_times, window_items, collections.pauses)
        del state, trainer
        gc.collect()
        traced = window_items[max(0, n_steps - TRACE_STEPS):] if trace else []
        return {
            "setup_s": setup_s,
            "window_s": window_s,
            "steps": n_steps,
            "tokens": sum(hw.tokens),
            "microbatches": [mb for it in window_items for mb in it],
            "traced_microbatches": [mb for it in traced for mb in it],
            "memory": memory,
            "trace_dir": trace_dir,
            "readings": readings,
        }


def _log_window(log, step_times, items, pauses) -> None:
    slow = sorted(range(len(step_times)), key=lambda i: -step_times[i])[:3]
    log("slowest window steps: " + ", ".join(
        f"#{i} {step_times[i]:.3f} s {items[i]}" for i in slow
    ))
    longest = max(pauses, key=lambda p: p[1], default=(None, 0.0))
    log(f"window gc: {len(pauses)} collections, {sum(p for _, p in pauses):.4f} s "
        f"in all, longest {longest[1]:.4f} s (generation {longest[0]})")


def check_steps(cell, seed: int) -> list:
    """The compared steps as the reference takes them: each step's key and
    its pool of ``(batch key, B, S)``: first one microbatch of every bucket
    shape, then the loader's first items."""
    tc = TrainCell(cell, seed)
    items = tc.planned_items()
    try:
        pools = [next(items) for _ in range(CHECK_STEPS - 1)]
    finally:
        items.close()
    first = [
        (seeds.batch_key(seed, i, first=True), b.batch_size, b.seq_len)
        for i, b in enumerate(tc.buckets)
    ]
    pools = [first] + [[(seeds.batch_key(seed, d), b, s) for d, b, s in p] for p in pools]
    return list(zip(seeds.step_keys(seed, CHECK_STEPS), pools))


def optimizer_dict(config: dict) -> dict:
    o = config["optimizer"]
    return {k: o[k] for k in ("lr", "beta1", "beta2", "eps", "weight_decay", "clip_norm")}
