"""syncs_per_step: host blocks on the device (``train.sync`` and
``engine.sync`` spans) that start in the traced window, over the training
steps (``train.step`` spans) that start in it."""

from chipbench.spans import started


def read(run: dict) -> float | None:
    trace = run["trace"]
    if trace is None:
        return None
    steps = len(started(trace, "train.step"))
    if not steps:
        return None
    syncs = len(started(trace, "train.sync")) + len(started(trace, "engine.sync"))
    return syncs / steps
