"""queue_wait: mean milliseconds of the program's ``loader.wait`` spans that
start in the traced window: the wait for the loader's next step, timed by
the program where it waits."""

from chipbench.spans import started


def read(run: dict) -> float | None:
    trace = run["trace"]
    if trace is None:
        return None
    waits = [e - s for _, s, e in started(trace, "loader.wait")]
    return 1e-6 * sum(waits) / len(waits) if waits else None
