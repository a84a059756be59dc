"""loader_wait: mean milliseconds a window step waited in ``next()`` on the
loader, the benchmark's own host span around the iterator it hands to
``Trainer.run``."""


def read(run: dict) -> float | None:
    waits = run["loader_wait_s"]
    return 1e3 * sum(waits) / len(waits) if waits else None
