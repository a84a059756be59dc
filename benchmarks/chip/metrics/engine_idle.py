"""engine_idle: device idle in the traced window charged to the execution
engines' own spans (``engine.step``, ``engine.sync``), as a share of the
window, mean over the cell's devices.  Each idle instant is charged to the
innermost program span open on the window's host thread
(``chipbench/spans.py``)."""

from chipbench.spans import idle_share


def read(run: dict) -> float | None:
    trace = run["trace"]
    if trace is None:
        return None
    return idle_share(trace, lambda name: name.startswith("engine."))
