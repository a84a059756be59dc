"""loop_idle: device idle in the traced window charged to the training
loop's spans (``train.step``, ``train.sync``, ``train.fetch``) and to the
loader's ``loader.wait``, as a share of the window, mean over the cell's
devices.  Idle that no program span covers is in ``device_idle`` and in
neither this nor ``engine_idle`` (``chipbench/spans.py``)."""

from chipbench.spans import idle_share


def read(run: dict) -> float | None:
    trace = run["trace"]
    if trace is None:
        return None
    return idle_share(trace, lambda name: name.startswith("train.") or name == "loader.wait")
