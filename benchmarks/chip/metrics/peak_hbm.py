"""peak_hbm: ``peak_bytes_in_use`` over ``bytes_limit`` after the window,
the fullest of the cell's devices."""


def read(run: dict) -> float | None:
    shares = [
        100.0 * m["peak_bytes_in_use"] / m["bytes_limit"]
        for m in run["memory"]
        if m.get("bytes_limit") and "peak_bytes_in_use" in m
    ]
    return max(shares) if shares else None
