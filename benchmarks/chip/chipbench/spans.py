"""The program's own host spans in a reduced trace, and the device idle
charged to them.

The program names its host phases ``<layer>.<what>`` with
``jax.profiler.TraceAnnotation``: ``train.step``, ``train.sync`` and
``train.fetch`` in the training loop, ``engine.step`` and ``engine.sync``
in the execution engines, ``loader.wait`` in the loaders.  They share the
trace's clock with the device ops.  Program spans are those of
:data:`PROGRAM_PREFIXES` on the window's host thread (``Reduced.host``);
the benchmark's own ``bench.*`` spans and the Python tracer's frames are
not.

Each idle instant of a device is charged to the innermost program span
open at that instant; idle that no program span covers is charged to
none.
"""

from __future__ import annotations

import bisect
from typing import Callable

from .xtrace import Reduced

PROGRAM_PREFIXES = ("train.", "engine.", "loader.")


def started(trace: Reduced, name: str) -> list[tuple[str, int, int]]:
    """The spans called ``name`` that start inside the window, unclipped."""
    w0, w1 = trace.window
    return [sp for sp in trace.host if sp[0] == name and w0 <= sp[1] < w1]


def program_spans(trace: Reduced) -> list[tuple[str, int, int]]:
    """The program's spans that overlap the window.  They are not clipped to
    it: which span is innermost goes by where each really starts, and the
    devices' gaps lie inside the window already."""
    w0, w1 = trace.window
    return [
        sp for sp in trace.host
        if sp[0].startswith(PROGRAM_PREFIXES) and sp[1] < w1 and sp[2] > w0
    ]


def _innermost(spans: list[tuple[str, int, int]]) -> list[tuple[int, int, str]]:
    """The intervals between the spans' starts and ends, in order, each with
    the innermost span open over all of it: of the open spans, the one that
    started last (and of those, the one that ends first)."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(s, -e, name) for name, s, e in spans if s <= a and b <= e]
        if open_:
            out.append((a, b, max(open_)[2]))
    return out


def charged_idle_ns(trace: Reduced) -> dict[str, int]:
    """Idle nanoseconds charged to each program span's name, summed over
    the devices."""
    owners = _innermost(program_spans(trace))
    starts = [a for a, _, _ in owners]
    out: dict[str, int] = {}
    for device in trace.devices:
        for g0, g1 in device.gaps:
            i = max(bisect.bisect_right(starts, g0) - 1, 0)
            while i < len(owners) and owners[i][0] < g1:
                a, b, name = owners[i]
                cover = min(b, g1) - max(a, g0)
                if cover > 0:
                    out[name] = out.get(name, 0) + cover
                i += 1
    return out


def idle_share(trace: Reduced, owns: Callable[[str], bool]) -> float | None:
    """Percent of the window in which a device was idle while a program span
    that ``owns`` accepts was the innermost one, mean over the devices;
    ``None`` when no such span overlaps the window."""
    if not any(owns(name) for name, _, _ in program_spans(trace)):
        return None
    w0, w1 = trace.window
    charged = sum(ns for name, ns in charged_idle_ns(trace).items() if owns(name))
    return 100.0 * charged / ((w1 - w0) * len(trace.devices))
