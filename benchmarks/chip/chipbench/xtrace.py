"""Reduction of a profiler trace (``jax.profiler.ProfileData``) to what the
per-layer metrics read: device busy time, idle gaps and what the host did
in them, time per Pallas kernel, and per-program intervals.

The window is the host span named :data:`WINDOW_SPAN`, which the harness
opens when tracing starts and closes before it stops.  Device and host
events share one clock in the trace.

The program's ``pallas_call``s carry no names of their own (the flash and
AdaLN forward kernels are both ``_fwd_kernel``), so a kernel is told by the
shapes in its event's HLO text: the flash kernels take 4-D
``[B, H, S, dh]`` operands, the AdaLN kernels 3-D ``[B, S, D]`` ones and
the q/k RMSNorm kernels 2-D ``[rows, dh]`` ones; within each family the
results tell the passes apart.
"""

from __future__ import annotations

import dataclasses
import gzip
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: control-flow ops whose events enclose their bodies' events
_ENCLOSING = ("while", "conditional", "call")

_SHAPE = re.compile(r"\b(bf16|f32|s32|u32|f16|s8|pred)\[([0-9,]*)\]")


def _shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    return [
        (dt, tuple(int(x) for x in dims.split(",") if x))
        for dt, dims in _SHAPE.findall(text)
    ]


def classify_kernel(hlo: str) -> str | None:
    """The kernel an XLA op event ran, from its HLO text; ``None`` for an
    op that is not one of the program's Pallas kernels."""
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return None
    lhs, _, rhs = hlo.partition(" custom-call(")
    results = _shapes(lhs.partition("=")[2])
    operands = _shapes(rhs.partition("), custom_call_target")[0])
    if not operands or not results:
        return None
    rank = len(operands[0][1])
    if rank == 4:  # flash attention, [B, H, S, dh]
        if len(results) == 2 and results[1][1][-1:] == (1,):
            return "flash_fwd"  # (out, lse)
        if len(results) == 1:
            return "flash_dq"
        return "flash_dkv"  # (dk, dv)
    if rank == 3:  # AdaLN, [B, S, D]
        if len(results) == 3:
            return "adaln_fwd"  # (y, mean, rstd)
        if len(results) == 1:
            return "adaln_dx"
        return "adaln_dmod"  # (dscale, dshift)
    if rank == 2:
        return "rmsnorm"
    return None


def op_group(hlo: str) -> str:
    """Short name of an op for the breakdown: its kernel, or its HLO
    instruction name without the numeric suffix."""
    kernel = classify_kernel(hlo)
    if kernel is not None:
        return kernel
    name = hlo.split(" ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class DeviceTrace:
    name: str
    busy_ns: int
    gaps: list[tuple[int, int]]  # idle intervals inside the window
    ops_ns: dict[str, int]  # op group -> summed duration (leaf ops only)
    kernels_ns: dict[str, int]  # kernel class -> summed duration
    modules: list[tuple[str, int, int]]  # (program, start, end)


@dataclasses.dataclass
class Reduced:
    window: tuple[int, int]
    devices: list[DeviceTrace]
    host: list[tuple[str, int, int]]  # spans on the window's host thread

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, mean over the devices."""
        return sum(d.busy_ns for d in self.devices) * 1e-9 / len(self.devices)

    def kernel_s(self, prefix: str) -> float:
        """Seconds of the kernels whose class starts with ``prefix``,
        summed over the devices."""
        return sum(
            ns for d in self.devices for k, ns in d.kernels_ns.items()
            if k.startswith(prefix)
        ) * 1e-9

    def top_ops(self, n: int = 10) -> list[list]:
        total: dict[str, int] = {}
        for d in self.devices:
            for k, ns in d.ops_ns.items():
                total[k] = total.get(k, 0) + ns
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, ns * 1e-9 / len(self.devices)] for k, ns in top]

    def gap_owner(self, start: int, end: int) -> str:
        """What the host was doing in an idle gap: the innermost span of the
        window's thread that covers at least half of it."""
        best, best_len = "unattributed", None
        for name, s, e in self.host:
            if name == WINDOW_SPAN:
                continue
            cover = min(e, end) - max(s, start)
            if 2 * cover >= end - start and (best_len is None or e - s < best_len):
                best, best_len = name, e - s
        return best

    def top_gaps(self, n: int = 10) -> list[list]:
        """Idle seconds grouped by what the host was doing, mean over the
        devices, longest first."""
        total: dict[str, int] = {}
        for d in self.devices:
            for s, e in d.gaps:
                owner = self.gap_owner(s, e)
                total[owner] = total.get(owner, 0) + (e - s)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, ns * 1e-9 / len(self.devices)] for k, ns in top]


def _window(pd) -> tuple[tuple[int, int], list[tuple[str, int, int]]]:
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, int(e.start_ns), int(e.end_ns)) for e in line.events]
            for name, s, e in spans:
                if name == WINDOW_SPAN:
                    return (s, e), spans
    raise ValueError(f"the trace has no host span {WINDOW_SPAN!r}")


def reduce_trace(pd) -> Reduced:
    """Reduce a ``ProfileData`` to the window's device and host activity."""
    (w0, w1), host = _window(pd)
    devices = []
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        intervals, ops, kernels, modules = [], {}, {}, []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for e in line.events:
                    s, t = int(e.start_ns), int(e.end_ns)
                    if t <= w0 or s >= w1:
                        continue
                    s, t = max(s, w0), min(t, w1)
                    intervals.append((s, t))
                    hlo = e.name
                    group = op_group(hlo)
                    if group in _ENCLOSING:
                        continue
                    ops[group] = ops.get(group, 0) + (t - s)
                    kernel = classify_kernel(hlo)
                    if kernel is not None:
                        kernels[kernel] = kernels.get(kernel, 0) + (t - s)
            elif line.name == MODULES_LINE:
                for e in line.events:
                    s, t = int(e.start_ns), int(e.end_ns)
                    if w0 <= s < w1:
                        modules.append((e.name.split("(")[0], s, t))
        busy = _union(intervals)
        gaps, prev = [], w0
        for s, t in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, t)
        if prev < w1:
            gaps.append((prev, w1))
        devices.append(DeviceTrace(
            name=plane.name,
            busy_ns=sum(t - s for s, t in busy),
            gaps=gaps,
            ops_ns=ops,
            kernels_ns=kernels,
            modules=sorted(modules, key=lambda m: m[1]),
        ))
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    return Reduced(window=(w0, w1), devices=devices, host=host)


def load(path: Path):
    """``ProfileData`` of an ``.xplane.pb`` file, gzipped or not."""
    import jax

    data = Path(path).read_bytes()
    if data[:2] == b"\x1f\x8b":
        data = gzip.decompress(data)
    return jax.profiler.ProfileData.from_serialized_xspace(data)


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]
