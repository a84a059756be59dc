"""The readers of the program's host spans on hand-built reduced traces:
device idle charged to the innermost program span, syncs per step and the
loader's wait."""

from __future__ import annotations

import pytest

from chipbench import catalog, spans, xtrace

SPAN_METRICS = ("queue_wait", "engine_idle", "loop_idle", "syncs_per_step")
US = 1000  # ns


def _trace(host, gaps_per_device, window=(0, 1000 * US)):
    w0, w1 = window
    devices = [
        xtrace.DeviceTrace(
            name=f"{xtrace.DEVICE_PREFIX}{i}",
            busy_ns=(w1 - w0) - sum(e - s for s, e in gaps),
            gaps=gaps, ops_ns={}, kernels_ns={}, modules=[],
        )
        for i, gaps in enumerate(gaps_per_device)
    ]
    host = [(xtrace.WINDOW_SPAN, w0, w1), *host]
    return xtrace.Reduced(window=window, devices=devices, host=host)


def _read(trace) -> dict:
    run = {"trace": trace}
    return {m: catalog.metric_reader(m)(run) for m in (*SPAN_METRICS, "device_idle")}


def test_known_charged_idle():
    trace = _trace(
        host=[
            ("train.step", 50 * US, 900 * US),
            ("engine.step", 80 * US, 450 * US),
            ("engine.sync", 150 * US, 180 * US),
        ],
        # 50 us under engine.step, 30 under engine.sync, 20 under engine.step
        # again; 100 under train.step alone
        gaps_per_device=[[(100 * US, 200 * US), (500 * US, 600 * US)]],
    )
    assert spans.charged_idle_ns(trace) == {
        "engine.step": 70 * US, "engine.sync": 30 * US, "train.step": 100 * US,
    }
    got = _read(trace)
    assert got["engine_idle"] == pytest.approx(10.0)
    assert got["loop_idle"] == pytest.approx(10.0)
    assert got["device_idle"] == pytest.approx(20.0)


def test_nested_and_sibling_spans():
    trace = _trace(
        host=[
            ("train.step", 0, 1000 * US),
            # siblings inside the step
            ("engine.step", 0, 400 * US),
            ("train.sync", 400 * US, 500 * US),
            # fetch > benchmark's span > loader's wait: the benchmark's span
            # is no program span, so its own time is the fetch's
            ("train.fetch", 600 * US, 800 * US),
            ("bench.loader_next", 620 * US, 780 * US),
            ("loader.wait", 650 * US, 750 * US),
            ("$loop.py:249 run", 800 * US, 1000 * US),
        ],
        gaps_per_device=[[(350 * US, 450 * US), (600 * US, 700 * US), (900 * US, 950 * US)]],
    )
    assert spans.charged_idle_ns(trace) == {
        "engine.step": 50 * US,
        "train.sync": 50 * US,
        "train.fetch": 50 * US,
        "loader.wait": 50 * US,
        "train.step": 50 * US,  # the Python frame is no program span either
    }
    got = _read(trace)
    assert got["engine_idle"] == pytest.approx(5.0)
    assert got["loop_idle"] == pytest.approx(20.0)
    assert got["engine_idle"] + got["loop_idle"] == pytest.approx(got["device_idle"])


def test_spans_clipped_at_the_window_edges():
    window = (100 * US, 1100 * US)
    trace = _trace(
        host=[
            # open when tracing started: charged inside the window, not
            # counted as a step, a sync or a wait that started there
            ("train.step", 0, 300 * US),
            ("train.sync", 20 * US, 40 * US),
            ("train.fetch", 50 * US, 250 * US),
            ("loader.wait", 60 * US, 200 * US),
            # open when tracing stopped
            ("train.step", 300 * US, 1200 * US),
            ("engine.sync", 400 * US, 500 * US),
            ("train.sync", 1000 * US, 1040 * US),
            ("train.fetch", 1045 * US, 1150 * US),
            ("loader.wait", 1050 * US, 1060 * US),
        ],
        gaps_per_device=[[(100 * US, 150 * US), (1080 * US, 1100 * US)]],
        window=window,
    )
    assert [sp[0] for sp in spans.program_spans(trace)][:3] == [
        "train.step", "train.fetch", "loader.wait",
    ]
    assert spans.charged_idle_ns(trace) == {"loader.wait": 50 * US, "train.fetch": 20 * US}
    got = _read(trace)
    assert got["loop_idle"] == pytest.approx(7.0)
    assert got["engine_idle"] == 0.0
    assert got["syncs_per_step"] == 2.0  # one step, its two syncs
    assert got["queue_wait"] == pytest.approx(0.010)  # the wait that started inside


def test_uncovered_idle_is_charged_to_no_program_span():
    trace = _trace(
        host=[
            ("train.step", 200 * US, 400 * US),
            ("engine.step", 250 * US, 350 * US),
            ("bench.loader_next", 500 * US, 600 * US),
        ],
        gaps_per_device=[
            [(0, 100 * US), (300 * US, 320 * US), (500 * US, 600 * US)],
            [(380 * US, 420 * US)],
        ],
    )
    got = _read(trace)
    # mean over the two devices: 20 us of engine idle on the first, 20 us of
    # loop idle on the second; the rest lies outside every program span
    assert got["engine_idle"] == pytest.approx(1.0)
    assert got["loop_idle"] == pytest.approx(1.0)
    assert got["device_idle"] == pytest.approx(13.0)
    assert got["engine_idle"] + got["loop_idle"] <= got["device_idle"]


def test_syncs_per_step_and_queue_wait():
    trace = _trace(
        host=[
            ("train.step", 0, 400 * US),
            ("engine.sync", 100 * US, 110 * US),
            ("engine.sync", 200 * US, 210 * US),
            ("train.sync", 300 * US, 310 * US),
            ("loader.wait", 350 * US, 450 * US),
            ("train.step", 500 * US, 900 * US),
            ("engine.sync", 600 * US, 610 * US),
            ("train.sync", 700 * US, 710 * US),
            ("loader.wait", 800 * US, 1100 * US),
        ],
        gaps_per_device=[[]],
    )
    got = _read(trace)
    assert got["syncs_per_step"] == 2.5
    assert got["queue_wait"] == pytest.approx(0.2)
    assert got["engine_idle"] == got["loop_idle"] == 0.0


@pytest.mark.parametrize("source", ["hand_built", "recorded"])
def test_a_trace_without_program_spans_reads_nothing(source):
    if source == "recorded":  # the harness's trace of a program without spans
        trace = xtrace.reduce_trace(xtrace.load(catalog.BENCH_DIR / "testdata" / "tiny.xplane.pb.gz"))
    else:
        trace = _trace(
            host=[("bench.loader_next", 0, 10 * US), ("$loop.py:249 run", 0, 900 * US)],
            gaps_per_device=[[(0, 500 * US)]],
        )
    got = _read(trace)
    assert got["device_idle"] > 0
    assert all(got[m] in (None, 0) for m in SPAN_METRICS)
