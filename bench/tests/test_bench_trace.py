"""The reduction from a profiler trace to busy, kernel, exposed-collective
and idle time, on a hand-built trace and on one recorded on a v5e."""
import json
import os
import re

import pytest

from bench import tracing

DATA = os.path.join(os.path.dirname(__file__), "data")
TPU0, TPU1 = "/device:TPU:0", "/device:TPU:1"


def ev(plane, name, start, end, line=tracing.OPS_LINE):
    return (plane, line, name, start, end - start)


def host(name, start, end):
    return ("/host:CPU", tracing.HOST_LINE, name, start, end - start)


@pytest.fixture
def hand_trace():
    # Device 0 in a window [0, 60): a fusion [0, 10) overlapped by an
    # expert kernel [5, 15); an all-gather [20, 30) with a fusion [25, 27)
    # inside it; an all-reduce [40, 50) alone; a fusion [55, 70) that runs
    # past the window's end. Device 1 is busy throughout.
    return [
        host(tracing.WINDOW_SPAN, 0, 60),
        host("bench.tick", 0, 35),
        host("bench.submit", 30, 45),
        ev(TPU0, "fusion.1", 0, 10),
        ev(TPU0, "esffn_glu_pallas.3", 5, 15),
        ev(TPU0, "all-gather.4", 20, 30),
        ev(TPU0, "fusion.2", 25, 27),
        ev(TPU0, "all-reduce.5", 40, 50),
        ev(TPU0, "fusion.9", 55, 70),
        ev(TPU1, "fusion.1", -5, 65),
        ev(TPU0, "not-an-op", 0, 60, line="XLA Modules"),
    ]


def test_hand_counted_busy_kernel_and_exposed(hand_trace):
    tr = tracing.Trace(hand_trace)
    assert tr.window == (0, 60)
    ops0 = tr.ops[0]
    # [0, 15) + [20, 30) + [40, 50) + [55, 60) = 15 + 10 + 10 + 5.
    assert tracing.busy_ns(ops0, tr.window) == 40
    assert tracing.busy_ns(tr.ops[1], tr.window) == 60
    assert tr.busy_s() == pytest.approx(50e-9)
    pattern = re.compile(r"esffn\w*_pallas")
    assert tracing.kernel_ns(ops0, pattern, tr.window) == 10
    # all-gather: 10 less the 2 the fusion covers; all-reduce: 10.
    assert tracing.exposed_collective_ns(ops0, tr.window) == 18
    assert tracing.exposed_collective_ns(tr.ops[1], tr.window) == 0


def test_idle_gaps_name_the_innermost_host_span(hand_trace):
    tr = tracing.Trace(hand_trace)
    # Idle on device 0: [15, 20) under bench.tick; [30, 40) under
    # bench.submit, the shorter of the two spans there; [50, 55) under
    # no span.
    gaps = dict(tracing.idle_gaps(tr.ops[0], tr.spans, tr.window))
    assert gaps == {"bench.tick": 5e-9, "bench.submit": 10e-9,
                    "host": 5e-9}
    top = tracing.top_ops(tr.ops[0], tr.window, n=2)
    assert top == [["fusion.1", 10e-9], ["esffn_glu_pallas.3", 10e-9]]


def test_op_name_drops_the_hlo():
    assert tracing.op_name(
        "%esffn_glu_pallas.11 = bf16[8,128]{1,0} custom-call(%x)") == \
        "esffn_glu_pallas.11"
    assert tracing.op_name("fusion.3") == "fusion.3"


def sweep_busy(intervals, lo, hi):
    """Busy time by counting open intervals at each boundary: a second
    method, independent of tracing.union."""
    points = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    points.sort()
    busy, depth, last = 0, 0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_decode_tick():
    """One decode tick of qwen3-moe-30b-a3b (4 layers, 128 slots) traced
    on a TPU v5e: 734 device ops."""
    with open(os.path.join(DATA, "decode_tick_trace.json")) as f:
        events = [tuple(e) for e in json.load(f)]
    tr = tracing.Trace(events)
    ops = tr.ops[0]
    assert len(ops) == 734
    want = sweep_busy([(s, e) for _, s, e in ops], *tr.window)
    assert tracing.busy_ns(ops, tr.window) == want
    assert want == 84_597_134
    expert = re.compile(r"(?:^|_)esffn\w*_pallas")
    assert tracing.kernel_ns(ops, expert, tr.window) == sum(
        e - s for n, s, e in ops if n.startswith("esffn_glu_pallas"))
    assert tracing.exposed_collective_ns(ops, tr.window) == 0
    gaps = tracing.idle_gaps(ops, tr.spans, tr.window)
    assert gaps[0][0] == "bench.tick"
    assert gaps[0][1] == pytest.approx(tr.window_s - want / 1e9)


def test_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        tracing.Trace([host(tracing.WINDOW_SPAN, 0, 10)])
