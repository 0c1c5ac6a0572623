"""Profiler traces: taking one, reading it, and reducing it to numbers.

A trace is kept in one plain form: a list of events ``(plane, line, name,
start_ns, dur_ns)``. ``read_xplane`` turns the profiler's ``.xplane.pb``
into that form; everything else here works on it, so the reduction can be
checked on a small recorded trace without a chip.

Device planes are named ``/device:TPU:<n>``; their operations lie on the
line ``XLA Ops``. The harness's own host spans (``bench.*``, written by
``jax.profiler.TraceAnnotation``) lie on the host plane's ``python`` line.
The profiler puts both on one clock.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_LINE = "python"
#: Prefix of the harness's own host spans.
SPAN_PREFIX = "bench."
#: The span around the traced window.
WINDOW_SPAN = "bench.window"
#: Device operations that move data between chips.
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|psum|send|recv", re.IGNORECASE)


@contextlib.contextmanager
def span(name: str, on: bool):
    """A host span in the profiler's trace when ``on``; else nothing."""
    if not on:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def start(trace_dir: str) -> None:
    """Start the profiler: device ops and host spans, no Python tracer
    (which would stamp every Python call of the harness and the engine)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def read_xplane(trace_dir: str) -> list[tuple]:
    """The events of the newest ``.xplane.pb`` under ``trace_dir``: every
    op of every TPU plane's ``XLA Ops`` line, and every host span of the
    harness on any line of the other planes (reported as on ``python``)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    events, lines = [], []
    for plane in data.planes:
        on_device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            lines.append(f"{plane.name}/{line.name}")
            if on_device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if on_device:
                    events.append((plane.name, OPS_LINE, op_name(ev.name),
                                   int(ev.start_ns), int(ev.duration_ns)))
                elif ev.name.startswith(SPAN_PREFIX):
                    events.append((plane.name, HOST_LINE, ev.name,
                                   int(ev.start_ns), int(ev.duration_ns)))
    if not any(ln == HOST_LINE for _, ln, *_ in events):
        raise ValueError(f"no span of the harness in {files[-1]}; lines: "
                         f"{lines}")
    return events


def op_name(text: str) -> str:
    """An op's name without the HLO that follows it: ``%esffn_glu_pallas.11
    = bf16[...] custom-call(...)`` gives ``esffn_glu_pallas.11``."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def window_of(events: Iterable[tuple]) -> tuple[int, int]:
    """(start, end) in ns of the ``bench.window`` host span, or where the
    trace lacks it, from the first to the last of the harness's spans."""
    host = [(name, s, s + d) for pl, ln, name, s, d in events
            if ln == HOST_LINE]
    spans = [(s, e) for name, s, e in host if name == WINDOW_SPAN] or [
        (s, e) for _, s, e in host]
    if not spans:
        raise ValueError("trace has no span of the harness")
    return min(s for s, _ in spans), max(e for _, e in spans)


def device_ops(events: Iterable[tuple]) -> dict[int, list[tuple]]:
    """device index -> [(name, start_ns, end_ns)] of its operations."""
    out: dict[int, list] = {}
    for plane, line, name, start, dur in events:
        m = DEVICE_PLANE.match(plane)
        if m and line == OPS_LINE:
            out.setdefault(int(m.group(1)), []).append(
                (name, start, start + dur))
    return out


def host_spans(events: Iterable[tuple]) -> list[tuple]:
    """[(name, start_ns, end_ns)] of the harness's host spans."""
    return [(name, s, s + d) for pl, ln, name, s, d in events
            if ln == HOST_LINE and name != WINDOW_SPAN]


def _clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted, disjoint intervals covering the same points."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def busy_ns(ops: list[tuple], window: tuple[int, int]) -> int:
    """Time within ``window`` in which some operation ran on the device."""
    return length(union(_clip([(s, e) for _, s, e in ops], *window)))


def kernel_ns(ops: list[tuple], pattern: re.Pattern,
              window: tuple[int, int]) -> int:
    """Summed device time, within ``window``, of the ops ``pattern``
    matches."""
    return length(_clip([(s, e) for name, s, e in ops if pattern.search(name)],
                        *window))


def exposed_collective_ns(ops: list[tuple], window: tuple[int, int]) -> int:
    """Time within ``window`` in which a collective ran on the device and
    no other operation did."""
    coll = union(_clip([(s, e) for n, s, e in ops if COLLECTIVE.search(n)],
                       *window))
    comp = union(_clip([(s, e) for n, s, e in ops
                        if not COLLECTIVE.search(n)], *window))
    exposed, j = 0, 0
    for s, e in coll:
        covered = 0
        while j < len(comp) and comp[j][1] <= s:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < e:
            covered += min(e, comp[k][1]) - max(s, comp[k][0])
            k += 1
        exposed += (e - s) - covered
    return exposed


def top_ops(ops: list[tuple], window: tuple[int, int],
            n: int = 10) -> list[list]:
    """[[op name, seconds]] of the ``n`` ops that took most device time."""
    total: dict[str, int] = {}
    for name, s, e in ops:
        s, e = max(s, window[0]), min(e, window[1])
        if e > s:
            total[name] = total.get(name, 0) + (e - s)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(ops: list[tuple], spans: list[tuple],
              window: tuple[int, int], n: int = 10) -> list[list]:
    """[[host activity, seconds]]: the device's idle time within
    ``window``, summed by the innermost host span that covered each idle
    stretch (``host`` where no span did), the ``n`` largest."""
    busy = union(_clip([(s, e) for _, s, e in ops], *window))
    gaps, t = [], window[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < window[1]:
        gaps.append((t, window[1]))
    # Innermost first: a shorter span nested in a longer one names the gap.
    ordered = sorted(spans, key=lambda sp: sp[2] - sp[1])
    total: dict[str, int] = {}
    for gs, ge in gaps:
        rest = [(gs, ge)]
        for name, s, e in ordered:
            nxt = []
            for a, b in rest:
                lo, hi = max(a, s), min(b, e)
                if hi > lo:
                    total[name] = total.get(name, 0) + (hi - lo)
                    if lo > a:
                        nxt.append((a, lo))
                    if b > hi:
                        nxt.append((hi, b))
                else:
                    nxt.append((a, b))
            rest = nxt
            if not rest:
                break
        for a, b in rest:
            total["host"] = total.get("host", 0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


class Trace:
    """The reductions of one traced window, averaged over its devices."""

    def __init__(self, events: list[tuple], window: Optional[tuple] = None):
        self.events = events
        self.window = window or window_of(events)
        self.ops = device_ops(events)
        if not self.ops:
            raise ValueError("trace holds no device operation")
        self.spans = host_spans(events)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _mean(self, fn) -> float:
        return sum(fn(ops) for ops in self.ops.values()) / len(self.ops)

    def busy_s(self) -> float:
        return self._mean(lambda ops: busy_ns(ops, self.window)) / 1e9

    def kernel_s(self, pattern: re.Pattern) -> float:
        return self._mean(
            lambda ops: kernel_ns(ops, pattern, self.window)) / 1e9

    def exposed_collective_s(self) -> float:
        return self._mean(
            lambda ops: exposed_collective_ns(ops, self.window)) / 1e9

    def breakdown(self) -> dict:
        first = min(self.ops)
        return {"device_ops": top_ops(self.ops[first], self.window),
                "idle_gaps": idle_gaps(self.ops[first], self.spans,
                                       self.window)}
