"""Profiler capture and the reduction from a trace to per-layer numbers.

The traced run records the device (one plane per chip) and the
benchmark's own host spans (``jax.profiler.TraceAnnotation`` named
``bench.<phase>``) on one clock. From them:

* busy time: the union of the intervals in which an operation ran on
  each chip, averaged over chips; the idle share is 1 - busy / window;
* kernel time: the summed device durations of the events a kernel gave;
* the longest idle gaps, each named by the host span that overlaps it
  most (what the host was doing while the chip waited);
* the device operations that took most time.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
import shutil
import sys
import tempfile
from collections import defaultdict
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]          # (start_s, end_s)

SPAN_PREFIX = "bench."
# Device lines that hold one event per executed operation. A chip's
# plane also has lines of whole programs and steps, which overlap them.
OP_LINES = ("XLA Ops",)


def span(name: str, on: bool):
    """A host span around a call into a layer, recorded only when the
    run is traced (so untraced runs pay nothing for it)."""
    if not on:
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@dataclass
class Trace:
    """Events of one traced window, times in seconds on one clock."""
    device_ops: Dict[int, List[Tuple[str, float, float]]] = \
        field(default_factory=dict)     # chip -> [(name, start, end)]
    host_spans: List[Tuple[str, float, float]] = field(default_factory=list)
    window: Interval = (0.0, 0.0)


def union_length(intervals: Iterable[Interval], lo: float, hi: float
                 ) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: Iterable[Interval], lo: float, hi: float
              ) -> List[Interval]:
    """The stretches of [lo, hi] that no interval covers."""
    gaps, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def name_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]
             ) -> str:
    """The host span that overlaps ``gap`` most, or ``host_other``."""
    best, name = 0.0, "host_other"
    for n, s, e in spans:
        o = overlap(gap, (s, e))
        if o > best:
            best, name = o, n
    return name


def busy_seconds(tr: Trace) -> float:
    """Device busy time averaged over the chips traced."""
    lo, hi = tr.window
    per_chip = [union_length(((s, e) for _, s, e in ops), lo, hi)
                for ops in tr.device_ops.values()]
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def kernel_events(tr: Trace, pattern: str
                  ) -> List[Tuple["re.Match", float]]:
    """(name match, device seconds) of each operation whose name matches
    ``pattern``, over all chips, inside the window."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    out = []
    for ops in tr.device_ops.values():
        for name, s, e in ops:
            m = rx.search(name)
            if m and s >= lo and e <= hi:
                out.append((m, e - s))
    return out


# Control-flow operations whose events span the operations of their
# bodies, which the trace lists as well.
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* = ")


def breakdown(tr: Trace, top: int = 10) -> Dict[str, list]:
    """The ``top`` device operations by time in the window (control-flow
    containers left out, their bodies counted instead) and the ``top``
    longest idle gaps, each named by the host span it overlaps most."""
    lo, hi = tr.window
    by_op: Dict[str, float] = defaultdict(float)
    for ops in tr.device_ops.values():
        for name, s, e in ops:
            if not CONTAINER.match(name):
                by_op[name] += overlap((s, e), (lo, hi))
    ops_top = heapq.nlargest(top, by_op.items(), key=lambda kv: kv[1])
    # A busy window has a gap between almost every two operations: name
    # only the longest, each against every host span.
    gaps = heapq.nlargest(
        top, (g for ops in tr.device_ops.values()
              for g in idle_gaps([(s, e) for _, s, e in ops], lo, hi)),
        key=lambda g: g[1] - g[0])
    return {"device_ops": [[n, t] for n, t in ops_top],
            "idle_gaps": [[name_gap(g, tr.host_spans), g[1] - g[0]]
                          for g in gaps]}


def read_xplane(path: str) -> Trace:
    """Reduce a profiler ``.xplane.pb`` to a :class:`Trace`. The window is
    the extent of the ``bench.window`` host span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    ops: Dict[int, list] = {}
    for plane in pd.planes:
        m = re.match(r"/device:[A-Z]+:(\d+)", plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name in OP_LINES:
                    # An operation's name is its HLO text, hundreds of
                    # characters, and each step repeats the same few.
                    ops.setdefault(chip, []).extend(
                        (sys.intern(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        tr.host_spans.append(
                            (ev.name[len(SPAN_PREFIX):], ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    tr.device_ops = ops
    win = [(s, e) for n, s, e in tr.host_spans if n == "window"]
    if not win:
        raise ValueError(f"{path}: no bench.window span in the trace")
    tr.window = win[0]
    tr.host_spans = [x for x in tr.host_spans if x[0] != "window"]
    return tr


@contextmanager
def profiled():
    """Trace what runs inside; yields a list that receives the
    :class:`Trace` once the profiler has written it. The raw trace goes
    to a temporary directory that is removed afterwards."""
    import jax
    out: List[Trace] = []
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    # Of the host, only the benchmark's own spans (level 1) are read; the
    # runtime's host events (level 2) would only grow the trace.
    opts.host_tracer_level = 1
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        out.append(read_xplane(files[0]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
