#!/usr/bin/env python3
"""The program's own spans in a trace: idle gaps named by the program
phase behind them, and the serving loop's host time per batch.

The program records spans named ``repro.<layer>.<phase>`` on the
profiler's clock when its spans are on (``repro.obs``). This module
reads them from a trace and joins them to what ``tracing.py`` reduces:

* ``name_gaps``: each of a breakdown's longest idle gaps keeps its
  length and its benchmark-span name, and gains as a suffix the program
  span in whose own time (its interval less its children's) the host
  spent most of the gap, as in ``drain/coord.harvest``;
* ``host_ms_per_batch``: the union of the program's spans on the serving
  loop's thread inside the window, over the batches dispatched in it;
* ``self_ms``: each span name's own time inside the window.

Run as a script, it makes one traced run of a cell with the program's
spans on and prints those numbers, with the cell's per-layer metrics, as
one JSON line::

    python3 benchmarks/chip/program_trace.py --workload <cell> --seed <n>
"""
from __future__ import annotations

import heapq
import sys
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

T_PROCESS_START = time.monotonic()

if __name__ == "__main__":
    _root = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(_root / "src"), str(_root)]

from benchmarks.chip import tracing  # noqa: E402

PREFIX = "repro."
Interval = Tuple[float, float]


@dataclass
class Span:
    """One program span: name without the prefix, times in seconds on
    the trace's clock, its integer arguments, and the host thread (line)
    that recorded it."""
    name: str
    start: float
    end: float
    args: Dict[str, int] = field(default_factory=dict)
    thread: int = 0


def read_program_spans(path: str) -> List[Span]:
    """The ``repro.`` spans of a profiler ``.xplane.pb``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span(ev.name[len(PREFIX):],
                                    ev.start_ns * 1e-9,
                                    (ev.start_ns + ev.duration_ns) * 1e-9,
                                    {k: v for k, v in ev.stats},
                                    thread))
    return out


def _contained(inner: Span, outer: Span) -> bool:
    return (inner is not outer and inner.thread == outer.thread
            and outer.start <= inner.start and inner.end <= outer.end)


def self_overlap(gap: Interval, span: Span, spans: Sequence[Span]) -> float:
    """How much of ``gap`` ``span`` covers outside the spans nested in
    it: the part of the gap spent in its own code."""
    own = tracing.overlap(gap, (span.start, span.end))
    if own <= 0:
        return 0.0
    kids = [(c.start, c.end) for c in spans if _contained(c, span)]
    return own - tracing.union_length(kids, *gap)


def program_phase(gap: Interval, spans: Sequence[Span]) -> str:
    """The program span whose own time covers most of ``gap``, or ""
    where no program span overlaps it."""
    near = [s for s in spans if s.end > gap[0] and s.start < gap[1]]
    best, name = 0.0, ""
    for s in near:
        o = self_overlap(gap, s, near)
        if o > best:
            best, name = o, s.name
    return name


def name_gaps(tr: tracing.Trace, spans: Sequence[Span], top: int = 10
              ) -> List[list]:
    """``tracing.breakdown``'s idle gaps, the same gaps in the same
    order, each named ``<benchmark span>/<program span>`` where a
    program span overlaps it."""
    lo, hi = tr.window
    gaps = heapq.nlargest(
        top, (g for ops in tr.device_ops.values()
              for g in tracing.idle_gaps([(s, e) for _, s, e in ops],
                                         lo, hi)),
        key=lambda g: g[1] - g[0])
    out = []
    for g in gaps:
        name = tracing.name_gap(g, tr.host_spans)
        phase = program_phase(g, spans)
        out.append([f"{name}/{phase}" if phase else name, g[1] - g[0]])
    return out


def loop_thread(spans: Sequence[Span]) -> int:
    """The thread that dispatched the batches: the serving loop's."""
    counts: Dict[int, int] = defaultdict(int)
    for s in spans:
        if s.name == "exec.dispatch":
            counts[s.thread] += 1
    return max(counts, key=counts.get) if counts else -1


def host_ms_per_batch(spans: Sequence[Span], window: Interval):
    """Host time of the serving path per batch, in ms: the union of the
    program's spans on the loop's thread inside ``window``, over the
    ``exec.dispatch`` spans that start in it. None without a batch."""
    t = loop_thread(spans)
    mine = [s for s in spans if s.thread == t]
    n = sum(1 for s in mine if s.name == "exec.dispatch"
            and window[0] <= s.start < window[1])
    if n == 0:
        return None
    busy = tracing.union_length(((s.start, s.end) for s in mine), *window)
    return 1000.0 * busy / n


def self_ms(spans: Sequence[Span], window: Interval) -> Dict[str, float]:
    """Each span name's own time inside ``window`` on the loop's thread,
    in ms (its intervals less those of the spans nested in it)."""
    t = loop_thread(spans)
    mine = sorted((s for s in spans if s.thread == t),
                  key=lambda s: (s.start, -s.end))
    out: Dict[str, float] = defaultdict(float)
    stack: List[Span] = []
    for s in mine:
        while stack and stack[-1].end <= s.start:
            stack.pop()
        out[s.name] += 1000.0 * tracing.overlap((s.start, s.end), window)
        if stack and _contained(s, stack[-1]):
            out[stack[-1].name] -= 1000.0 * tracing.overlap(
                (s.start, s.end), window)
        stack.append(s)
    return dict(out)


def traced_run(cell, seed: int, seconds: float):
    """``bench.run_cell`` traced, with the program's spans on. Returns
    its result, the reduced trace and the program's spans."""
    from benchmarks.chip import bench
    from repro import obs

    got: list = []
    read = tracing.read_xplane

    def read_both(path):
        tr = read(path)
        got.append((tr, read_program_spans(path)))
        return tr

    tracing.read_xplane = read_both
    obs.enable(True)
    try:
        res = bench.run_cell(cell, seed, seconds, True, T_PROCESS_START)
    finally:
        obs.enable(False)
        tracing.read_xplane = read
    return (res,) + got[0]


def main(argv=None) -> int:
    import argparse
    import json

    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse
                                .RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=None,
                   help="window length (default and most: "
                        "bench.TRACE_WINDOW_S)")
    args = p.parse_args(argv)

    import jax
    from benchmarks.chip import bench, run, spec

    cell = spec.load(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("program_trace: JAX found no TPU", file=sys.stderr)
        return 1
    run.enable_compile_cache()
    res, tr, spans = traced_run(
        cell, args.seed, args.seconds or bench.TRACE_WINDOW_S)
    out = {"workload": cell.name, "seed": args.seed,
           "correct": res.correct, "metrics": res.metrics,
           "device": res.device, "n_program_spans": len(spans),
           "host_ms_per_batch": host_ms_per_batch(spans, tr.window),
           "self_ms": self_ms(spans, tr.window),
           "idle_gaps": name_gaps(tr, spans, top=20),
           "breakdown": res.breakdown}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
