"""The reduction from a trace to per-layer numbers (``tracing.py``)."""
from pathlib import Path

import pytest

from benchmarks.chip import tracing

DATA = Path(__file__).resolve().parent / "data" / "tpu_trace.xplane.pb"


def synthetic() -> tracing.Trace:
    # chip 0 busy [1, 2) and [1.5, 3) and [4, 5); chip 1 busy [0, 1)
    return tracing.Trace(
        device_ops={0: [("fusion.1", 1.0, 2.0), ("_shed_kernel", 1.5, 3.0),
                        ("fusion.1", 4.0, 5.0)],
                    1: [("convolution", 0.0, 1.0)]},
        host_spans=[("drain", 0.0, 3.2), ("idle", 3.2, 4.0),
                    ("respond", 5.0, 6.0)],
        window=(0.0, 6.0))


def test_union_merges_overlaps_and_clips():
    assert tracing.union_length([(1, 2), (1.5, 3), (4, 5)], 0, 6) == 3.0
    assert tracing.union_length([(1, 2), (1.5, 3), (4, 5)], 2.5, 4.5) == 1.0
    assert tracing.union_length([], 0, 1) == 0.0


def test_idle_gaps_cover_what_no_operation_covers():
    assert tracing.idle_gaps([(1, 2), (1.5, 3), (4, 5)], 0, 6) == \
        [(0, 1), (3, 4), (5, 6)]


def test_busy_is_averaged_over_chips():
    assert tracing.busy_seconds(synthetic()) == pytest.approx((3.0 + 1.0) / 2)


def test_kernel_events_match_by_name_inside_the_window():
    ev = tracing.kernel_events(synthetic(), r"shed_kernel")
    assert [(m.group(0), t) for m, t in ev] == [("shed_kernel", 1.5)]


def test_breakdown_leaves_out_control_flow_containers():
    tr = tracing.Trace(
        device_ops={0: [("%while.4 = (s32[]) while(...)", 0.0, 2.0),
                        ("%fusion.1 = f32[8] fusion(...)", 0.0, 0.5),
                        ("%fusion.2 = f32[8] fusion(...)", 1.0, 1.2)]},
        window=(0.0, 2.0))
    names = [n for n, _ in tracing.breakdown(tr)["device_ops"]]
    assert names == ["%fusion.1 = f32[8] fusion(...)",
                     "%fusion.2 = f32[8] fusion(...)"]


def test_breakdown_names_gaps_by_the_overlapping_host_span():
    b = tracing.breakdown(synthetic())
    assert b["device_ops"][0] == ["fusion.1", 2.0]
    gaps = dict((n, t) for n, t in b["idle_gaps"] if t == 1.0)
    # chip 0's gap [3, 4) lies mostly in the idle span, [5, 6) in respond
    assert set(gaps) >= {"idle", "respond"}


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e by ``record_trace.py``: three matrix
    programs in ``drain`` spans, each followed by a 20 ms ``idle`` span,
    then three ``shed_partition`` calls over 4,096 keys."""
    tr = tracing.read_xplane(str(DATA))
    assert list(tr.device_ops) == [0]
    lo, hi = tr.window
    busy = tracing.busy_seconds(tr)
    assert 0 < busy < hi - lo
    gaps = [g for g in tracing.idle_gaps(
        [(s, e) for _, s, e in tr.device_ops[0]], lo, hi) if g[1] - g[0] > 0.015]
    assert len(gaps) >= 3
    # host spans and device events share one clock: each long idle gap
    # lies in an idle span
    assert all(tracing.name_gap(g, tr.host_spans) == "idle" for g in gaps)
    # every device operation ran inside a drain span, to within the
    # 2 ms by which the device's clock and the host's were seen to differ
    drains = [(s - 2e-3, e + 2e-3) for n, s, e in tr.host_spans
              if n == "drain"]
    for _, s, e in tr.device_ops[0]:
        if lo <= s and e <= hi:
            assert any(tracing.overlap((s, e), d) > 0 for d in drains)


def test_recorded_shed_partition_kernel_and_its_roofline():
    from types import SimpleNamespace
    from benchmarks.chip import peaks
    from benchmarks.chip.metrics import shed_partition_roofline as reader
    tr = tracing.read_xplane(str(DATA))
    ev = tracing.kernel_events(tr, reader.EVENT)
    # 4,096 keys are 32 rows of 128 lanes; nothing else matches
    assert [m.group(1) for m, _ in ev] == ["32"] * 3
    assert all(0 < t < 1e-3 for _, t in ev)
    cell = SimpleNamespace(config={"serving": {"trust_db_ways": 4}})
    share = reader.read(SimpleNamespace(
        trace=tr, cell=cell, peaks=peaks.peaks_of("TPU v5 lite")))
    assert 0 < share < 100
