"""The program's spans and counters as the benchmark reads them: the
readers of the scheduler's work counters, the naming of idle gaps by
program span (``program_trace.py``), both recorded chip traces, and a
traced run of the benchmark's loop on the CPU with the spans on."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.chip import peaks, program_trace, tracing
from benchmarks.chip.metrics import (batch_items, eval_row_use, idle_share,
                                     mfu, queue_wait_ms,
                                     shed_partition_roofline,
                                     trust_db_hit_share)
from benchmarks.chip.program_trace import Span, program_phase

HERE = Path(__file__).resolve().parent
DATA = HERE / "data" / "tpu_trace.xplane.pb"
PROGRAM_DATA = HERE / "data" / "tpu_program_trace.xplane.pb"
WAYS4 = SimpleNamespace(config={"serving": {"trust_db_ways": 4}})

# The counters of a program that keeps them, before and after a window.
STATS = ({"n_batches": 10, "n_batched_items": 1000, "n_eval_rows": 4096,
          "n_evaluated": 500, "n_cached": 300, "queue_wait_s": 1.0,
          "n_queue_waits": 40},
         {"n_batches": 20, "n_batched_items": 3000, "n_eval_rows": 12288,
          "n_evaluated": 1500, "n_cached": 1100, "queue_wait_s": 2.5,
          "n_queue_waits": 100})


def test_counter_readers_take_the_window_difference():
    ctx = SimpleNamespace(stats=STATS)
    assert queue_wait_ms.read(ctx) == pytest.approx(1000 * 1.5 / 60)
    assert eval_row_use.read(ctx) == pytest.approx(100 * 1000 / 8192)
    assert trust_db_hit_share.read(ctx) == pytest.approx(100 * 800 / 2000)


def test_counter_readers_are_silent_without_the_counters_or_the_work():
    old = tuple({"n_batches": s["n_batches"],
                 "n_batched_items": s["n_batched_items"]} for s in STATS)
    idle = (STATS[0], STATS[0])
    for reader in (queue_wait_ms, eval_row_use, trust_db_hit_share):
        assert reader.read(SimpleNamespace(stats=old)) is None
        assert reader.read(SimpleNamespace(stats=idle)) is None


def test_existing_readers_read_what_they_read_before():
    """The recorded trace of the earlier benchmark gives the numbers it
    gave before the program had spans, to the last bit."""
    tr = tracing.read_xplane(str(DATA))
    ctx = SimpleNamespace(trace=tr, cell=WAYS4,
                          peaks=peaks.peaks_of("TPU v5 lite"))
    assert idle_share.read(ctx) == 95.13909783967418
    assert shed_partition_roofline.read(ctx) == 8.167753440493223
    gaps = tracing.breakdown(tr)["idle_gaps"]
    assert gaps == [
        ["idle", 0.023037685000000002], ["idle", 0.021877649],
        ["idle", 0.021357886999999992], ["drain", 0.0017038129999999985],
        ["drain", 0.0016768080000000019], ["drain", 0.0016034310000000024],
        ["drain", 1.0480000000034906e-06], ["drain", 1.0459999999906655e-06],
        ["drain", 1.0459999999906655e-06], ["idle", 1.9999999989472883e-09]]
    assert batch_items.read(SimpleNamespace(stats=STATS)) == 200.0
    steps = [SimpleNamespace(tier=[0, 0, 1, 2]), SimpleNamespace(tier=[0])]
    cell = SimpleNamespace(family=SimpleNamespace(
        flops_per_item=lambda cfg: 1e9), config={})
    assert mfu.read(SimpleNamespace(steps=steps, cell=cell, window_s=2.0,
                                    peaks={"flops": 1e12})) == 0.15


def spans() -> list:
    # loop thread 0: a round [1, 5) holding harvest [2, 4.5) and a
    # dispatch [4.5, 5); a poll [6, 7) holding a sync [6, 6.2) and its
    # fold-back [6.2, 7); thread 1 holds a span that is not the loop's
    return [Span("coord.round", 1.0, 5.0), Span("coord.harvest", 2.0, 4.5),
            Span("exec.dispatch", 4.5, 5.0, {"batch": 0}),
            Span("exec.poll", 6.0, 7.0),
            Span("exec.sync", 6.0, 6.2, {"batch": 0}),
            Span("exec.foldback", 6.2, 7.0, {"batch": 0}),
            Span("exec.poll", 0.0, 8.0, thread=1)]


def test_a_gap_is_named_by_the_span_whose_own_time_covers_most_of_it():
    sp = spans()[:6]
    # [1.5, 4.6): the round's own time covers 0.5, harvest 2.5, dispatch 0.1
    assert program_phase((1.5, 4.6), sp) == "coord.harvest"
    assert program_phase((1.0, 2.5), sp) == "coord.round"
    assert program_phase((6.0, 6.15), sp) == "exec.sync"
    assert program_phase((5.2, 5.8), sp) == ""
    assert program_trace.self_overlap((1.0, 5.0), sp[0], sp) == \
        pytest.approx(1.0)


def test_named_gaps_keep_the_breakdowns_gaps_and_lengths():
    tr = tracing.Trace(
        device_ops={0: [("a", 0.0, 1.5), ("b", 4.8, 5.5), ("c", 5.8, 8.0)]},
        host_spans=[("drain", 1.0, 5.0), ("idle", 5.0, 6.0)],
        window=(0.0, 8.0))
    named = program_trace.name_gaps(tr, spans()[:6])
    plain = tracing.breakdown(tr)["idle_gaps"]
    assert [t for _, t in named] == [t for _, t in plain]
    assert [n for n, _ in named] == ["drain/coord.harvest", "idle"]
    assert [n for n, _ in plain] == ["drain", "idle"]


def test_host_time_per_batch_and_self_time_on_the_loops_thread():
    sp = spans()
    assert program_trace.loop_thread(sp) == 0
    # union on thread 0 inside [0, 8): [1, 5) and [6, 7), one dispatch
    assert program_trace.host_ms_per_batch(sp, (0.0, 8.0)) == \
        pytest.approx(5000.0)
    assert program_trace.host_ms_per_batch(sp, (5.5, 8.0)) is None
    own = program_trace.self_ms(sp, (0.0, 8.0))
    assert own == pytest.approx({
        "coord.round": 1000.0, "coord.harvest": 2500.0,
        "exec.dispatch": 500.0, "exec.poll": 0.0, "exec.sync": 200.0,
        "exec.foldback": 800.0})


def test_recorded_program_trace():
    """A trace of the benchmark's loop over a two-layer DLRM with the
    deployment's 4,096-candidate batch and 2^20 x 4 Trust DB, recorded
    on a TPU v5e with the program's spans on by
    ``record_program_trace.py``."""
    path = str(PROGRAM_DATA)
    tr = tracing.read_xplane(path)
    sp = program_trace.read_program_spans(path)
    names = {s.name for s in sp}
    assert names >= {"coord.enqueue", "coord.round", "exec.poll",
                     "coord.steal", "coord.hedge", "coord.fanout",
                     "coord.harvest", "coord.collect", "sched.form",
                     "exec.stage", "exec.dispatch", "exec.sync",
                     "exec.foldback"}
    # a batch's spans are joined by its number
    by = {n: {s.args["batch"] for s in sp if s.name == n}
          for n in ("exec.stage", "exec.dispatch", "exec.sync",
                    "exec.foldback")}
    assert by["exec.stage"] == by["exec.dispatch"]
    assert by["exec.sync"] & by["exec.stage"]
    # the program's spans run inside the benchmark's: each gap the
    # program overlaps inside a drain span gains the program's name
    named = program_trace.name_gaps(tr, sp, top=20)
    plain = tracing.breakdown(tr, top=20)["idle_gaps"]
    assert [t for _, t in named] == [t for _, t in plain]
    assert any(n.startswith("drain/") for n, _ in named)
    assert all(n.split("/")[0] == p for (n, _), (p, _) in zip(named, plain))
    assert program_trace.host_ms_per_batch(sp, tr.window) > 0
    # the kernel is named now, and its reader still finds its events
    ev = tracing.kernel_events(tr, shed_partition_roofline.EVENT)
    assert ev and all(m.group(1) == "32" for m, _ in ev)
    assert all("shed_partition" in m.string for m, _ in ev)
    share = shed_partition_roofline.read(SimpleNamespace(
        trace=tr, cell=WAYS4, peaks=peaks.peaks_of("TPU v5 lite")))
    assert 0 < share < 100


def test_traced_cpu_run_reads_the_program(monkeypatch):
    """A traced run of the benchmark's loop on the CPU, at test sizes,
    with the program's spans on: the spans are read from the trace and
    the counter readers find their counters."""
    from benchmarks.chip.spec import Cell
    v5e = peaks.peaks_of("TPU v5 lite")
    monkeypatch.setattr(peaks, "peaks_of", lambda kind: v5e)
    cfg = json.loads((HERE / "tiny-dlrm.json").read_text())
    mix = json.loads((HERE / "tiny-mix.json").read_text())
    per_layer = [{"name": f"{s}.x", "unit": "u"} for s in
                 ("queue_wait_ms", "eval_row_use", "trust_db_hit_share")]
    cell = Cell(name="tiny-dlrm", chips=1, config=cfg, mix=mix,
                rate_qps=40.0, end_to_end=[], per_layer=per_layer)
    res, tr, sp = program_trace.traced_run(cell, 2**33 + 1, 1.0)
    assert res.correct, res.checks
    assert {"coord.round", "exec.dispatch", "exec.sync"} <= \
        {s.name for s in sp}
    assert program_trace.host_ms_per_batch(sp, tr.window) > 0
    assert res.metrics["queue_wait_ms.x"]["value"] >= 0
    assert 0 < res.metrics["eval_row_use.x"]["value"] <= 100
    assert 0 < res.metrics["trust_db_hit_share.x"]["value"] < 100
    from repro import obs
    assert not obs.span("coord.round")         # off again
