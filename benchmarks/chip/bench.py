"""One run of one cell: boot, warm up, measure, check.

The system under test is the program's serving stack as
``launch/serve.py`` boots it: a ``ClusterCoordinator`` with one replica,
the fused drain (one jitted device step per micro-batch) and a depth-2
in-flight window, driven by the loop ``serve`` uses (enqueue, then
``drain(1)``). Two things differ from the launcher: the evaluator and
its weights come from the cell's configuration file, and nothing is
calibrated on the host clock; the Load Monitor follows the measured
rate from the warm-up traffic on.

The Trust DB is filled before the warm-up as a long-running deployment
would hold it. The traffic is an open loop: each request is made and
enqueued when it is due, and its response time runs from that due time
to the moment its response lands.
"""
from __future__ import annotations

import contextlib
import gc
import logging
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from benchmarks.chip import check, tracing
from benchmarks.chip.spec import Cell, reader
from benchmarks.chip.traffic import (Request, Schedule, TrustDBFill,
                                     seed_words, trust_db_fill)

PHASE_WARM, PHASE_WINDOW, PHASE_SHAPES = 0, 1, 2
# How long after the window closes the run waits for answers still due.
LATE_ANSWER_WAIT_S = 60.0
# A traced run measures at most this long: its per-layer numbers need a
# steady stretch, not a tail's hundreds of requests, and the trace of a
# window of DLRM steps holds about 40,000 device events a second, each
# collected, written and read back before the run may end.
TRACE_WINDOW_S = 10.0


@dataclass
class StepRecord:
    """What one fused step was given and answered (device arrays until
    the run is checked)."""
    t: float
    keys: object
    valid: object
    u_capacity: int
    budget_total: int
    max_evals: int
    trust: object
    tier: object


def record_steps(shedder, log: List[StepRecord]) -> None:
    """Keep every fused step's inputs and answers, in dispatch order."""
    step = shedder._step

    def recorded(cache, prior, params, keys, buckets, valid, feats, ucap,
                 uthr, budget, *, max_evals):
        out = step(cache, prior, params, keys, buckets, valid, feats, ucap,
                   uthr, budget, max_evals=max_evals)
        log.append(StepRecord(time.monotonic(), keys, valid, int(ucap),
                              int(budget), int(max_evals), out[0], out[1]))
        return out

    shedder._step = recorded


def boot(cfg: Dict, evaluator):
    """The serving stack of ``launch/serve.py`` with one replica."""
    from repro.cluster import ClusterConfig, ClusterCoordinator
    from repro.configs.base import TrustIRConfig

    s = cfg["serving"]
    tcfg = TrustIRConfig(
        u_capacity=s["u_capacity"], u_threshold=s["u_threshold"],
        deadline_s=s["deadline_s"],
        overload_deadline_s=s["overload_deadline_s"],
        chunk_size=s["chunk_size"], n_replicas=1,
        pipeline_depth=s["pipeline_depth"],
        cache_slots=s["trust_db_slots"], cache_ways=s["trust_db_ways"],
        trust_scale=s["trust_scale"])

    def evaluate(chunk):                  # host chunk-loop protocol
        import jax.numpy as jnp
        return np.asarray(evaluator({k: jnp.asarray(v)
                                     for k, v in chunk.items()}))

    return ClusterCoordinator(
        tcfg, evaluate,
        cluster_cfg=ClusterConfig(hedge_after_s=0.0, autoscale=False),
        drain_mode="fused", evaluate_batch=evaluator)


@dataclass
class Sent:
    due: float
    t_enqueue: float
    urls: np.ndarray


class OpenLoop:
    """Sends requests when they are due and collects their answers.

    It never waits on the device while a request is due: it folds back
    the steps that have landed (``poll``), forms a batch only where a
    replica's in-flight window has room, and otherwise sleeps until the
    next request is due or ``POLL_S`` has passed. Answers are read from
    each replica's response log as they land (one replica, no hedging,
    so each request has one answer there)."""

    POLL_S = 0.001

    def __init__(self, coord, cell: Cell, spans: bool):
        self.coord = coord
        self.cell = cell
        self.spans = spans
        self.sent: Dict[int, Sent] = {}
        self.answers: Dict[int, list] = defaultdict(list)
        self._seen = [0] * len(coord.replicas)

    def collect(self) -> None:
        now = time.monotonic()
        for i, rep in enumerate(self.coord.replicas):
            done = rep.engine.completed
            while self._seen[i] < len(done):
                r = done[self._seen[i]]
                self._seen[i] += 1
                self.answers[r.request_id].append((now, r))

    def in_flight(self) -> bool:
        return any(rep.scheduler.executor.in_flight
                   for rep in self.coord.replicas)

    def advance(self) -> bool:
        """Fold back landed steps and dispatch one batch where no window
        is full; never blocks on a step. Returns whether it dispatched."""
        reps = self.coord.replicas
        for rep in reps:
            rep.engine.poll()
        room = all(rep.queued_items == 0 or rep.scheduler.executor.in_flight
                   < max(rep.scheduler.executor.effective_depth, 1)
                   for rep in reps)
        if not (room and self.coord.queued_items > 0):
            return False
        before = sum(rep.scheduler.executor.n_submitted for rep in reps)
        self.coord.drain(1)
        return sum(rep.scheduler.executor.n_submitted
                   for rep in reps) > before

    def send(self, sched: Schedule, req: Request, due: float) -> int:
        from repro.scheduling import Priority
        with tracing.span("generate", self.spans):
            urls = sched.urls(req)
            feats = self.cell.family.features(self.cell.config, urls)
            buckets = (urls % np.uint32(64)).astype(np.int32)
        with tracing.span("enqueue", self.spans):
            t = time.monotonic()
            rid = self.coord.enqueue(urls, buckets, feats,
                                     slo_s=self.cell.mix["slo_s"],
                                     priority=Priority(req.priority),
                                     tenant=req.tenant, t_arrival=due)
        self.sent[rid] = Sent(due, t, urls)
        return rid

    def _turn(self, nxt: float) -> None:
        """One turn of the loop: advance, collect, and sleep until
        ``nxt`` (the next due time) when nothing was dispatched."""
        with tracing.span("drain", self.spans):
            dispatched = self.advance()
        with tracing.span("respond", self.spans):
            self.collect()
        if not dispatched:
            wait = nxt - time.monotonic()
            if self.in_flight():
                wait = min(wait, self.POLL_S)
            with tracing.span("idle", self.spans):
                time.sleep(max(0.0, wait))

    def run(self, sched: Schedule, t0: float, t_end: float) -> List[int]:
        """Offer ``sched`` from ``t0`` until ``t_end``; requests the loop
        could not send by then are sent at once. Returns their ids."""
        reqs, i, rids = sched.requests, 0, []
        while time.monotonic() < t_end:
            now = time.monotonic()
            while i < len(reqs) and t0 + reqs[i].due_s <= now:
                rids.append(self.send(sched, reqs[i], t0 + reqs[i].due_s))
                i += 1
            nxt = t0 + reqs[i].due_s if i < len(reqs) else t_end
            self._turn(min(nxt, t_end))
        for req in reqs[i:]:
            rids.append(self.send(sched, req, t0 + req.due_s))
        return rids

    def finish(self, rids: List[int], deadline: float) -> None:
        """Serve until every request in ``rids`` is answered."""
        while any(r not in self.answers for r in rids) \
                and time.monotonic() < deadline:
            self._turn(time.monotonic() + self.POLL_S)
        self.collect()


class CompileCounter(logging.Handler):
    """Counts JAX's "Compiling ..." log records while attached."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.n = 0

    def emit(self, record):
        if record.getMessage().startswith("Compiling"):
            self.n += 1


@dataclass
class RunResult:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, Dict]
    device: Dict
    checks: Dict[str, Dict]
    notes: Dict[str, float] = field(default_factory=dict)
    breakdown: Optional[Dict] = None


def _warm_shapes(loop: OpenLoop, cell: Cell, seed: int) -> None:
    """One request per padded batch shape the traffic can form, so every
    program the window runs is compiled before it."""
    cap = loop.coord.max_batch_items
    top = cell.mix["size"]["max"]
    sched = Schedule(cell.mix, 1.0, 1.0, seed, PHASE_SHAPES)
    rids = []
    for m in range(1, -(-top // cap) + 1):
        req = Request(index=m, due_s=0.0, size=min(m * cap, top),
                      priority=0, tenant="warmup")
        rids.append(loop.send(sched, req, time.monotonic()))
        loop.coord.drain()
    loop.finish(rids, time.monotonic() + LATE_ANSWER_WAIT_S)


@dataclass
class Stack:
    """A booted, warm serving stack and what the run keeps of it."""
    coord: object
    loop: OpenLoop
    log: List[StepRecord]
    weights: object
    fill: TrustDBFill


def fill_trust_db(coord, cell: Cell, seed: int) -> TrustDBFill:
    """Put the Trust DB of a long-running deployment in place of each
    replica's empty one (``traffic.trust_db_fill``)."""
    import jax.numpy as jnp

    s = cell.config["serving"]
    fill = trust_db_fill(cell.mix, s["trust_db_slots"], s["trust_db_ways"],
                         s["trust_scale"], seed)
    for rep in coord.replicas:
        sh = rep.engine.shedder
        assert sh.cache["keys"].shape == fill.keys.shape, \
            (sh.cache["keys"].shape, fill.keys.shape)
        sh.cache = {"keys": jnp.asarray(fill.keys),
                    "values": jnp.asarray(fill.values),
                    "age": jnp.asarray(fill.age),
                    "clock": jnp.zeros((), jnp.int32)}
    return fill


def set_up(cell: Cell, seed: int, spans: bool) -> Stack:
    """Weights, the serving stack with its Trust DB filled, and its
    warm-up: every padded batch shape once, then ``warmup_s`` of the
    cell's own traffic so the Load Monitor follows the measured rate.
    The queues are empty after it."""
    import jax

    cfg = cell.config
    key = jax.random.PRNGKey(
        int(seed_words(seed, 7).generate_state(1)[0]))
    weights = cell.family.make_weights(cfg, key)
    coord = boot(cfg, cell.family.make_evaluator(cfg, weights))
    fill = fill_trust_db(coord, cell, seed)
    log: List[StepRecord] = []
    for rep in coord.replicas:
        record_steps(rep.engine.shedder, log)
    loop = OpenLoop(coord, cell, spans)
    _warm_shapes(loop, cell, seed)
    warm = Schedule(cell.mix, cell.rate_qps, cell.mix["warmup_s"], seed,
                    PHASE_WARM)
    t0 = time.monotonic()
    rids = loop.run(warm, t0, t0 + cell.mix["warmup_s"])
    loop.finish(rids, time.monotonic() + LATE_ANSWER_WAIT_S)
    # What set-up made lives for the whole run: keep it out of the
    # collector's full passes, each of which would otherwise rescan it
    # and hold the loop for up to 0.16 s (three passes in a DLRM window
    # on a TPU v5e host).
    gc.collect()
    gc.freeze()
    return Stack(coord, loop, log, weights, fill)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process_start: float) -> RunResult:
    import jax

    dev = jax.devices()[0]
    if trace:
        seconds = min(seconds, TRACE_WINDOW_S)
    st = set_up(cell, seed, spans=trace)
    coord, loop, log = st.coord, st.loop, st.log
    sched = Schedule(cell.mix, cell.rate_qps, seconds, seed, PHASE_WINDOW)
    stats0 = coord.scheduler_stats()
    n_log0 = len(log)
    counter = CompileCounter()
    jax_log = logging.getLogger("jax")
    jax_log.addHandler(counter)
    jax.config.update("jax_log_compiles", True)
    with contextlib.ExitStack() as stack:
        stack.callback(jax.config.update, "jax_log_compiles", False)
        stack.callback(jax_log.removeHandler, counter)
        got_trace = (stack.enter_context(tracing.profiled()) if trace
                     else [])
        t0 = time.monotonic()
        setup_s = t0 - t_process_start
        with tracing.span("window", trace):
            rids = loop.run(sched, t0, t0 + seconds)
        t_close = time.monotonic()
    trace_read_s = time.monotonic() - t_close      # profiler stop and read
    stats1 = coord.scheduler_stats()
    window_log = log[n_log0:]
    n_window_steps = sum(1 for r in window_log if r.t < t_close)
    loop.finish(rids, t_close + LATE_ANSWER_WAIT_S)
    gc.unfreeze()
    jax.block_until_ready([r.trust for r in log])
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    answers = {rid: loop.answers.get(rid, []) for rid in rids}
    sent = {rid: loop.sent[rid] for rid in rids}
    responses = check.Responses(answers, sent, t0, t_close, seconds)
    metrics = {}
    if not trace:
        values = responses.end_to_end(setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        from benchmarks.chip import peaks
        ctx = MetricContext(cell=cell, trace=got_trace[0], window_s=seconds,
                            steps=window_log[:n_window_steps],
                            stats=(stats0, stats1),
                            peaks=peaks.peaks_of(dev.device_kind))
        for m in cell.per_layer:
            v = reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # The program's state is freed before the reference runs on the chip.
    weights, fill = st.weights, st.fill
    all_answers, all_sent = dict(loop.answers), loop.sent
    del coord, loop, st
    t_check = time.monotonic()
    checks, failed_rids = check.run_checks(cell, weights, log, fill,
                                           responses, all_answers, all_sent,
                                           seed)
    check_s = time.monotonic() - t_check
    n_failed = len(failed_rids | responses.failed_rids())
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    breakdown = None
    if trace:
        tr = got_trace[0]
        device["busy_s"] = tracing.busy_seconds(tr)
        device["window_s"] = tr.window[1] - tr.window[0]
        breakdown = tracing.breakdown(tr)
    window_ucap = [r.u_capacity for r in window_log[:n_window_steps]]
    notes = {"setup_s": setup_s, "window_compiles": counter.n,
             "generator_lag_max_s": responses.lag_max(),
             "window_steps": n_window_steps,
             "u_capacity_median": float(np.median(window_ucap or [0])),
             "trace_read_s": trace_read_s, "check_s": check_s,
             **responses.tier_shares()}
    return RunResult(correct, len(rids), n_failed, metrics, device, checks,
                     notes, breakdown)


@dataclass
class MetricContext:
    """What a per-layer metric reader may read."""
    cell: Cell
    trace: Optional[tracing.Trace]
    window_s: float
    steps: List[StepRecord]           # fused steps dispatched in the window
    stats: tuple                      # scheduler_stats() before and after
    peaks: Dict[str, float]
