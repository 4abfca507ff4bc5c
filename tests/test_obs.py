"""The program's spans (``repro.obs``) and the scheduler's work counters
on the serving path: off by default at no cost, on the profiler's clock
when on, nested by layer, and a batch's spans joined by its sequence
number; counters equal to the sums over the run's shed results and
queue delays, across a replica restart."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.cluster import ClusterConfig, ClusterCoordinator
from repro.configs.base import TrustIRConfig
from repro.core.fused_shedder import MAX_SLICES
from repro.scheduling import Priority

D = 8
W = np.linspace(-1.0, 1.0, D).astype(np.float32)

# Every span of the serving path the benchmark drives.
SPANS = {"coord.enqueue", "coord.round", "exec.poll", "coord.steal",
         "coord.hedge", "coord.fanout", "coord.harvest", "coord.gossip",
         "coord.collect", "sched.form", "exec.stage", "exec.dispatch",
         "exec.sync", "exec.foldback"}


@jax.jit
def _ev(chunk):
    return jax.nn.sigmoid(chunk["x"] @ jnp.asarray(W)) * 5.0


def _ev_np(chunk):
    return np.asarray(_ev({"x": jnp.asarray(chunk["x"])}))


def _coord(drain_mode="fused", n_replicas=1, gossip=False):
    cfg = TrustIRConfig(u_capacity=96, u_threshold=96, deadline_s=0.5,
                        overload_deadline_s=1.0, chunk_size=16,
                        cache_slots=1024, cache_ways=2, pipeline_depth=2,
                        n_replicas=n_replicas)
    return ClusterCoordinator(
        cfg, _ev_np, cluster_cfg=ClusterConfig(gossip=gossip),
        drain_mode=drain_mode, evaluate_batch=_ev)


def _request(i, n):
    r = np.random.default_rng(i)
    # Keys repeat across requests so the Trust DB answers some.
    keys = r.integers(1, 400, n).astype(np.uint32)
    feats = {"x": r.normal(size=(n, D)).astype(np.float32)}
    return keys, (keys % 4).astype(np.int32), feats


def _serve(coord, sizes, first=0):
    """The benchmark's loop in miniature: enqueue, fold back what has
    landed, drain one batch; then drain the rest."""
    for i, n in enumerate(sizes, start=first):
        keys, buckets, feats = _request(i, n)
        coord.enqueue(keys, buckets, feats, priority=Priority.HIGH,
                      tenant=f"t{i % 3}")
        for rep in coord.replicas:
            rep.engine.poll()
        coord.drain(1)
    coord.drain()


@pytest.fixture
def spans_on():
    obs.enable(True)
    try:
        yield
    finally:
        obs.enable(False)


def test_spans_off_build_no_annotation(monkeypatch):
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a[0])
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    assert not obs.span("coord.round")         # off by default
    coord = _coord()
    _serve(coord, [40, 70, 30])
    assert made == []
    obs.enable(True)
    try:
        _serve(coord, [40], first=3)
    finally:
        obs.enable(False)
    assert {"repro.coord.enqueue", "repro.exec.sync"} <= set(made)


def _program_spans(tmp_path):
    """(name, start, end, args, line) of every ``repro.`` event."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(obs.PREFIX):
                    out.append((ev.name[len(obs.PREFIX):], ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), li))
    return out


def _parent(span, spans):
    """The innermost span on the same thread that contains ``span``."""
    name, s, e, _, line = span
    outer = [x for x in spans if x is not span and x[4] == line
             and x[1] <= s and e <= x[2]]
    return min(outer, key=lambda x: x[2] - x[1])[0] if outer else None


def test_spans_on_record_the_serving_path_nested(tmp_path, spans_on):
    coord = _coord(gossip=True)
    _serve(coord, [40])                     # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(coord, [40, 70, 130, 30, 90, 60], first=1)
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(tmp_path)
    assert {n for n, *_ in spans} >= SPANS
    allowed = {
        "coord.enqueue": {None},
        "coord.round": {None},
        "coord.steal": {"coord.round"}, "coord.hedge": {"coord.round"},
        "coord.fanout": {"coord.round"}, "coord.harvest": {"coord.round"},
        "coord.gossip": {"coord.round"},
        "coord.collect": {"coord.round", "coord.enqueue"},
        "exec.poll": {None, "coord.round"},
        "sched.form": {"coord.round"},
        "exec.stage": {"coord.round"}, "exec.dispatch": {"coord.round"},
        "exec.foldback": {"exec.poll", "exec.dispatch", "coord.round"},
        "exec.sync": {"exec.foldback"},
    }
    for sp in spans:
        assert _parent(sp, spans) in allowed[sp[0]], sp
    args = {}
    for name, _, _, a, _ in spans:
        args.setdefault(name, []).append(a)
    assert all({"rid", "items"} <= set(a) for a in args["coord.enqueue"])
    assert all("keys" in a for a in args["coord.harvest"])
    assert all(a["n_valid"] > 0 for a in args["sched.form"]
               if "n_valid" in a)
    assert all(a["ready"] in (0, 1) for a in args["exec.sync"])
    assert all(a["new_shape"] in (0, 1) for a in args["exec.dispatch"])
    # The batch number joins a batch's four spans across loop turns.
    batches = {n: sorted(a["batch"] for a in args[n])
               for n in ("exec.stage", "exec.dispatch", "exec.sync",
                         "exec.foldback")}
    assert len(set(map(tuple, batches.values()))) == 1, batches
    assert len(batches["exec.stage"]) == len(set(batches["exec.stage"]))
    assert all(a["rows"] > 0 for a in args["exec.stage"])


def _record_sheds(coord, log):
    for rep in coord.replicas:
        ex = rep.scheduler.executor
        fin = ex._finalize

        def finalize(batch, shed, _fin=fin):
            log.append(shed)
            return _fin(batch, shed)
        ex._finalize = finalize


@pytest.mark.parametrize("drain_mode", ["fused", "host"])
def test_counters_are_the_sums_of_the_run(drain_mode):
    coord = _coord(drain_mode, n_replicas=2)
    sheds = []
    _record_sheds(coord, sheds)
    _serve(coord, [40, 70, 130, 30, 90, 60, 200, 20])
    s = coord.scheduler_stats()
    assert s["n_batches"] == len(sheds) > 0
    assert s["n_eval_rows"] == sum(r.n_eval_rows for r in sheds)
    assert s["n_evaluated"] == sum(r.n_evaluated for r in sheds) > 0
    assert s["n_cached"] == sum(r.n_cached for r in sheds) > 0
    assert s["n_eval_rows"] >= s["n_evaluated"]
    cs = coord.cfg.chunk_size
    assert all(r.n_eval_rows % cs == 0 for r in sheds)
    if drain_mode == "fused":    # the evaluated slices, not the batch
        cfg = coord.cfg
        assert cfg.u_capacity + cfg.u_threshold <= MAX_SLICES * cs
        assert all(r.n_eval_rows == -(-r.n_evaluated // cs) * cs
                   <= len(r.tier) for r in sheds)
        assert any(r.n_eval_rows < len(r.tier) for r in sheds)
    admitted = [r for r in coord.completed if r.admitted]
    assert s["n_queue_waits"] == len(admitted)
    assert s["queue_wait_s"] == pytest.approx(
        sum(r.queue_delay_s for r in admitted))

    # A restart rebuilds the replicas' schedulers from zero; the fleet
    # aggregate keeps what they counted, then adds what follows.
    before = {k: s[k] for k in coord._SCHED_SUM_KEYS}
    coord.rolling_restart()
    after = coord.scheduler_stats()
    assert {k: after[k] for k in before} == pytest.approx(before)
    _record_sheds(coord, sheds)
    _serve(coord, [50, 80], first=20)
    s = coord.scheduler_stats()
    assert s["n_evaluated"] == sum(r.n_evaluated for r in sheds)
    assert s["n_eval_rows"] == sum(r.n_eval_rows for r in sheds)
    admitted = [r for r in coord.completed if r.admitted]
    assert s["n_queue_waits"] == len(admitted)
    assert s["queue_wait_s"] == pytest.approx(
        sum(r.queue_delay_s for r in admitted))


def test_rescued_batch_counts_its_queue_waits():
    coord = _coord("host")
    rep = coord.replicas[0]

    def boom(*a, **kw):
        raise RuntimeError("evaluator down")

    rep.engine.shedder.process = boom
    for i in range(3):
        keys, buckets, feats = _request(i, 30)
        coord.enqueue(keys, buckets, feats, priority=Priority.HIGH)
    coord.drain()
    s = coord.scheduler_stats()
    assert s["n_executor_errors"] >= 1
    assert s["n_evaluated"] == s["n_eval_rows"] == 0
    assert s["n_queue_waits"] == 3
    assert s["queue_wait_s"] == pytest.approx(
        sum(r.queue_delay_s for r in coord.completed))



def _record_xfer_steps(coord, steps):
    """Each batch's ``xfer_s`` beside what it added to its replica's
    counter."""
    for rep in coord.replicas:
        fin = rep.scheduler.executor._finalize

        def finalize(batch, shed, _fin=fin, _stats=rep.scheduler.stats):
            before = _stats.xfer_s
            out = _fin(batch, shed)
            steps.append((shed.xfer_s, _stats.xfer_s - before))
            return out
        rep.scheduler.executor._finalize = finalize


@pytest.mark.parametrize("drain_mode", ["fused", "host"])
def test_transfer_seconds_grow_with_every_fused_batch(drain_mode):
    """``xfer_s``: every fused batch adds its upload and readback time,
    the host chunk loop adds none; the fleet aggregate sums the
    replicas' and keeps them across a restart."""
    coord = _coord(drain_mode, n_replicas=2)
    steps = []
    _record_xfer_steps(coord, steps)
    _serve(coord, [40, 70, 130, 30, 90, 60, 200, 20])
    s = coord.scheduler_stats()
    assert len(steps) == s["n_batches"] > 0
    for own, added in steps:
        assert added == pytest.approx(own)
        assert (own > 0) == (drain_mode == "fused")
    assert s["xfer_s"] == pytest.approx(sum(own for own, _ in steps))

    before = s["xfer_s"]
    coord.rolling_restart()
    assert coord.scheduler_stats()["xfer_s"] == pytest.approx(before)
    _record_xfer_steps(coord, steps)
    _serve(coord, [50, 80], first=20)
    s = coord.scheduler_stats()
    assert s["xfer_s"] == pytest.approx(sum(own for own, _ in steps))
    assert (s["xfer_s"] > before) == (drain_mode == "fused")
