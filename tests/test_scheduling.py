"""Priority-aware admission & scheduling subsystem (repro.scheduling):
EDF ordering, token buckets, static micro-batch shapes, the no-drop
invariant under all three regimes, hedging, and the multi-tenant
simulator driver."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.trust_ir import smoke_config
from repro.core import Regime, SimClock, TIER_INVALID, TIER_PRIOR
from repro.core.regimes import classify
from repro.scheduling import (AdmissionPolicy, MicroBatcher, Priority,
                              PriorityQueueBank, QueuedRequest,
                              REASON_RATE_LIMITED,
                              REASON_SHED_LOW_VERY_HEAVY, Request,
                              SchedulerConfig, TenantRateLimiter,
                              TokenBucket, to_fused_inputs)
from repro.serving.engine import ServingEngine


def _mkreq(rid, n, arrival=0.0, slo=10.0, seed=0):
    r = np.random.default_rng(seed + rid)
    return Request(rid, np.arange(rid * 10_000 + 1,
                                  rid * 10_000 + n + 1, dtype=np.uint32),
                   r.integers(0, 8, n).astype(np.int32),
                   {"x": np.linspace(0, 5, n, dtype=np.float32)},
                   arrival_s=arrival, slo_s=slo)


def _mkq(rid, n, priority=Priority.NORMAL, deadline=10.0,
         enqueue=0.0, tenant="t"):
    return QueuedRequest(request=_mkreq(rid, n), priority=priority,
                         tenant=tenant, deadline_t=deadline,
                         enqueue_t=enqueue)


def _sim_engine(cfg=None, rate_scale=1.0, evaluate=None, **sched_kw):
    cfg = cfg or smoke_config()
    clock = SimClock(rate_items_per_s=rate_scale * cfg.u_capacity
                     / cfg.deadline_s)
    eng = ServingEngine(cfg, evaluate or (lambda ch: np.asarray(ch["x"])),
                        sim_clock=clock,
                        sched_cfg=SchedulerConfig(**sched_kw))
    return eng, clock


# ---------------------------------------------------------------------------
# queues: EDF ordering + strict priority + backpressure
# ---------------------------------------------------------------------------

def test_edf_pops_earliest_deadline_first():
    bank = PriorityQueueBank(capacity_per_class=16)
    deadlines = [5.0, 1.0, 3.0, 0.5, 2.0]
    for i, d in enumerate(deadlines):
        assert bank.push(_mkq(i, n=4, deadline=d))
    popped = [bank.pop_next().deadline_t for _ in deadlines]
    assert popped == sorted(deadlines)


def test_strict_priority_across_classes_edf_within():
    bank = PriorityQueueBank(capacity_per_class=16)
    bank.push(_mkq(0, 4, Priority.LOW, deadline=0.1))
    bank.push(_mkq(1, 4, Priority.NORMAL, deadline=9.0))
    bank.push(_mkq(2, 4, Priority.NORMAL, deadline=1.0))
    bank.push(_mkq(3, 4, Priority.CRITICAL, deadline=99.0))
    order = [(bank.pop_next().priority, ) for _ in range(4)]
    assert [p for (p,) in order] == [Priority.CRITICAL, Priority.NORMAL,
                                     Priority.NORMAL, Priority.LOW]


def test_queue_backpressure_static_capacity():
    bank = PriorityQueueBank(capacity_per_class=2)
    assert bank.push(_mkq(0, 4))
    assert bank.push(_mkq(1, 4))
    assert not bank.push(_mkq(2, 4))          # full -> explicit refusal
    assert bank.push(_mkq(3, 4, Priority.HIGH))   # other class unaffected
    assert bank.n_items == 12


# ---------------------------------------------------------------------------
# ratelimit: refill + tenant isolation
# ---------------------------------------------------------------------------

def test_token_bucket_refill():
    b = TokenBucket(rate=10.0, burst=20.0)
    assert b.try_acquire(20, now=0.0)          # starts full
    assert not b.try_acquire(1, now=0.0)       # empty
    assert b.try_acquire(10, now=1.0)          # +10 after 1s
    assert not b.try_acquire(1, now=1.0)
    assert b.available(now=100.0) == pytest.approx(20.0)   # capped


def test_tenant_isolation_and_default_unlimited():
    lim = TenantRateLimiter()                  # inf defaults: no limiting
    assert lim.allow("anyone", 10 ** 9, now=0.0)
    lim.configure("noisy", rate=10.0, burst=10.0)
    assert lim.allow("noisy", 10, now=0.0)
    assert not lim.allow("noisy", 1, now=0.0)  # noisy exhausted
    assert lim.allow("quiet", 10 ** 6, now=0.0)   # others unaffected


# ---------------------------------------------------------------------------
# priorities: per-regime admission ladder
# ---------------------------------------------------------------------------

def test_admission_ladder_rules():
    pol = AdmissionPolicy(low_watermark=0.5, normal_watermark=0.9)
    # CRITICAL always admitted
    for reg in Regime:
        assert pol.decide(Priority.CRITICAL, reg, 1.0) is None
    # NORMAL regime admits all classes
    assert pol.decide(Priority.LOW, Regime.NORMAL, 0.9) is None
    # HEAVY throttles LOW above the watermark only
    assert pol.decide(Priority.LOW, Regime.HEAVY, 0.4) is None
    assert pol.decide(Priority.LOW, Regime.HEAVY, 0.6) is not None
    # VERY_HEAVY rejects LOW outright, throttles NORMAL above watermark
    assert pol.decide(Priority.LOW, Regime.VERY_HEAVY, 0.0) \
        == REASON_SHED_LOW_VERY_HEAVY
    assert pol.decide(Priority.NORMAL, Regime.VERY_HEAVY, 0.95) \
        is not None
    assert pol.decide(Priority.HIGH, Regime.VERY_HEAVY, 1.0) is None


# ---------------------------------------------------------------------------
# batcher: static padded shapes across drains
# ---------------------------------------------------------------------------

def test_micro_batch_shapes_static_across_drains():
    batcher = MicroBatcher(capacity_items=128)
    shapes = []
    for sizes in [(30, 40, 50), (5,), (128,), (7, 7, 7, 7)]:
        bank = PriorityQueueBank(64)
        for i, n in enumerate(sizes):
            bank.push(_mkq(i, n))
        batch = batcher.form(bank)
        shapes.append((batch.item_keys.shape, batch.buckets.shape,
                       batch.valid.shape, batch.segments.shape,
                       batch.features["x"].shape))
        assert batch.n_valid == sum(sizes)
        # valid prefix, invalid suffix; segments map rows to slices
        assert batch.valid[:batch.n_valid].all()
        assert not batch.valid[batch.n_valid:].any()
        assert (batch.segments[batch.n_valid:] == -1).all()
        for si, (q, s, ln) in enumerate(batch.slices):
            assert (batch.segments[s:s + ln] == si).all()
            np.testing.assert_array_equal(
                batch.item_keys[s:s + ln], q.request.item_keys)
    assert len(set(shapes)) == 1          # identical across drains


def test_micro_batch_jumbo_pads_to_capacity_multiple():
    batcher = MicroBatcher(capacity_items=64)
    bank = PriorityQueueBank(8)
    bank.push(_mkq(0, 150))                   # > capacity
    batch = batcher.form(bank)
    assert batch.capacity == 192              # next multiple of 64
    assert batch.n_valid == 150


def test_micro_batch_stops_at_first_nonfitting_head():
    batcher = MicroBatcher(capacity_items=100)
    bank = PriorityQueueBank(8)
    bank.push(_mkq(0, 60, deadline=1.0))
    bank.push(_mkq(1, 60, deadline=2.0))      # does not fit after #0
    bank.push(_mkq(2, 30, deadline=3.0))      # would fit, but after #1
    batch = batcher.form(bank)
    assert [q.request.request_id for q, _, _ in batch.slices] == [0]
    assert len(bank) == 2                     # order preserved


def test_micro_batch_feeds_fused_shed_eval():
    import jax.numpy as jnp
    from repro.core import average_trust as AT
    from repro.core import trust_cache as TC
    from repro.core.shedder import fused_shed_eval

    cfg = smoke_config()
    batcher = MicroBatcher(capacity_items=64)
    bank = PriorityQueueBank(8)
    for i, n in enumerate((20, 30)):
        bank.push(_mkq(i, n))
    batch = batcher.form(bank)
    keys, buckets, valid, feats = to_fused_inputs(batch)
    trust, aux = fused_shed_eval(
        TC.init(cfg.cache_slots, cfg.cache_ways),
        AT.init(cfg.prior_buckets), keys, buckets, valid, feats,
        evaluate=lambda f: f["x"], max_evals=64, cfg=cfg,
        u_capacity=cfg.u_capacity, u_threshold=cfg.u_threshold)
    trust = np.asarray(trust)
    tier = np.asarray(aux["plan"]["tier"])
    assert trust.shape == (64,)
    assert (tier[:50] != TIER_INVALID).all()      # every valid item tiered
    assert (tier[50:] == TIER_INVALID).all()
    assert (trust[50:] == 0.0).all()


# ---------------------------------------------------------------------------
# scheduler end-to-end: no-drop invariant, rejections, hedging
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_items,regime", [
    (40, Regime.NORMAL),        # <= Ucap=64
    (80, Regime.HEAVY),         # <= Ucap+Uthr=96
    (300, Regime.VERY_HEAVY),
])
def test_admitted_requests_never_dropped_per_regime(n_items, regime):
    eng, _ = _sim_engine()
    resp = eng.submit(*_req_arrays(0, n_items), slo_s=10.0,
                      priority=Priority.HIGH)
    assert resp.admitted
    assert resp.shed.regime == regime
    assert resp.trust.shape == (n_items,)
    assert (resp.tier != TIER_INVALID).all()
    assert np.isfinite(resp.trust).all()


def _req_arrays(rid, n, seed=0):
    r = np.random.default_rng(seed + rid)
    return (np.arange(rid * 10_000 + 1, rid * 10_000 + n + 1,
                      dtype=np.uint32),
            r.integers(0, 8, n).astype(np.int32),
            {"x": np.linspace(0, 5, n, dtype=np.float32)})


@given(st.lists(st.tuples(st.integers(1, 120), st.integers(0, 2),
                          st.integers(0, 2)),
                min_size=1, max_size=12),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=25, deadline=None)
def test_no_admitted_request_dropped_property(reqs, seed):
    """Random multi-tenant streams (spanning NORMAL through VERY_HEAVY,
    incl. floods far past Ucapacity+Uthreshold): every submitted request
    gets exactly one response; admitted ones carry a finite trust value
    for EVERY item; rejections are explicit with a reason. Each carries
    the regime the scheduler's rules give it: a rejection, the regime
    offered at its admission (queued items plus its own); an admitted
    request, its batch's, which packs whole requests up to the
    micro-batch, so a batch runs Very Heavy only when one request alone
    exceeds Ucapacity+Uthreshold. A flood need not reach Very Heavy:
    queue-full rejections bound the backlog."""
    eng, _ = _sim_engine(queue_capacity_requests=4)
    ucap, uthr = eng.scheduler.shedder.monitor.parameters()
    cap = eng.scheduler.batcher.capacity_items
    assert cap <= ucap + uthr
    rids = [eng.enqueue(*_req_arrays(i, n, seed=seed),
                        priority=Priority(p + 1),    # HIGH/NORMAL/LOW
                        tenant=f"t{tn}")
            for i, (n, p, tn) in enumerate(reqs)]
    eng.drain()
    by_rid = {}
    for r in eng.completed:
        assert r.request_id not in by_rid          # exactly one response
        by_rid[r.request_id] = r
    assert sorted(by_rid) == sorted(rids)          # none missing
    queued = 0       # admitted items waiting as each request arrived
    for i, (n, _, _) in enumerate(reqs):
        r = by_rid[rids[i]]
        assert r.trust.shape == (n,)
        assert np.isfinite(r.trust).all()
        if r.admitted:
            assert (r.tier != TIER_INVALID).all()  # no silent drops
            queued += n
            assert r.shed.uload <= max(n, cap)
            assert r.shed.regime == classify(r.shed.uload, ucap, uthr)
        else:
            assert r.reason                        # observable rejection
            assert (r.tier == TIER_PRIOR).all()    # answered from prior
            assert r.shed.regime == classify(queued + n, ucap, uthr)


def test_low_priority_rejection_is_explicit_under_very_heavy():
    eng, _ = _sim_engine()
    cfg = eng.cfg
    # queue a flood so the offered load is VERY_HEAVY, then a LOW request
    eng.enqueue(*_req_arrays(0, cfg.u_capacity + cfg.u_threshold + 50),
                priority=Priority.HIGH)
    n0 = len(eng.completed)
    eng.enqueue(*_req_arrays(1, 10), priority=Priority.LOW)
    assert len(eng.completed) == n0 + 1            # rejected immediately
    rej = eng.completed[-1]
    assert not rej.admitted
    assert rej.reason == REASON_SHED_LOW_VERY_HEAVY
    assert (rej.tier == TIER_PRIOR).all()
    # answered from the average-trust prior (init value 2.5)
    assert rej.trust == pytest.approx(2.5)
    stats = eng.scheduler_stats()
    assert stats["rejected_by_reason"][REASON_SHED_LOW_VERY_HEAVY] == 1


def test_rate_limited_tenant_rejected_others_flow():
    eng, _ = _sim_engine(tenant_rate_items_per_s=10.0,
                         tenant_burst_items=20.0)
    eng.enqueue(*_req_arrays(0, 20), tenant="noisy")   # drains the bucket
    eng.enqueue(*_req_arrays(1, 20), tenant="noisy")   # rejected
    eng.enqueue(*_req_arrays(2, 20), tenant="quiet")   # own bucket: ok
    rejected = [r for r in eng.completed if not r.admitted]
    assert len(rejected) == 1
    assert rejected[0].reason == REASON_RATE_LIMITED
    eng.drain()
    assert sum(r.admitted for r in eng.completed) == 2


def test_hedged_request_answered_once():
    eng, clock = _sim_engine(hedge_after_s=0.5)
    rid = eng.enqueue(*_req_arrays(0, 20), priority=Priority.NORMAL)
    clock.t += 1.0                                  # waits past the hedge
    out = eng.drain()
    assert [r.request_id for r in out] == [rid]     # twin deduplicated
    assert out[0].hedged
    assert eng.scheduler_stats()["n_hedges"] == 1
    assert out[0].priority == Priority.NORMAL


# ---------------------------------------------------------------------------
# engine API: compat shim + slo_s semantics
# ---------------------------------------------------------------------------

def test_submit_honors_explicit_zero_slo():
    cfg = smoke_config()
    eng = ServingEngine(cfg, lambda ch: np.asarray(ch["x"]))  # real clock
    resp = eng.submit(*_req_arrays(0, 8), slo_s=0.0)
    assert not resp.met_slo          # 0.0 must not fall back to default
    resp2 = eng.submit(*_req_arrays(1, 8))          # default SLO: generous
    assert resp2.met_slo


def test_enqueue_drain_matches_submit_results():
    eng1, _ = _sim_engine()
    eng2, _ = _sim_engine()
    r1 = eng1.submit(*_req_arrays(0, 50))
    rid = eng2.enqueue(*_req_arrays(0, 50))
    (r2,) = eng2.drain()
    assert r2.request_id == rid
    np.testing.assert_allclose(r1.trust, r2.trust)
    np.testing.assert_array_equal(r1.tier, r2.tier)


# ---------------------------------------------------------------------------
# simulator: multi-tenant Poisson priority mixes
# ---------------------------------------------------------------------------

def test_multi_tenant_scheduled_workload():
    from repro.core.pipeline import SyntheticSearcher
    from repro.serving.simulator import (MultiTenantWorkload, TenantSpec,
                                         run_scheduled_workload)

    eng, _ = _sim_engine(evaluate=lambda ch: np.asarray(ch["trust"]))
    wl = MultiTenantWorkload(tenants=[
        TenantSpec("interactive", qps=20.0,
                   priority_mix={Priority.CRITICAL: 0.2,
                                 Priority.HIGH: 0.8},
                   max_results=300, slo_s=5.0),
        TenantSpec("crawler", qps=10.0,
                   priority_mix={Priority.LOW: 1.0},
                   max_results=2000, slo_s=5.0),
    ], n_queries=40, seed=7)
    rep = run_scheduled_workload(eng, SyntheticSearcher(corpus_size=5000,
                                                        seed=1), wl)
    s = rep.summary()
    assert s["n_responses"] == s["n_admitted"] + s["n_rejected"]
    assert s["n_responses"] >= 40 * 0.9       # every arrival answered
    by_p = s["by_priority"]
    assert any(k in by_p for k in ("CRITICAL", "HIGH"))
    for r in rep.responses:                   # no-drop, end to end
        assert np.isfinite(r.trust).all()
        if r.admitted:
            assert (r.tier != TIER_INVALID).all()
