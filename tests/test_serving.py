"""Serving layer: engine SLOs, KV slot pool, overload simulator, and
evaluator backends for every arch family."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.trust_ir import smoke_config
from repro.core import LoadShedder, SimClock, SyntheticSearcher, \
    TrustIRPipeline
from repro.serving.engine import ServingEngine
from repro.serving.evaluators import make_evaluator
from repro.serving.kv_cache import KVCachePool, SlotAllocator
from repro.serving.simulator import WorkloadConfig, run_workload

ALL_ARCHS = ["smollm-135m", "gemma2-2b", "gcn-cora", "dlrm-mlperf",
             "bst", "two-tower-retrieval", "mind", "qwen3-moe-30b-a3b",
             "moonshot-v1-16b-a3b"]


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_evaluator_backend_produces_bounded_scores(arch):
    ev, mk = make_evaluator(arch, smoke=True)
    feats = mk(32, fseed=0)
    scores = np.asarray(ev({k: jnp.asarray(v) for k, v in feats.items()}))
    assert scores.shape == (32,)
    assert np.isfinite(scores).all()
    assert (scores >= 0).all() and (scores <= 5.0).all()


def test_engine_meets_slo_under_overload():
    cfg = smoke_config()
    clock = SimClock(rate_items_per_s=cfg.u_capacity / cfg.deadline_s)
    eng = ServingEngine(cfg, lambda ch: np.asarray(ch["x"]),
                        sim_clock=clock)
    for n in [50, 150, 400]:
        resp = eng.submit(np.arange(1, n + 1, dtype=np.uint32),
                          np.zeros(n, np.int32),
                          {"x": np.linspace(0, 5, n, dtype=np.float32)},
                          slo_s=cfg.overload_deadline_s * (
                              1 + cfg.very_heavy_weight))
        assert resp.met_slo
    stats = eng.slo_stats()
    assert stats["n"] == 3 and stats["slo_met_frac"] == 1.0


def test_slot_allocator_claims_and_releases():
    a = SlotAllocator(4)
    slots = [a.claim(i) for i in range(4)]
    assert sorted(slots) == [0, 1, 2, 3]
    assert a.claim(99) is None          # pool exhausted
    a.release(slots[1])
    assert a.claim(100) == slots[1]
    assert a.n_active == 4


def test_kv_cache_pool_lifecycle():
    cfg = get_config("smollm-135m", smoke=True)
    pool = KVCachePool(cfg, n_slots=3, max_len=16)
    s0 = pool.admit(request_id=7, prompt_len=0)
    assert s0 is not None
    assert pool.active_mask()[s0]
    pool.retire(s0)
    assert not pool.active_mask().any()
    assert int(pool.cache["lengths"][s0]) == 0


def test_simulator_overload_shifts_percentiles():
    cfg = smoke_config()

    def build(rate_scale):
        clock = SimClock(rate_items_per_s=rate_scale * cfg.u_capacity
                         / cfg.deadline_s)
        shed = LoadShedder(cfg, lambda ch: np.asarray(ch["trust"]),
                           sim_clock=clock)
        searcher = SyntheticSearcher(corpus_size=3000, seed=1)
        return TrustIRPipeline(cfg, searcher, shed)

    wl = WorkloadConfig(n_queries=30, seed=3, max_results=2000)
    fast = run_workload(build(rate_scale=1.0), wl)
    assert fast.summary()["mean_recall"] == 1.0
    # under the deadline discipline P99 stays below the extended deadline
    assert fast.percentile(99) <= cfg.overload_deadline_s * (
        1 + cfg.very_heavy_weight) + 1e-6


def test_simulator_reports_regime_mix():
    cfg = smoke_config()
    clock = SimClock(rate_items_per_s=cfg.u_capacity / cfg.deadline_s)
    shed = LoadShedder(cfg, lambda ch: np.asarray(ch["trust"]),
                       sim_clock=clock)
    pipe = TrustIRPipeline(cfg, SyntheticSearcher(corpus_size=3000,
                                                  seed=1), shed)
    rep = run_workload(pipe, WorkloadConfig(n_queries=25, seed=0,
                                            max_results=3000))
    assert len(rep.regimes) == 25
    assert rep.summary()["frac_heavy+"] > 0      # workload does overload
