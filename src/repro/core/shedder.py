"""The Optimal Load Shedding Algorithm (paper §5), TPU-adapted.

Paper semantics preserved:
  * three regimes (Normal / Heavy / Very Heavy) from (Uload, Ucapacity,
    Uthreshold),
  * Normal Queue = first Ucapacity URLs in arrival order — Trust-DB hits
    assigned from cache, the rest fully evaluated (no deadline check),
  * Drop Queue = the remainder — cache hits first, then evaluation until
    the (possibly extended) deadline, then the average-trust prior,
  * Very Heavy extends the deadline per §4.3 before running the Heavy
    procedure,
  * NO item is ever dropped: every URL leaves with a trust value
    (the property RLS-EDA [2] lacks; property-tested in
    ``tests/test_shedder_properties.py``).

TPU adaptation (DESIGN.md §2): per-URL sequential evaluation becomes
chunked batched evaluation. Two execution modes:

  * ``shed_plan`` + ``fused_shed_eval`` — fully jitted: tier assignment is
    computed with masked cumulative counts, EVAL-tier items are gathered
    to a *static-size* evaluation batch (budget-shaped), scored in one
    batched forward, and scattered back. This is the form that lowers to
    the production mesh.
  * ``LoadShedder.process`` — host loop at chunk granularity with a real
    (or simulated) clock; used by the serving engine and the paper-figure
    benchmarks where wall-clock deadlines are the measured quantity.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrustIRConfig
from repro.core import average_trust as AT
from repro.core import trust_cache as TC
from repro.core.deadline import effective_deadline, effective_deadline_jnp
from repro.core.load_monitor import LoadMonitor, WarmupGate
from repro.core.regimes import Regime, classify, classify_jnp

# Tier codes (answer ladder)
TIER_EVAL = 0      # full trust evaluation (model forward)
TIER_CACHED = 1    # Trust DB hit
TIER_PRIOR = 2     # average-trustworthiness fallback
TIER_INVALID = 3   # padding


# ---------------------------------------------------------------------------
# Jitted planning
# ---------------------------------------------------------------------------

def shed_plan(valid: jnp.ndarray, cache_hit: jnp.ndarray,
              u_capacity, u_threshold, *,
              deadline_s: float, overload_deadline_s: float,
              very_heavy_weight: float) -> Dict[str, jnp.ndarray]:
    """Assign a tier to every item of a padded batch.

    valid: (N,) bool arrival-ordered validity mask; cache_hit: (N,) bool.
    u_capacity / u_threshold: int32 scalars (static or traced).

    Returns dict with ``tier`` (N,) int32, ``regime`` scalar, ``uload``,
    ``eval_budget_dq`` and ``deadline_eff`` scalars — everything the
    executor needs, computed with static shapes only.
    """
    valid = valid.astype(bool)
    cache_hit = cache_hit & valid
    uload = jnp.sum(valid.astype(jnp.int32))
    regime = classify_jnp(uload, u_capacity, u_threshold)
    deadline_eff = effective_deadline_jnp(
        uload, u_capacity, u_threshold, deadline_s=deadline_s,
        overload_deadline_s=overload_deadline_s, weight=very_heavy_weight)

    # Arrival position among valid items.
    pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    in_normal = valid & (pos < u_capacity)

    # Normal queue: cache hit -> CACHED else EVAL (no deadline check, §5.2).
    # Drop queue: cache hit -> CACHED (§5.3 first loop).
    tier = jnp.where(cache_hit, TIER_CACHED, TIER_PRIOR)
    tier = jnp.where(in_normal & ~cache_hit, TIER_EVAL, tier)

    # Drop-queue evaluation budget: the evaluator runs at
    # rate = Ucapacity / deadline_s items/s by definition (§4); after the
    # normal queue the remaining time until the effective deadline buys
    #   floor(rate * deadline_eff) - n_normal_evals
    # further evaluations (§5.3 second loop, chunk-granular clock).
    n_normal_evals = jnp.sum((in_normal & ~cache_hit).astype(jnp.int32))
    rate = jnp.asarray(u_capacity, jnp.float32) / jnp.float32(deadline_s)
    budget_total = jnp.floor(rate * deadline_eff).astype(jnp.int32)
    budget_dq = jnp.maximum(budget_total - n_normal_evals, 0)

    dq_eval_cand = valid & ~in_normal & ~cache_hit
    dq_rank = jnp.cumsum(dq_eval_cand.astype(jnp.int32)) - 1
    tier = jnp.where(dq_eval_cand & (dq_rank < budget_dq), TIER_EVAL, tier)
    tier = jnp.where(valid, tier, TIER_INVALID)

    return {
        "tier": tier.astype(jnp.int32),
        "regime": regime,
        "uload": uload,
        "deadline_eff": deadline_eff,
        "eval_budget_dq": budget_dq,
        "n_normal_evals": n_normal_evals,
    }


def gather_eval_indices(tier: jnp.ndarray, max_evals: int) -> Tuple[
        jnp.ndarray, jnp.ndarray]:
    """Static-size gather of EVAL-tier item indices (arrival order).

    Returns (idx (max_evals,) int32, valid (max_evals,) bool). This is the
    pure-jnp oracle of the ``shed_partition`` Pallas kernel. O(N log N)
    (argsort) — the fused serving drain uses the kernel's compacted rank
    output with :func:`eval_indices_from_rank` (one O(N) scatter)
    instead.
    """
    n = tier.shape[0]
    is_eval = tier == TIER_EVAL
    key = jnp.where(is_eval, jnp.arange(n), n + jnp.arange(n))
    order = jnp.argsort(key)
    idx = order[:max_evals]
    valid = is_eval[idx]
    return idx.astype(jnp.int32), valid


def eval_indices_from_rank(eval_rank: jnp.ndarray, max_evals: int
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """O(N) gather-index compaction from the ``shed_partition`` kernel's
    ``eval_rank`` output (arrival-ordered rank of each EVAL item, -1
    otherwise): one scatter replaces ``gather_eval_indices``'s argsort.

    Returns (idx (max_evals,) int32, valid (max_evals,) bool). Invalid
    slots hold ``n`` (out of range — gathers clamp, scatters with
    ``mode="drop"`` discard them).
    """
    n = eval_rank.shape[0]
    in_budget = (eval_rank >= 0) & (eval_rank < max_evals)
    slot = jnp.where(in_budget, eval_rank, max_evals)
    idx = jnp.full((max_evals,), n, jnp.int32).at[slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return idx, idx < n


def combine_trust(tier: jnp.ndarray, eval_scores_scattered: jnp.ndarray,
                  cached_vals: jnp.ndarray,
                  prior_vals: jnp.ndarray) -> jnp.ndarray:
    """Final per-item trust by tier (answer ladder, §5)."""
    t = jnp.where(tier == TIER_EVAL, eval_scores_scattered,
                  jnp.where(tier == TIER_CACHED, cached_vals, prior_vals))
    return jnp.where(tier == TIER_INVALID, 0.0, t)


def fused_shed_eval(cache_state: Dict, prior_state: Dict,
                    item_keys: jnp.ndarray, buckets: jnp.ndarray,
                    valid: jnp.ndarray, features,
                    evaluate: Callable, max_evals: int,
                    cfg: TrustIRConfig,
                    u_capacity, u_threshold) -> Tuple[jnp.ndarray, Dict]:
    """One fully-jitted shedding step (plan -> gather -> eval -> combine).

    ``features`` is a pytree whose leaves have leading dim N (items);
    ``evaluate(features_subset) -> (max_evals,) scores``. Returns
    (trust (N,), aux dict incl. updated cache/prior states + plan).
    """
    cached_vals, hit = TC.lookup(cache_state, item_keys)
    plan = shed_plan(valid, hit, u_capacity, u_threshold,
                     deadline_s=cfg.deadline_s,
                     overload_deadline_s=cfg.overload_deadline_s,
                     very_heavy_weight=cfg.very_heavy_weight)
    tier = plan["tier"]
    idx, eval_valid = gather_eval_indices(tier, max_evals)
    sub = jax.tree.map(lambda a: a[idx], features)
    scores = evaluate(sub)                                  # (max_evals,)
    n = tier.shape[0]
    scattered = jnp.zeros((n,), jnp.float32).at[idx].set(
        jnp.where(eval_valid, scores.astype(jnp.float32), 0.0), mode="drop")
    prior_vals = AT.query(prior_state, buckets)
    trust = combine_trust(tier, scattered, cached_vals, prior_vals)
    # Fold fresh evaluations back into the Trust DB + prior.
    evald = tier == TIER_EVAL
    new_cache = TC.insert(cache_state, item_keys, trust, evald)
    new_prior = AT.update(prior_state, buckets, trust, evald,
                          ewma=cfg.prior_ewma)
    return trust, {"plan": plan, "cache": new_cache, "prior": new_prior,
                   "n_evald": jnp.sum(evald.astype(jnp.int32))}


# ---------------------------------------------------------------------------
# Host chunked executor (wall-clock or simulated clock)
# ---------------------------------------------------------------------------

@dataclass
class ShedResult:
    trust: np.ndarray                # (N,) final trust for every item
    tier: np.ndarray                 # (N,) tier per item
    regime: Regime
    response_time_s: float           # measured (or simulated) latency
    deadline_eff_s: float
    n_evaluated: int
    n_cached: int
    n_prior: int
    uload: int
    # Additive per-batch counters, by the name ``scheduler_stats()``
    # reports them under, filled by the shedder that ran the batch:
    # ``n_eval_rows`` (rows the evaluator computed, padding included),
    # the fused step's ``xfer_s`` (seconds of host<->device transfer)
    # and ``"n_" + name`` for each count its evaluator names. Empty for
    # a request's share of a batch.
    counts: Dict[str, float] = field(default_factory=dict)

    @property
    def no_item_dropped(self) -> bool:
        return bool(np.all(self.tier != TIER_INVALID))


class SimClock:
    """Deterministic clock: evaluation chunks cost chunk/rate seconds."""

    def __init__(self, rate_items_per_s: float, probe_cost_s: float = 0.0):
        self.t = 0.0
        self.rate = rate_items_per_s
        self.probe_cost_s = probe_cost_s

    def now(self) -> float:
        return self.t

    def charge_eval(self, n_items: int) -> None:
        self.t += n_items / self.rate

    def charge_probe(self) -> None:
        self.t += self.probe_cost_s


class LoadShedder:
    """Host-side Optimal Load Shedding executor (paper §5 procedures).

    evaluate_chunk: Callable[(features chunk pytree)] -> np scores; chunks
    are padded to ``cfg.chunk_size`` so the evaluator jit-compiles once.
    """

    # The host chunk loop is synchronous: the DrainExecutor runs it
    # eagerly (dispatch + finalize per submit) instead of windowing.
    supports_async = False

    def __init__(self, cfg: TrustIRConfig,
                 evaluate_chunk: Callable,
                 monitor: Optional[LoadMonitor] = None,
                 cache_state: Optional[Dict] = None,
                 prior_state: Optional[Dict] = None,
                 sim_clock: Optional[SimClock] = None,
                 adaptive=None):
        self.cfg = cfg
        self.evaluate_chunk = evaluate_chunk
        self.monitor = monitor or LoadMonitor(cfg)
        self.cache = (cache_state if cache_state is not None
                      else TC.init(cfg.cache_slots, cfg.cache_ways))
        self.prior = (prior_state if prior_state is not None
                      else AT.init(cfg.prior_buckets))
        self.sim_clock = sim_clock
        # optional AdaptiveWeightController (core.adaptive): closes the
        # loop on the Very-Heavy extension weight — the paper's §7
        # future work
        self.adaptive = adaptive
        # Optional tap fired after every shed with (item_keys, result):
        # the cluster layer uses it to harvest fresh-evaluation Trust-DB
        # deltas for cross-replica gossip.
        self.on_shed: Optional[Callable[[np.ndarray, "ShedResult"],
                                        None]] = None
        # Shared jit-warmup exclusion (host and fused paths apply the
        # SAME rule, so their Ucapacity estimates are comparable —
        # see load_monitor.WarmupGate).
        self._warmup = WarmupGate()

    def _vh_weight(self) -> float:
        return (self.adaptive.weight if self.adaptive is not None
                else self.cfg.very_heavy_weight)

    # -- clock helpers -----------------------------------------------------
    def _now(self) -> float:
        return self.sim_clock.now() if self.sim_clock else time.monotonic()

    def _eval(self, features, idx: np.ndarray) -> np.ndarray:
        """Evaluate items ``idx`` in padded chunks; returns scores.

        ``features`` leaves must already be numpy (``process`` converts
        the pytree ONCE per batch — re-converting inside the chunk loop
        paid O(chunks x N) copies).
        """
        cs = self.cfg.chunk_size
        n = len(idx)
        out = np.zeros((n,), np.float32)
        for s in range(0, n, cs):
            chunk_idx = idx[s:s + cs]
            pad = cs - len(chunk_idx)
            padded = np.concatenate([chunk_idx,
                                     np.zeros((pad,), chunk_idx.dtype)])
            sub = jax.tree.map(lambda a: a[padded], features)
            warm = self._warmup.warm(WarmupGate.signature(cs, sub))
            t0 = time.monotonic()
            scores = np.asarray(self.evaluate_chunk(sub))
            if self.sim_clock:
                self.sim_clock.charge_eval(len(chunk_idx))
            elif warm:
                # First sight of a chunk shape is jit warmup: excluded
                # from the throughput EWMA under the same rule the
                # fused path applies, so host-vs-fused Ucapacity
                # estimates stay comparable.
                self.monitor.observe(len(chunk_idx),
                                     time.monotonic() - t0)
            out[s:s + len(chunk_idx)] = scores[:len(chunk_idx)]
        return out

    def _chunk_rows(self, n: int) -> int:
        """Rows ``_eval`` computes for ``n`` items: whole chunks."""
        cs = self.cfg.chunk_size
        return -(-n // cs) * cs

    # -- the algorithm (§5.1 Load_Shedder) ----------------------------------
    def process(self, item_keys: np.ndarray, buckets: np.ndarray,
                features, n_valid: Optional[int] = None) -> ShedResult:
        """Shed one (possibly padded) batch.

        ``n_valid`` marks the valid prefix of a padded batch (the
        scheduler's micro-batches keep array shapes static across
        drains so device ops hit their executable caches instead of
        recompiling per batch size). Items past ``n_valid`` are padding:
        excluded from Uload, tiered ``TIER_INVALID``, and masked out of
        the Trust-DB / prior fold-back. Default: the whole batch is
        valid (the original per-request behavior).
        """
        t_start = self._now()
        n_total = len(item_keys)
        n = n_total if n_valid is None else int(n_valid)
        ucap, uthr = self.monitor.parameters()
        regime = classify(n, ucap, uthr)
        deadline_eff = effective_deadline(
            n, ucap, uthr, deadline_s=self.cfg.deadline_s,
            overload_deadline_s=self.cfg.overload_deadline_s,
            weight=self._vh_weight())
        deadline_t = t_start + deadline_eff

        keys_j = jnp.asarray(item_keys, jnp.uint32)
        cached_vals, hit = TC.lookup(self.cache, keys_j)
        if self.sim_clock:
            self.sim_clock.charge_probe()
        cached_vals = np.asarray(cached_vals)
        hit = np.asarray(hit)
        # Materialize the feature pytree once per batch; _eval's chunk
        # loop then only pays O(chunk) fancy-indexing per chunk.
        features = jax.tree.map(np.asarray, features)

        trust = np.zeros((n_total,), np.float32)
        tier = np.full((n_total,), TIER_INVALID, np.int32)
        tier[:n] = TIER_PRIOR

        # ---- Normal Queue (§5.2): first Ucapacity items ----
        n_normal = min(n, ucap)
        nq = np.arange(n_normal)
        nq_hit = nq[hit[:n_normal]]
        nq_eval = nq[~hit[:n_normal]]
        trust[nq_hit] = cached_vals[nq_hit]
        tier[nq_hit] = TIER_CACHED
        n_eval_rows = 0
        if len(nq_eval):
            trust[nq_eval] = self._eval(features, nq_eval)
            tier[nq_eval] = TIER_EVAL
            n_eval_rows += self._chunk_rows(len(nq_eval))

        # ---- Drop Queue (§5.3 / §5.4) ----
        if n > n_normal:
            dq = np.arange(n_normal, n)
            dq_hit = dq[hit[n_normal:n]]
            trust[dq_hit] = cached_vals[dq_hit]
            tier[dq_hit] = TIER_CACHED
            dq_eval_cand = dq[~hit[n_normal:n]]
            # Evaluate until the (extended) deadline. Chunk-granular
            # adaptation of §5.3's per-URL clock check: only start a chunk
            # if its estimated completion still fits within the deadline.
            cs = self.cfg.chunk_size
            rate = (self.sim_clock.rate if self.sim_clock
                    else self.monitor.rate)
            done = 0
            while done < len(dq_eval_cand):
                take = dq_eval_cand[done:done + cs]
                if self._now() + len(take) / rate > deadline_t + 1e-9:
                    break
                trust[take] = self._eval(features, take)
                tier[take] = TIER_EVAL
                done += len(take)
                n_eval_rows += self._chunk_rows(len(take))
            # rest: average trustworthiness (prior) — host-side numpy
            # lookup (ragged sizes would retrace a jit per request)
            rest = dq_eval_cand[done:]
            if len(rest):
                means = np.asarray(self.prior["mean"])
                trust[rest] = means[buckets[rest] % len(means)]
                tier[rest] = TIER_PRIOR

        # ---- fold results back into Trust DB + prior ----
        evald = tier == TIER_EVAL
        if evald.any():
            self.cache = TC.insert(self.cache, keys_j,
                                   jnp.asarray(trust),
                                   jnp.asarray(evald))
            self.prior = AT.update(self.prior, jnp.asarray(buckets),
                                   jnp.asarray(trust), jnp.asarray(evald),
                                   ewma=self.cfg.prior_ewma)

        rt = self._now() - t_start
        result = ShedResult(
            trust=trust, tier=tier, regime=regime,
            response_time_s=rt, deadline_eff_s=deadline_eff,
            n_evaluated=int(evald.sum()),
            n_cached=int((tier == TIER_CACHED).sum()),
            n_prior=int((tier == TIER_PRIOR).sum()),
            uload=n, counts={"n_eval_rows": n_eval_rows})
        if self.adaptive is not None:
            self.adaptive.observe(result)
        if self.on_shed is not None:
            self.on_shed(np.asarray(item_keys), result)
        return result
