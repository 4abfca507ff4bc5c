"""Device-resident fused drain: one jitted step per micro-batch.

``LoadShedder.process`` is the paper-figure executor — a host-side
chunk loop with a real (or simulated) clock, one device round-trip per
chunk. The serving hot path doesn't need a wall-clock deadline check
*inside* the batch (the budget is decided up front by the same
``shed_plan`` math), so ``FusedLoadShedder`` collapses the whole
shedding decision into ONE device dispatch per micro-batch:

    shed_partition (XLA gather of each key's Trust-DB set, then
                    Pallas: way compare + tier scan, SMEM
                    write-cursor emits compacted eval ranks)
      -> eval_indices_from_rank   O(N) scatter, no argsort
      -> static-shape gather      features picked once, on device
      -> evaluator forward        one batched call, no chunk loop
      -> scatter + combine        trust per tier
      -> TC.insert / AT.update    cache + prior fold-back, donated
                                  buffers update in place

The evaluator's parameters enter the step as an argument (an
``Evaluator``'s ``params``), never as constants folded into the
compiled step. The cache and prior are donated: after a dispatch only
the step's outputs (``self.cache``/``self.prior``) are live, so code
that keeps a state across a dispatch must copy it first.

Features transfer to device once per *batch* (the host path converts
the pytree then re-gathers per chunk), and the transfer is its own
stage: ``stage`` enqueues the host->device copies, ``dispatch_staged``
launches the step, and ``process_async`` composes the two into a
:class:`PendingShed` whose arrays stay on device until ``.result()``.
The ``scheduling.executor.DrainExecutor`` sequences these handles in a
depth-k in-flight window (``TrustIRConfig.pipeline_depth``): batch N+2
forms and transfers while batch N computes and N+1 waits, and at depth
>= 2 the window survives across drain calls so a serving loop never
pays a device sync per iteration. With a ``SimClock`` the step resolves
eagerly instead — simulated timelines are sequential by construction
and exist for deterministic parity with the host path, not throughput.

Tier parity: ``budget_total = floor(rate * deadline_eff)`` is computed
from the same Load-Monitor parameters and deadline controller as
``shed_plan`` / ``LoadShedder.process``, and the kernel nets out
normal-queue evaluations in-flight (``budget_is_total=True``), so the
fused tiers match the ``shed_plan`` oracle bit-for-bit. The host
executor grants drop-queue evaluations at *chunk* granularity against a
running clock; with chunk-aligned budgets (benchmarks, tests) the two
paths agree exactly.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import TrustIRConfig
from repro.core import average_trust as AT
from repro.core import trust_cache as TC
from repro.core.deadline import effective_deadline
from repro.core.load_monitor import LoadMonitor, WarmupGate
from repro.core.regimes import classify
from repro.core.shedder import (LoadShedder, ShedResult, SimClock,
                                TIER_CACHED, TIER_EVAL, TIER_PRIOR,
                                combine_trust, eval_indices_from_rank)


def _mesh_of(params) -> Optional[Mesh]:
    """The multi-device mesh a sharded evaluator's parameters live on,
    or None when they sit on one device."""
    for leaf in jax.tree.leaves(params):
        sharding = getattr(leaf, "sharding", None)
        if isinstance(sharding, NamedSharding) and sharding.mesh.size > 1:
            return sharding.mesh
    return None


class StagedBatch:
    """One micro-batch after its host->device feature transfer.

    Staging is the front half of the fused pipeline: ``stage`` enqueues
    the transfers, ``dispatch_staged`` launches the jitted shedding
    step on the staged buffers. The copies are asynchronous, so under a
    depth-k ``DrainExecutor`` window the transfer of batch N+2 runs
    behind the in-flight device steps of N and N+1 — the overlap comes
    from the window plus JAX async dispatch, the split keeps the
    transfer cost visible (and monitorable) as its own stage.
    """

    __slots__ = ("item_keys", "keys_j", "buckets_j", "valid_j",
                 "feats_j", "n", "n_total", "t_start", "wall_start")

    def __init__(self, item_keys, keys_j, buckets_j, valid_j, feats_j,
                 n: int, n_total: int, t_start: float,
                 wall_start: float):
        self.item_keys = item_keys
        self.keys_j = keys_j
        self.buckets_j = buckets_j
        self.valid_j = valid_j
        self.feats_j = feats_j
        self.n = n
        self.n_total = n_total
        self.t_start = t_start
        self.wall_start = wall_start


class PendingShed:
    """Handle to an in-flight fused shedding step.

    ``trust``/``tier`` stay device-resident (possibly still computing —
    JAX async dispatch) until :meth:`result` materializes them, charges
    the clock/monitor, and builds the :class:`ShedResult`.
    """

    def __init__(self, shedder: "FusedLoadShedder", trust, tier,
                 n_evald, *, t_start: float, wall_start: float,
                 n: int, regime, deadline_eff: float,
                 skip_observe: bool = False,
                 item_keys: Optional[np.ndarray] = None):
        self._shedder = shedder
        self._trust = trust
        self._tier = tier
        self._n_evald = n_evald
        self._item_keys = item_keys
        self._t_start = t_start
        self._wall_start = wall_start
        self._n = n
        self._regime = regime
        self._deadline_eff = deadline_eff
        self._skip_observe = skip_observe
        self._result: Optional[ShedResult] = None
        # Wall time at which the step was FIRST observed complete
        # (stamped by is_ready): the honest end of the throughput
        # window when finalize happens long after completion.
        self._wall_ready: Optional[float] = None

    def result(self) -> ShedResult:
        if self._result is None:
            self._result = self._shedder._finish(self)
        return self._result

    def is_ready(self) -> bool:
        """True when the device step has completed (materializing would
        not block). The DrainExecutor's ``poll`` uses this to fold
        finished batches back without stalling on running ones."""
        if self._result is not None:
            return True
        ready = getattr(self._trust, "is_ready", None)
        done = True if ready is None else bool(ready())
        if done and self._wall_ready is None:
            self._wall_ready = time.monotonic()
        return done


class FusedLoadShedder(LoadShedder):
    """Drop-in ``LoadShedder`` whose ``process`` runs the fused device
    step. ``evaluate_batch`` must be jax-traceable: features pytree
    (leading dim ``max_evals``) -> (max_evals,) scores. When it carries
    ``apply``/``params`` (a ``serving.evaluators.Evaluator``) the step
    calls ``apply`` with the params as a step argument. The host
    executor's ``evaluate_chunk`` protocol is satisfied by the same
    callable whenever it is traceable (every ``serving.evaluators``
    backend is), so baseline drivers can still call the inherited
    chunked path explicitly if they need a wall-clock deadline.
    """

    supports_async = True

    def __init__(self, cfg: TrustIRConfig, evaluate_batch: Callable,
                 monitor: Optional[LoadMonitor] = None,
                 cache_state: Optional[Dict] = None,
                 prior_state: Optional[Dict] = None,
                 sim_clock: Optional[SimClock] = None,
                 adaptive=None,
                 max_evals: Optional[int] = None,
                 feature_sharding=None):
        """``feature_sharding`` (optional) places staged features for a
        mesh-sharded evaluator: a pytree of ``jax.sharding.Sharding``
        matching the feature pytree, or a callable
        ``features -> sharding pytree`` (what
        ``serving.evaluators.make_sharded_evaluator`` returns). When
        set, ``stage`` transfers each micro-batch with
        ``jax.device_put(features, sharding)`` — batch N+2's
        host->device copies land directly in the sharded layout batch
        N's forward is computing in, so the depth-k window overlaps
        transfer with the SHARDED evaluator, not a replicated copy of
        it."""
        super().__init__(cfg, evaluate_batch, monitor=monitor,
                         cache_state=cache_state,
                         prior_state=prior_state,
                         sim_clock=sim_clock, adaptive=adaptive)
        self.evaluate_batch = evaluate_batch
        self.max_evals = max_evals
        self.feature_sharding = feature_sharding
        apply = getattr(evaluate_batch, "apply", None)
        if apply is None:
            self._apply = lambda _params, feats: evaluate_batch(feats)
            self._eval_params = None
        else:
            self._apply = apply
            self._eval_params = evaluate_batch.params
        self._mesh = _mesh_of(self._eval_params)
        if self._mesh is not None:
            # Start the state where the step leaves it (replicated on
            # the evaluator's mesh), so the second batch does not
            # compile the step again for a new input placement.
            rep = NamedSharding(self._mesh, P())
            self.cache = jax.device_put(self.cache, rep)
            self.prior = jax.device_put(self.prior, rep)
        self._step = jax.jit(self._step_impl,
                             static_argnames=("max_evals",),
                             donate_argnums=(0, 1))
        # Wall time of the last throughput observation: pipelined
        # batches overlap, so each observation charges only the
        # marginal window since the previous one (see _finish).
        self._last_obs_wall = 0.0

    # -- the fused device step ----------------------------------------------
    def _step_impl(self, cache, prior, eval_params, keys, buckets, valid,
                   features, u_capacity, u_threshold, budget_total, *,
                   max_evals: int):
        from repro.distribution.constraints import shard_map
        from repro.kernels.ops import interpret_mode
        from repro.kernels.shed_partition import shed_partition
        n = keys.shape[0]
        # (8, 128) lane-shaped blocks — the native f32/i32 TPU tile;
        # the kernel pads ragged tails internally, so any batch budget
        # (chunk-aligned or not) takes the same code path.
        probe = functools.partial(shed_partition, budget_is_total=True,
                                  interpret=interpret_mode())
        if self._mesh is not None:
            # XLA cannot partition a Mosaic kernel: on a multi-device
            # evaluator mesh every device runs the probe over the
            # whole (small, replicated) batch.
            probe = shard_map(probe, mesh=self._mesh, in_specs=P(),
                              out_specs=P())
        tier, cval, rank = probe(keys, valid, cache["keys"],
                                 cache["values"], u_capacity, u_threshold,
                                 budget_total)
        # Safety on a too-small max_evals: overflow evals fall back to
        # the prior tier (no-drop) instead of silently scoring 0. The
        # default max_evals = batch capacity can never overflow.
        tier = jnp.where((rank >= max_evals) & (tier == TIER_EVAL),
                         TIER_PRIOR, tier)
        idx, eval_valid = eval_indices_from_rank(rank, max_evals)
        gidx = jnp.minimum(idx, n - 1)              # clamp pad slots
        sub = jax.tree.map(lambda a: a[gidx], features)
        scores = self._apply(eval_params, sub)      # (max_evals,)
        scattered = jnp.zeros((n,), jnp.float32).at[idx].set(
            jnp.where(eval_valid, scores.astype(jnp.float32), 0.0),
            mode="drop")
        prior_vals = AT.query(prior, buckets)
        trust = combine_trust(tier, scattered, cval, prior_vals)
        evald = tier == TIER_EVAL
        new_cache = TC.insert(cache, keys, trust, evald)
        new_prior = AT.update(prior, buckets, trust, evald,
                              ewma=self.cfg.prior_ewma)
        return (trust, tier, jnp.sum(evald.astype(jnp.int32)),
                new_cache, new_prior)

    # -- stage / dispatch / finish --------------------------------------------
    def stage(self, item_keys: np.ndarray, buckets: np.ndarray,
              features, n_valid: Optional[int] = None) -> StagedBatch:
        """Front half of the fused step: ONE host->device transfer per
        batch (the host path re-gathers from the feature pytree once
        per chunk). The copies are enqueued asynchronously, so under a
        depth-k executor the transfer of batch N+2 runs behind batch
        N's in-flight compute — the transfer half of the pipeline."""
        t_start = self._now()
        wall_start = time.monotonic()
        n_total = len(item_keys)
        n = n_total if n_valid is None else int(n_valid)
        valid = np.zeros((n_total,), bool)
        valid[:n] = True
        if self.feature_sharding is not None:
            sharding = (self.feature_sharding(features)
                        if callable(self.feature_sharding)
                        else self.feature_sharding)
            feats_j = jax.device_put(features, sharding)
        else:
            feats_j = jax.tree.map(jnp.asarray, features)
        return StagedBatch(
            item_keys=np.asarray(item_keys),
            keys_j=jnp.asarray(item_keys, jnp.uint32),
            buckets_j=jnp.asarray(buckets, jnp.int32),
            valid_j=jnp.asarray(valid),
            feats_j=feats_j,
            n=n, n_total=n_total, t_start=t_start,
            wall_start=wall_start)

    def dispatch_staged(self, staged: StagedBatch) -> PendingShed:
        """Back half: launch the jitted shedding step on staged
        buffers; returns a handle whose ``.result()`` materializes the
        :class:`ShedResult`. With a ``SimClock`` the handle resolves
        eagerly (deterministic sequential timeline)."""
        n, n_total = staged.n, staged.n_total
        ucap, uthr = self.monitor.parameters()
        regime = classify(n, ucap, uthr)
        deadline_eff = effective_deadline(
            n, ucap, uthr, deadline_s=self.cfg.deadline_s,
            overload_deadline_s=self.cfg.overload_deadline_s,
            weight=self._vh_weight())
        # Same budget math as shed_plan: rate * effective deadline.
        budget_total = int(np.floor(
            ucap / self.cfg.deadline_s * deadline_eff))
        max_evals = self.max_evals or n_total

        # First sight of a work shape is jit warmup — the SAME
        # exclusion rule the host chunk loop applies (WarmupGate), so
        # both drain modes feed the LoadMonitor comparably.
        warm = self._warmup.warm(
            WarmupGate.signature(n_total, staged.feats_j)
            + (max_evals,))
        trust, tier, n_evald, self.cache, self.prior = self._step(
            self.cache, self.prior, self._eval_params, staged.keys_j,
            staged.buckets_j, staged.valid_j, staged.feats_j, ucap, uthr,
            budget_total, max_evals=max_evals)
        pending = PendingShed(self, trust, tier, n_evald,
                              t_start=staged.t_start,
                              wall_start=staged.wall_start,
                              n=n, regime=regime,
                              deadline_eff=deadline_eff,
                              skip_observe=not warm,
                              item_keys=staged.item_keys)
        if self.sim_clock is not None:
            pending.result()
        return pending

    def process_async(self, item_keys: np.ndarray, buckets: np.ndarray,
                      features, n_valid: Optional[int] = None
                      ) -> PendingShed:
        """Stage + dispatch in one call (the DrainExecutor's entry
        point; staging still runs ahead of the step's device slot)."""
        return self.dispatch_staged(
            self.stage(item_keys, buckets, features, n_valid=n_valid))

    def _finish(self, p: PendingShed) -> ShedResult:
        t_entry = time.monotonic()
        ready_at_entry = p.is_ready()   # stamps _wall_ready if so
        trust = np.asarray(p._trust)                # sync point
        tier = np.asarray(p._tier)
        n_evald = int(p._n_evald)
        wall_end = time.monotonic()
        if self.sim_clock is not None:
            self.sim_clock.charge_probe()
            self.sim_clock.charge_eval(n_evald)
        elif n_evald and not p._skip_observe:
            # Marginal service window: from the LATER of this batch's
            # dispatch and the previous observation, to the batch's
            # COMPLETION. Under a depth-k window the naive dispatch-to-
            # materialize span covers several batches' device work (and,
            # across ``flush=False`` drain calls, arbitrary caller idle
            # time), which would deflate the rate — and Ucapacity — in
            # proportion to the depth. Completion is taken from the
            # earliest ``is_ready`` stamp (the executor checks the
            # window head at poll AND at every submit, so busy loops
            # stamp at loop cadence), or from the sync we just paid
            # when the step was genuinely still running. A batch that
            # finished at some unknown earlier moment (ready on entry,
            # never observed) falls back to the entry time — an
            # overestimate whose damage LoadMonitor bounds with its
            # symmetric rate clamp.
            if p._wall_ready is not None \
                    and p._wall_ready < t_entry - 1e-6:
                completed = p._wall_ready       # stamped earlier
            elif not ready_at_entry:
                completed = wall_end            # we blocked: honest end
            else:
                completed = t_entry             # bounded overestimate
            base = max(p._wall_start, self._last_obs_wall)
            if completed > base:
                self.monitor.observe(n_evald, completed - base)
                self._last_obs_wall = completed
        rt = self._now() - p._t_start
        result = ShedResult(
            trust=trust, tier=tier, regime=p._regime,
            response_time_s=rt, deadline_eff_s=p._deadline_eff,
            n_evaluated=n_evald,
            n_cached=int((tier == TIER_CACHED).sum()),
            n_prior=int((tier == TIER_PRIOR).sum()),
            uload=p._n)
        if self.adaptive is not None:
            self.adaptive.observe(result)
        if self.on_shed is not None and p._item_keys is not None:
            self.on_shed(p._item_keys, result)
        return result

    # -- synchronous API (drop-in for LoadShedder.process) --------------------
    def process(self, item_keys: np.ndarray, buckets: np.ndarray,
                features, n_valid: Optional[int] = None) -> ShedResult:
        return self.process_async(item_keys, buckets, features,
                                  n_valid=n_valid).result()
