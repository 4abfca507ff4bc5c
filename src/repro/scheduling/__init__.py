"""Priority-aware admission & scheduling in front of the Load Shedder.

Request lifecycle (who owns each hop). The front half is the retrieval
stage (``repro.retrieval``, optional — engines fed pre-retrieved
candidate sets start at *arrive*):

    parse    retrieval.text                 tokenize -> common-word
       |                                    filter -> stem the raw
       |                                    query string
    index    retrieval.index / .shard       blocked inverted-index
       |                                    build (per-block postings,
       |                                    sequential merge) held as
       |                                    doc-partitioned IndexShards
       |                                    owned by replicas through
       |                                    the consistent-hash ring
       |                                    (``"docpart:p"`` keys);
       |                                    dense static-shape postings
       |                                    with precomputed BM25
       |                                    per-posting weights,
       |                                    collection-GLOBAL stats so
       |                                    a sharded fleet ranks like
       |                                    one big index
    retrieve ServingEngine.enqueue_query    jitted BM25 segment-sum ->
       |     (retrieval.CorpusSearcher)     Pallas ``topk_select``
       |                                    per shard, scatter-gather
       |                                    merge (score desc, doc id
       |                                    asc) picks the candidate
       |                                    set; measured retrieve
       |                                    latency feeds the
       |                                    LoadMonitor under the
       |                                    WarmupGate rule so
       |                                    Ucapacity reflects the
       |                                    whole pipeline
    scatter  repro.fanout                   quorum-gather[hedged]
       |     (FanoutSearcher)               (``TrustIRConfig.
       |                                    fanout_*``): the fan-out
       |                                    answers at the first-
       |                                    ``quorum_k``-of-n shard
       |                                    completion; late stripes
       |                                    are prior-answered from
       |                                    the stripe answer cache
       |                                    (trust already on file) or
       |                                    the downstream trust
       |                                    prior — never dropped; a
       |                                    straggling shard probe
       |                                    races a twin on a sibling's
       |                                    MIRROR stripes (selective
       |                                    replication of persistently
       |                                    slow shards, EWMA-picked,
       |                                    bounded, dropped on
       |                                    recovery), charged to the
       |                                    same fleet hedge budget;
       |                                    ``quorum_k == n`` is
       |                                    bit-identical to the full
       |                                    gather
       |
    arrive   ServingEngine.enqueue          stamp arrival + SLO deadline
       |
    admit    scheduling.priorities          per-regime priority ladder
       |     scheduling.ratelimit           per-tenant token buckets
       |                                    (reject => explicit Response
       |                                     from the average-trust
       |                                     prior, admitted=False)
    quarantine scheduling.quarantine        per-WORK-SIGNATURE circuit
       |     (PoisonQuarantine)             breaker in front of the
       |                                    ladder: after ``k`` executor
       |                                    errors on batches containing
       |                                    a signature (md5 of the
       |                                    candidate-key prefix), new
       |                                    matching requests are
       |                                    prior-answered
       |                                    (REASON_QUARANTINED) instead
       |                                    of queued — a query of death
       |                                    costs O(k) evaluator crashes
       |                                    per replica, not one per
       |                                    arrival; after
       |                                    ``quarantine_probe_after_s``
       |                                    a HALF-OPEN timed probe
       |                                    admits ONE matching request,
       |                                    and a clean completion
       |                                    closes the breaker (a
       |                                    deployed evaluator fix
       |                                    un-quarantines itself);
       |                                    innocent signatures struck
       |                                    by sharing a failed batch
       |                                    decay back to zero on any
       |                                    clean completion
       |                                    (``TrustIRConfig.
       |                                    quarantine_k`` — 0 disables)
    queue    scheduling.queues              EDF within class, strict
       |                                    priority across classes,
       |                                    static-capacity backpressure
    batch    scheduling.batcher             coalesce queued candidate
       |                                    sets into one padded,
       |                                    budget-shaped micro-batch
    execute  scheduling.executor            ONE DrainExecutor sequences
       |     (DrainExecutor)                every path: a depth-k
       |                                    in-flight window
       |                                    (``TrustIRConfig.
       |                                    pipeline_depth``; depth 1 =
       |                                    sync-per-drain, depth >= 2
       |                                    keeps the window open
       |                                    ACROSS drain calls so batch
       |                                    N+2 forms + transfers while
       |                                    N computes and N+1 waits;
       |                                    with ``TrustIRConfig.
       |                                    adaptive_depth`` a bounded
       |                                    hysteresis controller
       |                                    (cluster.depth) retunes the
       |                                    window each drain tick —
       |                                    deepen under backlog,
       |                                    shallow when queue delay
       |                                    eats the deadline, reading
       |                                    the capacity planner's
       |                                    STAGE_QUEUE p99 when no
       |                                    fresh sample exists — one
       |                                    step at a time between
       |                                    ``adaptive_depth_min`` and
       |                                    the static config, which
       |                                    stays the CLAMP; streak
       |                                    votes + cooldown mean
       |                                    alternating pressure never
       |                                    flaps the depth),
       |                                    per-batch completion
       |                                    callbacks (results, Trust-
       |                                    DB/prior fold-back, Load-
       |                                    Monitor observations land
       |                                    as EACH batch finishes, and
       |                                    ``poll`` folds ready
       |                                    batches without blocking),
       |                                    and exception-mid-window
       |                                    rescue (a failed batch is
       |                                    prior-answered; the rest of
       |                                    the window still lands)
    shed     core.shedder                   ONE three-regime shedding
       |     (drain_mode="host")            decision per micro-batch
       |                                    (EVAL / CACHED / PRIOR tiers)
       |                                    via the host chunk loop with
       |                                    a wall-clock deadline
       |                                    (sequential: the executor
       |                                    runs it eagerly), OR
       |     core.fused_shedder             shed[fused]
       |     (drain_mode="fused")           (``TrustIRConfig.drain_mode``)
       |                                    ONE jitted device step per
       |                                    batch: Pallas shed_partition
       |                                    probe+tier ((8,128)-lane
       |                                    blocks, ragged tails padded
       |                                    in-kernel) with compacted
       |                                    eval indices, static-shape
       |                                    gather, batched evaluator,
       |                                    scatter, cache/prior
       |                                    fold-back — staged (host->
       |                                    device transfer) then
       |                                    dispatched, both async; the
       |                                    Trust-DB probe is an XLA
       |                                    gather of each key's set
       |                                    (cache in HBM) feeding the
       |                                    kernel (8,128) per-way
       |                                    candidate blocks; a mesh-sharded
       |                                    evaluator (serving.
       |                                    evaluators.
       |                                    make_sharded_evaluator)
       |                                    hands the engine its
       |                                    ``feature_sharding`` so
       |                                    stage() device_puts each
       |                                    batch's gathered features
       |                                    with the evaluator's INPUT
       |                                    sharding — batch N+2's
       |                                    transfer overlaps the
       |                                    sharded forward of batch N
       |                                    inside the same depth-k
       |                                    window, exactly-once
       |                                    fold-back unchanged
    respond  scheduling.scheduler           split per-request Responses
                                            per completed batch; hedged
                                            re-dispatch via
                                            distribution.fault_tolerance

With a multi-replica fleet (``repro.cluster``) the map gains a layer in
FRONT of this one — ``route -> admit -> steal -> drain -> hedge ->
gossip -> join/leave``:

    route    cluster.routing                consistent-hash ring picks
       |                                    the tenant's replica shard
    admit    (this subsystem, per replica)  the ladder above, against
       |                                    THAT replica's regime
    steal    cluster.coordinator            hot bank -> idle sibling,
       |                                    non-head entry of the
       |                                    lowest class picked by
       |                                    estimated eval cost (items
       |                                    x Trust-DB miss probability
       |                                    — cache-cold work migrates,
       |                                    cache-hot work stays warm;
       |                                    EDF heads never reorder)
    drain    cluster.coordinator            round-robin micro-batches
       |                                    across replicas, one
       |                                    DrainExecutor window per
       |                                    replica spanning rounds
       |                                    (device steps overlap the
       |                                    next round's scans); each
       |                                    round POLLS completed
       |                                    batches first so steal/
       |                                    hedge/autoscale read fresh
       |                                    stats, not one batch late;
       |                                    decode requests only occupy
       |                                    batch budget when a
       |                                    KVCachePool slot is
       |                                    claimable
    hedge    distribution.fault_tolerance   stuck requests race a twin
       |                                    on a REAL backup replica;
       |                                    first completion wins, the
       |                                    loser is deduplicated
       |                                    fleet-wide
    gossip   cluster.gossip                 fresh Trust-DB cache fills
       |                                    reach siblings on a bounded
       |                                    budget (hot URLs evaluated
       |                                    once fleet-wide) — either
       |                                    O(n^2) broadcast (default)
       |                                    or epidemic peer-sampling
       |                                    push (O(log n) fanout per
       |                                    delta, relayed) + one
       |                                    anti-entropy digest pull per
       |                                    round, O(n log n) messages
       |                                    total (``TrustIRConfig.
       |                                    gossip_mode``)
    forecast cluster.capacity               feedforward autoscaling:
       |                                    sliding-window NHPP rate
       |                                    estimate of the arrival
       |                                    curve, extrapolated
       |                                    warmup_lead_s ahead and
       |                                    folded into the SAME
       |                                    watermark membership vote
       |                                    (shared cooldown) so
       |                                    scale-up fires BEFORE the
       |                                    queue-pressure breach; the
       |                                    per-stage ServiceTimeModel
       |                                    it fits from live drain
       |                                    stats also answers what-if
       |                                    predict(n, depth, batch) ->
       |                                    (throughput, p99)
    prewarm  cluster.replica                forecast-triggered joins
       |                                    jit-compile the micro-batch
       |                                    shape on synthetic keys
       |                                    BEFORE the ring unfences
       |                                    the new replica — its first
       |                                    real batch is never cold
       |                                    (cache/prior/clock snapshot
       |                                    -restored around the warm
       |                                    pass, so no serving state
       |                                    leaks from prewarm traffic)
    restart  cluster.coordinator            coordinated rolling
       |                                    restarts: ring-disjoint
       |                                    waves (no replica restarts
       |                                    alongside the sibling that
       |                                    would inherit its keys),
       |                                    fence + queue handoff +
       |                                    warm-cache export per wave,
       |                                    autoscaler membership votes
       |                                    held for the sweep, restart
       |                                    counters banked so fleet
       |                                    stats survive the engine
       |                                    rebuild
    join/    cluster.coordinator            runtime membership: fence +
    leave                                   drain-and-handoff (EDF
                                            order) on leave — queued
                                            work, the top-K freshest
                                            Trust-DB entries (warm
                                            handoff via the gossip
                                            apply_trust_deltas path),
                                            AND the doc-partition index
                                            stripes remap_diff claims
                                            (postings travel
                                            export_docs -> absorb; a
                                            crash rebuilds them from
                                            the corpus on survivors) —
                                            admission-journal replay on
                                            crash, autoscaler-voted
                                            joins and leaves between
                                            min/max replica bounds

No *admitted* request is ever dropped: every item leaves with a trust
value (paper §5 invariant, preserved across the batching layer), every
rejection is an observable ``Response`` with a reason — never silence —
and fleet-wide each request id yields EXACTLY one ``Response`` even
when its hedged twin also ran.
"""
from repro.scheduling.batcher import (MicroBatch, MicroBatcher,
                                      to_fused_inputs)
from repro.scheduling.executor import DrainExecutor
from repro.scheduling.priorities import (AdmissionPolicy, Priority,
                                         REASON_QUARANTINED,
                                         REASON_QUEUE_FULL,
                                         REASON_RATE_LIMITED,
                                         REASON_SHED_LOW_HEAVY,
                                         REASON_SHED_LOW_VERY_HEAVY,
                                         REASON_SHED_NORMAL_VERY_HEAVY)
from repro.scheduling.quarantine import (PoisonQuarantine,
                                         QuarantineStats,
                                         work_signature)
from repro.scheduling.queues import (EDFQueue, PriorityQueueBank,
                                     QueuedRequest)
from repro.scheduling.ratelimit import TenantRateLimiter, TokenBucket
from repro.scheduling.scheduler import (Request, Response, Scheduler,
                                        SchedulerConfig, SchedulerStats)

__all__ = [
    "AdmissionPolicy", "Priority",
    "REASON_QUARANTINED", "REASON_QUEUE_FULL", "REASON_RATE_LIMITED",
    "REASON_SHED_LOW_HEAVY", "REASON_SHED_LOW_VERY_HEAVY",
    "REASON_SHED_NORMAL_VERY_HEAVY",
    "EDFQueue", "PriorityQueueBank", "QueuedRequest",
    "TenantRateLimiter", "TokenBucket",
    "DrainExecutor",
    "MicroBatch", "MicroBatcher", "to_fused_inputs",
    "PoisonQuarantine", "QuarantineStats", "work_signature",
    "Request", "Response", "Scheduler", "SchedulerConfig",
    "SchedulerStats",
]
