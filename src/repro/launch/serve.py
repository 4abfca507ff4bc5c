"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Boots an N-replica serving fleet (``repro.cluster``) with the chosen
trust-evaluator backbone, calibrates Ucapacity/Uthreshold to the
measured evaluator throughput (the Load Monitor's job, §4), and serves
a synthetic request stream through the priority scheduler
(``repro.scheduling``): requests arrive with a CRITICAL/HIGH/NORMAL/LOW
mix, route to a replica by tenant (consistent hashing), are admitted
per-regime, queue EDF, rebalance by work-stealing, and drain as
budget-shaped micro-batches round-robin across replicas. ``--replicas
1`` (the default) is the degenerate single-host path; ``--sync``
restores the original per-request synchronous path; ``--adaptive``
enables the §7 adaptive Very-Heavy controller.

``--corpus N`` attaches the ``repro.retrieval`` front end: a
deterministic N-doc Zipf corpus is indexed into ``--index-shards``
doc-partitions owned by replicas through the consistent-hash ring, and
requests arrive as *raw query strings* — parse -> BM25 -> Pallas top-k
picks each candidate set — instead of pre-retrieved key arrays.

The evaluator runs at its smoke width (CPU runs, CI) unless
``--full-width`` builds the published configuration. The run exits
non-zero when a batch had to be rescued from the prior
(``executor_error``): a serving path that only answers from the prior
is broken, however fast its scoreboard looks. ``chip_smoke.py`` drives
this same code path in-process through :func:`launch`, :func:`serve`
and :func:`report`.
"""
from __future__ import annotations

import argparse
import sys
import time
from dataclasses import dataclass
from typing import Any, Callable, List, Optional

import numpy as np


_EPILOG = """\
chaos trace replay (--trace)
----------------------------
--trace SECONDS replays a deterministic chaos trace (repro.chaos)
against the fleet instead of the synthetic request loop: diurnal +
flash-crowd arrivals with Zipf tenant skew and hot-URL floods, driven
on simulated per-replica clocks calibrated to the measured evaluator
throughput of --arch. The fault timeline is scripted by the
--chaos-* flags; everything derives from --seed, so the same command
line replays bit-identically within a process.

  --trace 6 --replicas 8                  clean diurnal trace
  --trace 6 --chaos-flash 5               + flash crowd x5 mid-trace
  --trace 6 --chaos-poison 4 \\
           --quarantine-k 3               + query-of-death flood; the
                                          per-signature breaker
                                          prior-answers repeats after
                                          3 evaluator crashes
  --trace 6 --chaos-crash 3               + 3 replicas crash the same
                                          tick at 70% of the trace
                                          (journal replay re-homes
                                          their admitted work)
  --trace 6 --chaos-restart               + coordinated rolling
                                          restart sweep at 85%
  --gossip --gossip-mode epidemic         O(log n)-fanout epidemic
                                          push + anti-entropy pull
                                          instead of O(n^2) broadcast
  --trace 6 --max-replicas 6 \\
           --forecast                     feedforward capacity planner:
                                          extrapolate the arrival curve
                                          (repro.cluster.capacity) and
                                          join replicas --warmup-lead-s
                                          BEFORE the predicted breach,
                                          jit-prewarmed so the first
                                          real batch is never cold

The chaos gates themselves (no-drop, p99, O(k) quarantine containment,
O(n log n) gossip, bit-determinism) run in benchmarks/bench_fleet.py.

heavyweight evaluators on the fused drain (config knobs)
--------------------------------------------------------
``TrustIRConfig.evaluator_arch`` names the trust backbone ('bst',
'dlrm-mlperf', 'gcn-cora', 'gemma2-2b', 'mind', 'moonshot-v1-16b-a3b',
'qwen2.5-14b', 'smollm-135m', 'two-tower-retrieval'); --arch maps to
it here. Production-scale backbones stay on the fused hot path via:

  --sharded (needs --drain-mode fused)    mesh-shard the evaluator
                                          with serving.evaluators.
                                          make_sharded_evaluator:
                                          params placed by
                                          distribution.sharding specs,
                                          each micro-batch's features
                                          staged with the evaluator's
                                          INPUT sharding so batch
                                          N+2's host->device transfer
                                          overlaps the sharded forward
                                          of batch N inside the
                                          depth-k window
  --adaptive-depth                        bounded hysteresis
                                          controller (cluster.depth)
                                          retunes the DrainExecutor
                                          window each drain tick
                                          between adaptive_depth_min
                                          and --pipeline-depth (the
                                          static config stays the
                                          CLAMP); deepen under
                                          backlog, shallow when queue
                                          delay eats the deadline;
                                          TrustIRConfig.
                                          adaptive_depth_hysteresis /
                                          _cooldown_ticks /
                                          _backlog_batches /
                                          _latency_frac tune the
                                          no-flap guarantees
  TrustIRConfig.cache_ways_leading        Trust-DB cache layout:
                                          True (default) stores
                                          (ways, slots) so the probe
                                          gathers one row per way;
                                          False restores the legacy
                                          (slots, ways) layout
  TrustIRConfig.fanout_adaptive_quorum    let the coordinator walk
                                          fanout_quorum_k with the
                                          offered regime (tighten
                                          toward n when Normal, relax
                                          toward the configured floor
                                          when Very Heavy); quorum_k
                                          == n stays bit-identical to
                                          the full gather
"""


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        description=__doc__, epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--arch", default="smollm-135m")
    p.add_argument("--full-width", action="store_true",
                   help="build the evaluator at its published width "
                        "(default: the smoke width, for CPU runs)")
    p.add_argument("--n-requests", type=int, default=10)
    p.add_argument("--deadline-ms", type=float, default=50.0)
    p.add_argument("--overload-deadline-ms", type=float, default=100.0)
    p.add_argument("--adaptive", action="store_true")
    p.add_argument("--sync", action="store_true",
                   help="per-request synchronous submit() path")
    p.add_argument("--drain-mode", choices=("host", "fused"),
                   default="host",
                   help="micro-batch executor: host chunk loop "
                        "(wall-clock deadline) or the fused "
                        "one-device-step-per-batch drain")
    p.add_argument("--sharded", action="store_true",
                   help="mesh-sharded evaluator windows (needs "
                        "--drain-mode fused): place evaluator params "
                        "and each staged micro-batch's features with "
                        "distribution.sharding specs (see epilog)")
    p.add_argument("--adaptive-depth", action="store_true",
                   help="adaptive DrainExecutor window: a bounded "
                        "hysteresis controller retunes the in-flight "
                        "depth per drain tick; --pipeline-depth "
                        "becomes the clamp (see epilog)")
    p.add_argument("--pipeline-depth", type=int, default=2,
                   help="DrainExecutor in-flight window (fused drain): "
                        "1 syncs every drain call (the PR-3 "
                        "behaviour); >= 2 keeps that many batches in "
                        "flight across drain calls, overlapping device "
                        "compute with admission + batch formation")
    p.add_argument("--replicas", type=int, default=1,
                   help="serving fleet size (1 = single host)")
    p.add_argument("--min-replicas", type=int, default=0,
                   help="elastic lower bound: the autoscaler may drain "
                        "the fleet down to this many replicas (0 = "
                        "membership fixed at --replicas)")
    p.add_argument("--max-replicas", type=int, default=0,
                   help="elastic upper bound: the autoscaler may join "
                        "replicas at runtime up to this many (0 = "
                        "membership fixed at --replicas)")
    p.add_argument("--forecast", action="store_true",
                   help="feedforward autoscaling: extrapolate the "
                        "arrival curve and join prewarmed replicas "
                        "--warmup-lead-s before the predicted breach "
                        "instead of waiting for queue pressure (needs "
                        "--max-replicas; see --trace epilog)")
    p.add_argument("--warmup-lead-s", type=float, default=0.5,
                   help="forecast horizon: how far ahead the planner "
                        "extrapolates the arrival rate — roughly the "
                        "join + jit-prewarm time of one replica")
    p.add_argument("--gossip", action="store_true",
                   help="cross-replica Trust-DB gossip: broadcast "
                        "fresh cache fills to sibling replicas so hot "
                        "URLs are evaluated once fleet-wide")
    p.add_argument("--gossip-mode", choices=("broadcast", "epidemic"),
                   default="broadcast",
                   help="delta dissemination: every-sibling broadcast "
                        "(O(n^2) messages/round) or epidemic "
                        "peer-sampling push + anti-entropy pull "
                        "(O(n log n))")
    p.add_argument("--quarantine-k", type=int, default=0,
                   help="poison-pill circuit breaker: quarantine a "
                        "work signature after this many executor "
                        "errors (0 disables; see --trace epilog)")
    p.add_argument("--trace", type=float, default=0.0,
                   help="replay a chaos trace of this many simulated "
                        "seconds instead of the request loop (see "
                        "epilog)")
    p.add_argument("--chaos-qps", type=float, default=60.0,
                   help="chaos trace base arrival rate")
    p.add_argument("--chaos-flash", type=float, default=0.0,
                   help="flash-crowd rate multiplier over the middle "
                        "of the trace (0 = no flash crowd)")
    p.add_argument("--chaos-poison", type=float, default=0.0,
                   help="query-of-death arrivals/s during the poison "
                        "window (0 = no poison)")
    p.add_argument("--chaos-crash", type=int, default=0,
                   help="replicas crashing on the same tick at 70%% of "
                        "the trace (0 = no regional failure)")
    p.add_argument("--chaos-restart", action="store_true",
                   help="coordinated rolling-restart sweep at 85%% of "
                        "the trace")
    p.add_argument("--hedge-after-ms", type=float, default=0.0,
                   help="cluster hedge latency (0 disables; needs "
                        "--replicas >= 2)")
    p.add_argument("--drain-every", type=int, default=4,
                   help="drain a micro-batch every N enqueues")
    p.add_argument("--corpus", type=int, default=0,
                   help="attach the retrieval front end: synthetic "
                        "Zipf corpus of this many docs; requests "
                        "become raw query strings (0 = requests "
                        "arrive pre-retrieved, the original path)")
    p.add_argument("--index-shards", type=int, default=0,
                   help="doc-partition count for the inverted index "
                        "(0 = config default); partitions map to "
                        "replicas through the consistent-hash ring")
    p.add_argument("--quorum-k", type=int, default=0,
                   help="tail-tolerant gather (repro.fanout, needs "
                        "--corpus): answer at the first k of n shard "
                        "completions, prior-answering late stripes "
                        "(0 = wait for every shard)")
    p.add_argument("--shard-hedge-ms", type=float, default=0.0,
                   help="per-shard probe hedge latency: a stripe "
                        "probe slower than this races a twin on a "
                        "sibling's mirror (0 disables)")
    p.add_argument("--straggle-mult", type=float, default=0.0,
                   help="pin a persistent service-time multiplier on "
                        "replica r0's shard (straggler injection demo "
                        "for --quorum-k/--shard-hedge-ms; 0 = off)")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


@dataclass
class Launch:
    """A booted, jit-warm serving stack: what :func:`serve` drives and
    :func:`report` summarizes (``chip_smoke.py`` inspects it too)."""
    args: argparse.Namespace
    cfg: Any                      # TrustIRConfig
    evaluate: Callable            # serving.evaluators.Evaluator
    make_features: Callable
    eng: Any                      # ServingEngine (--sync) or the fleet
    retrieval: Any = None         # retrieval.CorpusRetrieval
    queries: Any = None           # retrieval.ZipfQueryModel


def calibrate(args: argparse.Namespace):
    """Build the evaluator and size the shedder to its measured rate:
    ``(evaluate, make_features, feature_sharding, rate, cfg)``."""
    from repro.configs.base import TrustIRConfig
    from repro.serving.evaluators import (make_evaluator,
                                          make_sharded_evaluator)

    smoke = not args.full_width
    feature_sharding = None
    if args.sharded:
        if args.drain_mode != "fused":
            raise SystemExit("--sharded shards the fused evaluator "
                             "window; add --drain-mode fused")
        se = make_sharded_evaluator(args.arch, smoke=smoke)
        ev, mk = se.evaluate, se.make_features
        feature_sharding = se.feature_sharding
    else:
        ev, mk = make_evaluator(args.arch, smoke=smoke)

    feats64 = mk(64)
    np.asarray(ev(feats64))
    t0 = time.perf_counter()
    np.asarray(ev(feats64))
    rate = 64 / max(time.perf_counter() - t0, 1e-6)
    dl = args.deadline_ms / 1e3
    odl = args.overload_deadline_ms / 1e3
    n_rep = max(args.replicas, 1)
    elastic = args.max_replicas > 0
    cfg_kw = dict(u_capacity=max(int(rate * dl), 16),
                  u_threshold=max(int(rate * (odl - dl)), 8),
                  deadline_s=dl, overload_deadline_s=odl,
                  chunk_size=64, n_replicas=n_rep,
                  min_replicas=args.min_replicas,
                  max_replicas=args.max_replicas,
                  gossip=args.gossip,
                  gossip_mode=args.gossip_mode,
                  quarantine_k=max(args.quarantine_k, 0),
                  pipeline_depth=max(args.pipeline_depth, 1),
                  adaptive_depth=args.adaptive_depth,
                  forecast=args.forecast,
                  warmup_lead_s=max(args.warmup_lead_s, 0.0))
    if args.corpus > 0:
        cfg_kw["corpus_docs"] = args.corpus
        if args.index_shards > 0:
            cfg_kw["index_partitions"] = args.index_shards
        cfg_kw["fanout_quorum_k"] = max(args.quorum_k, 0)
        cfg_kw["fanout_hedge_after_s"] = \
            max(args.shard_hedge_ms, 0.0) / 1e3
    cfg = TrustIRConfig(**cfg_kw)
    print(f"{args.arch}: {rate:,.0f} items/s -> Ucap={cfg.u_capacity} "
          f"Uthr={cfg.u_threshold} deadline={dl * 1e3:.0f}ms "
          f"(overload {odl * 1e3:.0f}ms)"
          + (" [full width]" if args.full_width else " [smoke width]")
          + (" [adaptive]" if args.adaptive else "")
          + (" [sync]" if args.sync
             else f" [scheduled x{n_rep} replica(s)]")
          + (f" [elastic {max(args.min_replicas, 1)}"
             f"..{args.max_replicas}]" if elastic else "")
          + (" [gossip]" if args.gossip else "")
          + f" [drain={args.drain_mode}"
          + (f" depth={cfg.pipeline_depth}]"
             if args.drain_mode == "fused" else "]"))
    return ev, mk, feature_sharding, rate, cfg


def launch(args: argparse.Namespace) -> Launch:
    """Calibrate, attach the retrieval front end (``--corpus``), boot
    the engine or fleet, and warm every jit shape the run will use."""
    import jax.numpy as jnp
    from repro.cluster import ClusterConfig, ClusterCoordinator
    from repro.core.adaptive import AdaptiveWeightController
    from repro.serving.engine import ServingEngine

    ev, mk, feature_sharding, _, cfg = calibrate(args)
    odl = args.overload_deadline_ms / 1e3
    n_rep = max(args.replicas, 1)
    elastic = args.max_replicas > 0

    def evaluate(chunk):                  # host chunk-loop protocol
        return np.asarray(ev({k: jnp.asarray(v)
                              for k, v in chunk.items()}))

    retrieval = queries = fanout_model = None
    if args.corpus > 0:
        from repro.retrieval import (CorpusRetrieval, SyntheticCorpus,
                                     ZipfQueryModel)

        def doc_features(docs):    # retrieved docs -> backbone features
            return mk(len(docs),
                      fseed=int(docs[0]) % 1_000_000 if len(docs) else 0)

        t0 = time.perf_counter()
        corpus = SyntheticCorpus(n_docs=cfg.corpus_docs,
                                 vocab_size=cfg.corpus_vocab,
                                 zipf_a=cfg.corpus_zipf_a,
                                 seed=cfg.corpus_seed)
        retrieval = CorpusRetrieval(corpus,
                                    n_partitions=cfg.index_partitions,
                                    block_docs=cfg.index_block_docs,
                                    feature_fn=doc_features)
        queries = ZipfQueryModel.for_corpus(corpus, seed=args.seed + 1)
        print(f"retrieval: {corpus.n_docs} docs / vocab "
              f"{corpus.vocab_size} -> {cfg.index_partitions} "
              f"doc-partitions, top-k={cfg.retrieve_top_k} "
              f"({time.perf_counter() - t0:.2f}s corpus+stats)")
        fan_on = cfg.fanout_quorum_k > 0 or cfg.fanout_hedge_after_s > 0
        if fan_on:
            from repro.fanout import ShardServiceModel
            fanout_model = ShardServiceModel(seed=args.seed)
            if args.straggle_mult > 1.0:
                fanout_model.set_persistent("r0", args.straggle_mult)
            print(f"fanout: quorum_k={cfg.fanout_quorum_k or 'n'} "
                  f"shard-hedge={args.shard_hedge_ms:.1f}ms "
                  + (f"straggler r0 x{args.straggle_mult:.0f}"
                     if args.straggle_mult > 1.0 else "no straggler"))

    if args.sync:
        retriever = None
        if retrieval is not None:
            # single host owns every doc-partition in one shard
            retriever = retrieval.searcher(
                [retrieval.build_shard(range(cfg.index_partitions))])
        eng = ServingEngine(cfg, evaluate, drain_mode=args.drain_mode,
                            evaluate_batch=ev,
                            retriever=retriever,
                            feature_sharding=feature_sharding)
        if args.adaptive:
            eng.shedder.adaptive = AdaptiveWeightController()
    else:
        # N-replica fleet; n_replicas=1 is the degenerate single host.
        eng = ClusterCoordinator(
            cfg, evaluate,
            cluster_cfg=ClusterConfig(
                hedge_after_s=args.hedge_after_ms / 1e3,
                autoscale=n_rep > 1 or elastic,
                min_replicas=args.min_replicas,
                max_replicas=args.max_replicas,
                gossip=args.gossip,
                forecast=args.forecast,
                warmup_lead_s=max(args.warmup_lead_s, 0.0)),
            drain_mode=args.drain_mode,
            evaluate_batch=ev,
            retrieval=retrieval,
            fanout_model=fanout_model,
            feature_sharding=feature_sharding)
        if args.adaptive:
            for rep in eng.replicas:
                rep.engine.shedder.adaptive = AdaptiveWeightController()

    warm_shedders = ([eng.shedder] if args.sync
                     else [rep.engine.shedder for rep in eng.replicas])
    if queries is None:
        _, sizes, _ = _request_stream(args, args.n_requests)
        for n in sorted(set(int(s) for s in sizes)):  # warm jit
            for shedder in warm_shedders:  # every replica compiles NOW
                shedder.process(
                    np.arange(10**6, 10**6 + n, dtype=np.uint32),
                    np.zeros(n, np.int32), mk(n, fseed=999))
    # ... and the padded micro-batch shape the submit/drain path uses —
    # again per replica (the ring would route one warm tenant to ONE
    # replica; the rest would pay the batch-shape compile mid-run). In
    # corpus mode one real query per replica warms the whole front
    # half — index dense form, BM25 segment-sum, top-k kernel — plus
    # the evaluator batch shape (fixed warm string: sampling the query
    # model here would shift the serve stream's rng).
    warm_q = "term00001 term00002"
    if args.sync:
        if queries is not None:
            eng.enqueue_query(warm_q, slo_s=odl * 2.5)
        else:
            eng.enqueue(np.arange(1, 65, dtype=np.uint32),
                        np.zeros(64, np.int32), mk(64, fseed=998))
        eng.drain()
    else:
        for rep in eng.replicas:
            if queries is not None:
                rep.engine.enqueue_query(warm_q, slo_s=odl * 2.5)
            else:
                rep.engine.enqueue(np.arange(1, 65, dtype=np.uint32),
                                   np.zeros(64, np.int32),
                                   mk(64, fseed=998))
            rep.engine.drain()
        eng.drain()                  # collect warm responses, then drop
    eng.completed.clear()
    return Launch(args=args, cfg=cfg, evaluate=ev, make_features=mk,
                  eng=eng, retrieval=retrieval, queries=queries)


def _request_stream(args: argparse.Namespace, n_req: int):
    """``(rng, sizes, priority indices)`` of the request loop, all
    drawn from ``--seed`` (the rng goes on to draw the buckets)."""
    r = np.random.default_rng(args.seed)
    sizes = np.clip(r.zipf(1.4, size=n_req) * 64, 64, 4096)
    # Priority mix: mostly NORMAL, some HIGH/CRITICAL, a LOW tail.
    prios = r.choice(4, size=n_req, p=[0.1, 0.2, 0.5, 0.2])
    return r, sizes, prios


def serve(run: Launch, query_strings: Optional[List[str]] = None
          ) -> Optional[List[str]]:
    """The request loop. In corpus mode it serves ``query_strings``
    (default: ``--n-requests`` draws from the query model) and returns
    the query strings it served; otherwise synthetic pre-retrieved
    requests, returning None."""
    from repro.scheduling import Priority

    args, eng, queries, mk = run.args, run.eng, run.queries, \
        run.make_features
    odl = args.overload_deadline_ms / 1e3
    n_rep = max(args.replicas, 1)
    n_req = (len(query_strings) if query_strings is not None
             else args.n_requests)
    r, sizes, prios = _request_stream(args, n_req)
    prio_choices = [Priority.CRITICAL, Priority.HIGH, Priority.NORMAL,
                    Priority.LOW]
    served: List[str] = []

    for i, n in enumerate(int(s) for s in sizes):
        prio = prio_choices[int(prios[i])]
        if queries is not None:
            q = (query_strings[i] if query_strings is not None
                 else queries.sample())
            served.append(q)
            if args.sync:
                rid = eng.enqueue_query(q, slo_s=odl * 2.5,
                                        priority=prio)
                eng.drain()
                resp = next(rr for rr in reversed(eng.completed)
                            if rr.request_id == rid)
                sh = resp.shed
                print(f"  req {i:>3} q={q[:22]!r:<24} {prio.name:<9} "
                      f"{sh.regime.name:<11} "
                      f"{resp.latency_s * 1e3:7.1f} ms  "
                      f"eval {sh.n_evaluated:>5} cached "
                      f"{sh.n_cached:>5} prior {sh.n_prior:>5} "
                      f"{'SLO ok' if resp.met_slo else 'SLO MISS'}")
            else:
                eng.enqueue_query(q, slo_s=odl * 2.5, priority=prio,
                                  tenant=f"tenant{i % (4 * n_rep)}")
                if (i + 1) % args.drain_every == 0:
                    eng.drain(1)             # one batch (or round)
            continue
        keys = np.arange(i * 10_000 + 1, i * 10_000 + n + 1,
                         dtype=np.uint32)
        buckets = r.integers(0, 64, n).astype(np.int32)
        if args.sync:
            resp = eng.submit(keys, buckets, mk(n, fseed=i),
                              slo_s=odl * 2.5, priority=prio)
            s = resp.shed
            print(f"  req {i:>3} n={n:<5} {prio.name:<9} "
                  f"{s.regime.name:<11} {resp.latency_s * 1e3:7.1f} ms  "
                  f"eval {s.n_evaluated:>5} cached {s.n_cached:>5} "
                  f"prior {s.n_prior:>5} "
                  f"{'SLO ok' if resp.met_slo else 'SLO MISS'}")
        else:
            # Tenants rotate so the ring spreads them across replicas.
            eng.enqueue(keys, buckets, mk(n, fseed=i), slo_s=odl * 2.5,
                        priority=prio, tenant=f"tenant{i % (4 * n_rep)}")
            if (i + 1) % args.drain_every == 0:
                eng.drain(1)                 # one batch (or round)
    if not args.sync:
        eng.drain()
    return served if queries is not None else None


def report(run: Launch) -> int:
    """Print the responses, stats and P50/P99 scoreboard; the exit
    code is non-zero when any batch was rescued by the executor."""
    args, eng, retrieval = run.args, run.eng, run.retrieval
    st = eng.scheduler_stats()
    if not args.sync:
        for resp in eng.completed:
            s = resp.shed
            flag = ("REJECTED " + resp.reason if not resp.admitted
                    else ("SLO ok" if resp.met_slo else "SLO MISS"))
            print(f"  req {resp.request_id:>3} n={len(resp.trust):<5} "
                  f"{resp.priority.name:<9} {s.regime.name:<11} "
                  f"{resp.latency_s * 1e3:7.1f} ms  "
                  f"eval {s.n_evaluated:>5} cached {s.n_cached:>5} "
                  f"prior {s.n_prior:>5} {flag}")
        print(f"scheduler: {st['n_batches']} batches, mean fill "
              f"{st['mean_batch_fill']:.0f} items, "
              f"{st['n_rejected']} rejected {st['rejected_by_reason']}, "
              f"{st['n_hedges']} hedges")
        if "cluster" in st:
            c = st["cluster"]
            print(f"cluster: {len(eng.replicas)} replicas, "
                  f"{c['n_steals']} steals, {c['n_hedges']} "
                  f"cross-replica hedges, {c['n_twin_drops']} twins "
                  f"deduplicated, {c['n_joins']} joins / "
                  f"{c['n_leaves']} leaves")
            if "gossip" in st:
                g = st["gossip"]
                print(f"gossip: {g['n_broadcast']} deltas broadcast "
                      f"({g['n_dropped_budget']} over budget, "
                      f"{g['n_dropped_stale']} stale), "
                      f"{c['n_duplicate_evals']} duplicate evals "
                      f"fleet-wide")
    if retrieval is not None:
        sr = eng.retriever if args.sync else eng.searcher
        live = [s for s in sr.shards if s.n_docs]
        print(f"retrieval: {sr.n_searches} searches "
              f"({sr.n_fallback} fallback), {len(live)} live "
              f"shard(s), {sum(s.n_docs for s in live)} docs resident")
        if hasattr(sr, "gather_stats") and sr.n_gathers:
            fs = sr.gather_stats()
            print(f"fanout: gather p50/p99 "
                  f"{fs['gather_p50_s'] * 1e3:.1f}/"
                  f"{fs['gather_p99_s'] * 1e3:.1f} ms (full "
                  f"{fs['full_p50_s'] * 1e3:.1f}/"
                  f"{fs['full_p99_s'] * 1e3:.1f} ms), "
                  f"{fs['n_late_shards']} late stripes "
                  f"({fs['n_cache_fills']} cache-filled, "
                  f"{fs['n_prior_answered']} prior), "
                  f"{fs['n_shard_hedges']} shard hedges "
                  f"({fs['n_shard_hedge_wins']} wins), "
                  f"{fs['n_mirrors_built']} mirrors built / "
                  f"{fs['n_mirrors_dropped']} dropped")
    board = eng.slo_stats()
    print(f"P50 {board['p50_s'] * 1e3:.1f} ms  P99 "
          f"{board['p99_s'] * 1e3:.1f} ms  SLO met "
          f"{100 * board['slo_met_frac']:.0f}%")
    if st["n_executor_errors"]:
        print(f"FAIL: {st['n_executor_errors']} batch(es) rescued from "
              f"the prior by the executor (executor_error)")
        return 1
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    from repro.launch.compile_cache import enable as enable_compile_cache
    args = parse_args(argv)
    enable_compile_cache()
    if args.trace > 0:
        if args.sync:
            raise SystemExit("--trace drives a fleet; drop --sync")
        *_, rate, cfg = calibrate(args)
        return _run_trace(args, cfg, rate)
    run = launch(args)
    serve(run)
    return report(run)


def _run_trace(args, cfg, rate: float) -> int:
    """Replay a chaos trace against a simulated fleet calibrated to the
    measured evaluator rate (the trace needs deterministic per-replica
    clocks; the oracle evaluator stands in for the backbone so the
    poison feature column can detonate it)."""
    from repro.chaos import (FlashCrowd, PoisonSpec, RegionalFailure,
                             RollingRestartEvent, TraceConfig,
                             poisonable, run_fleet_trace)
    from repro.cluster import ClusterConfig, ClusterCoordinator
    from repro.core.pipeline import (SyntheticSearcher,
                                     exact_oracle_evaluator)

    searcher = SyntheticSearcher(corpus_size=20_000, seed=args.seed)
    elastic = args.max_replicas > 0
    coord = ClusterCoordinator(
        cfg, poisonable(exact_oracle_evaluator(searcher)),
        cluster_cfg=ClusterConfig(
            hedge_after_s=args.hedge_after_ms / 1e3,
            gossip=args.gossip, gossip_mode=args.gossip_mode,
            autoscale=elastic or max(args.replicas, 1) > 1,
            min_replicas=args.min_replicas,
            max_replicas=args.max_replicas,
            forecast=args.forecast,
            warmup_lead_s=max(args.warmup_lead_s, 0.0)),
        sim_rate_items_per_s=rate)
    d = args.trace
    tc = TraceConfig(
        duration_s=d, base_qps=args.chaos_qps,
        diurnal_period_s=d, seed=args.seed,
        flash_crowds=([FlashCrowd(0.35 * d, 0.5 * d, args.chaos_flash)]
                      if args.chaos_flash > 1.0 else []),
        poison=([PoisonSpec(0.15 * d, 0.55 * d, qps=args.chaos_poison)]
                if args.chaos_poison > 0 else []),
        failures=([RegionalFailure(t=0.7 * d, n_crash=args.chaos_crash)]
                  if args.chaos_crash > 0 else []),
        restarts=([RollingRestartEvent(t=0.85 * d)]
                  if args.chaos_restart else []))
    rep = run_fleet_trace(coord, searcher, tc)
    st = rep.scheduler_stats
    rids = [r.request_id for r in rep.responses]
    adm = [r for r in rep.responses if r.admitted]
    lat = np.asarray([r.latency_s for r in adm])
    no_drop = (len(rids) == len(set(rids)) == st["n_submitted"])
    print(f"trace: {d:.0f}s, {len(rids)} responses "
          f"({len(adm)} admitted, {st['n_quarantined']} quarantined, "
          f"{st['n_executor_errors']} executor errors), fleet "
          f"{coord.n_replicas} final; "
          f"no-drop {'OK' if no_drop else 'VIOLATED'}")
    for row in rep.churn_log:
        print(f"  event t={row[0]:.2f}s {row[1]}"
              + (f" {row[2]}" if row[2] else "")
              + f" -> {row[3]} replicas")
    if len(lat):
        print(f"P50 {np.percentile(lat, 50) * 1e3:.1f} ms  "
              f"P99 {np.percentile(lat, 99) * 1e3:.1f} ms")
    if "gossip" in st:
        g = st["gossip"]
        print(f"gossip[{args.gossip_mode}]: {g['n_messages']} messages"
              f" ({g['max_round_messages']} busiest round)")
    if "forecast" in st:
        f = st["forecast"]
        print(f"forecast: rate now {f['rate_now_items_per_s']:.0f} -> "
              f"+{args.warmup_lead_s:.1f}s "
              f"{f['rate_forecast_items_per_s']:.0f} items/s, "
              f"{f['n_prewarm_joins']} prewarm joins "
              f"({f['n_cold_joins']} jit-cold)")
    return 0 if no_drop else 1


if __name__ == "__main__":
    sys.exit(main())
