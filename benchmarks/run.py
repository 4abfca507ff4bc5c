"""Benchmark orchestrator: one bench per paper figure + the roofline
harness. Prints ``name,us_per_call,derived`` CSV rows per the repo
convention, followed by the human-readable sections. ``--quick``
shrinks the parameterizable workloads (scheduler / cluster / fused
drain) so a CI run finishes in minutes.

Every ``BENCH_*.json``-writing bench reports boolean ``*_ok`` gates;
the orchestrator collects them all and exits non-zero if ANY gate
fails, so a regression fails CI instead of merely flipping a field in
an artifact nobody reads.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def _timed(name, fn):
    t0 = time.perf_counter()
    out = fn()
    dt_us = 1e6 * (time.perf_counter() - t0)
    return name, dt_us, out


def _gates(name, rows):
    """Top-level ``*_ok`` booleans of one bench's row dict."""
    return {f"{name}:{k}": bool(v) for k, v in rows.items()
            if k.endswith("_ok") and isinstance(v, bool)}


def main(quick: bool = False) -> int:
    from repro.launch.compile_cache import enable as enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_adaptive, bench_capacity,
                            bench_cluster, bench_elastic, bench_fanout,
                            bench_fleet, bench_fused_drain,
                            bench_heavy_load, bench_response_time,
                            bench_retrieval, bench_roofline,
                            bench_scheduler, bench_throughput,
                            bench_very_heavy_load)

    csv_rows = []
    gates = {}

    print("=" * 72)
    print("Fig 3.1(a) — Heavy load (Existing vs RLS-EDA vs Proposed)")
    print("=" * 72)
    name, us, rows = _timed("fig3.1a_heavy", bench_heavy_load.main)
    csv_rows.append((name, us, "rt+trust scale-of-5"))

    print()
    print("=" * 72)
    print("Fig 3.1(b) — Very Heavy load")
    print("=" * 72)
    name, us, rows = _timed("fig3.1b_very_heavy",
                            bench_very_heavy_load.main)
    csv_rows.append((name, us, "rt+trust scale-of-5, extended deadline"))

    print()
    print("=" * 72)
    print("Fig 3.2 — End-to-end response times (incl. real evaluator)")
    print("=" * 72)
    name, us, rows = _timed("fig3.2_response_time",
                            bench_response_time.main)
    csv_rows.append((name, us, "wall-clock speedups vs paper"))

    print()
    print("=" * 72)
    print("Beyond-paper: adaptive Very-Heavy control (paper §7 future "
          "work)")
    print("=" * 72)
    name, us, rows = _timed("adaptive_control", bench_adaptive.main)
    csv_rows.append((name, us, "PI on extension weight vs static"))

    print()
    print("=" * 72)
    print("Beyond-paper: priority scheduler vs synchronous submit "
          "(repro.scheduling)")
    print("=" * 72)
    name, us, rows = _timed(
        "scheduler",
        (lambda: bench_scheduler.main(n_requests=48)) if quick
        else bench_scheduler.main)
    csv_rows.append((name, us,
                     f"{rows['speedup']:.2f}x req throughput vs sync"))
    gates.update(_gates("scheduler", rows))
    with open("BENCH_scheduler.json", "w") as f:
        json.dump(rows, f, indent=2)
    print("wrote BENCH_scheduler.json")

    print()
    print("=" * 72)
    print("Beyond-paper: serving fleet 1 vs 2 vs 4 replicas "
          "(repro.cluster)")
    print("=" * 72)
    name, us, rows = _timed(
        "cluster",
        (lambda: bench_cluster.main(n_queries=240)) if quick
        else bench_cluster.main)
    csv_rows.append((name, us,
                     f"{rows['speedup_4v1']:.2f}x items/s 4 vs 1 "
                     f"replicas"))
    gates.update(_gates("cluster", rows))
    with open("BENCH_cluster.json", "w") as f:
        json.dump(rows, f, indent=2)
    print("wrote BENCH_cluster.json")

    print()
    print("=" * 72)
    print("Beyond-paper: elastic membership churn + Trust-DB gossip "
          "(repro.cluster)")
    print("=" * 72)
    name, us, rows = _timed(
        "elastic",
        (lambda: bench_elastic.main(n_queries=240)) if quick
        else bench_elastic.main)
    csv_rows.append((name, us,
                     f"churn no-drop={rows['no_drop_ok']} "
                     f"p99_ok={rows['p99_ok']} gossip "
                     f"{rows['gossip']['dup_eval_cut']:.1f}x dup cut"))
    gates.update(_gates("elastic", rows))
    with open("BENCH_elastic.json", "w") as f:
        json.dump(rows, f, indent=2)
    print("wrote BENCH_elastic.json")

    print()
    print("=" * 72)
    print("Beyond-paper: 48-replica chaos trace — quarantine, epidemic "
          "gossip, rolling restarts (repro.chaos)")
    print("=" * 72)
    name, us, rows = _timed(
        "fleet",
        (lambda: bench_fleet.main(duration_s=3.0, base_qps=60.0,
                                  poison_duration_s=3.0)) if quick
        else bench_fleet.main)
    csv_rows.append((name, us,
                     f"no_drop={rows['no_drop_ok']} "
                     f"p99={rows['p99_ok']} gossip={rows['gossip_ok']} "
                     f"det={rows['determinism_ok']} "
                     f"quarantine={rows['quarantine_ok']}"))
    gates.update(_gates("fleet", rows))
    with open("BENCH_fleet.json", "w") as f:
        json.dump(rows, f, indent=2)
    print("wrote BENCH_fleet.json")

    print()
    print("=" * 72)
    print("Beyond-paper: feedforward capacity planner — what-if "
          "prediction + forecast scaling (repro.cluster.capacity)")
    print("=" * 72)
    name, us, rows = _timed(
        "capacity",
        (lambda: bench_capacity.main(fit_duration_s=4.0,
                                     valid_duration_s=3.0,
                                     ramp_duration_s=6.0)) if quick
        else bench_capacity.main)
    ff = rows["contrast"]
    csv_rows.append((name, us,
                     f"predict={rows['predict_ok']} "
                     f"ff p99 {ff['feedforward']['p99_s']*1e3:.0f}ms "
                     f"vs reactive {ff['reactive']['p99_s']*1e3:.0f}ms "
                     f"({ff['feedforward']['n_prewarm_joins']} prewarmed "
                     f"joins, {ff['feedforward']['n_cold_joins']} cold)"))
    gates.update(_gates("capacity", rows))
    with open("BENCH_capacity.json", "w") as f:
        json.dump(rows, f, indent=2)
    print("wrote BENCH_capacity.json")

    print()
    print("=" * 72)
    print("Beyond-paper: sharded retrieval front-end — regimes, kernel "
          "parity, scorer (repro.retrieval)")
    print("=" * 72)
    name, us, rows = _timed(
        "retrieval",
        (lambda: bench_retrieval.main(n_queries=120, n_docs=768,
                                      n_partitions=8)) if quick
        else bench_retrieval.main)
    csv_rows.append((name, us,
                     f"no_drop={rows['no_drop_ok']} "
                     f"regimes={rows['regimes_ok']} "
                     f"parity={rows['parity_ok']} scorer "
                     f"{rows['scorer']['speedup']:.1f}x jit vs py"))
    gates.update(_gates("retrieval", rows))
    with open("BENCH_retrieval.json", "w") as f:
        json.dump(rows, f, indent=2)
    print("wrote BENCH_retrieval.json")

    print()
    print("=" * 72)
    print("Beyond-paper: tail-tolerant scatter-gather — quorum, "
          "hedging, stripe replication (repro.fanout)")
    print("=" * 72)
    name, us, rows = _timed(
        "fanout",
        (lambda: bench_fanout.main(n_queries=120, n_docs=768)) if quick
        else bench_fanout.main)
    csv_rows.append((name, us,
                     f"{rows['tail']['p99_speedup']:.1f}x p99 quorum "
                     f"vs full; recall={rows['recall_ok']} "
                     f"parity={rows['parity_ok']} "
                     f"det={rows['determinism_ok']}"))
    gates.update(_gates("fanout", rows))
    with open("BENCH_fanout.json", "w") as f:
        json.dump(rows, f, indent=2)
    print("wrote BENCH_fanout.json")

    print()
    print("=" * 72)
    print("Beyond-paper: fused device-resident drain vs host chunk "
          "loop (core.fused_shedder)")
    print("=" * 72)
    # --quick shrinks the stream but keeps the full --pipeline-depth
    # sweep (1/2/4): the depth >= 2 window vs the depth-1
    # sync-per-drain behaviour is this PR's measured claim.
    name, us, rows = _timed(
        "fused_drain", lambda: bench_fused_drain.main(quick=quick))
    csv_rows.append((name, us,
                     f"{rows['speedup']:.2f}x items/s fused vs host "
                     f"drain; depth-{rows.get('depth_speedup_best', 1)}"
                     f" {rows.get('depth_speedup', 1.0):.2f}x vs "
                     f"depth-1"))
    gates.update(_gates("fused_drain", rows))
    with open("BENCH_fused_drain.json", "w") as f:
        json.dump(rows, f, indent=2)
    print("wrote BENCH_fused_drain.json")

    print()
    print("=" * 72)
    print("Evaluator throughput per architecture (reduced, this host)")
    print("=" * 72)
    name, us, rows = _timed("throughput", bench_throughput.main)
    csv_rows.append((name, us, "us/item per arch"))

    print()
    print("=" * 72)
    print("Roofline (single-pod baseline, from dry-run artifacts)")
    print("=" * 72)
    try:
        name, us, rows = _timed(
            "roofline_single",
            lambda: bench_roofline.run("single", csv=True))
        csv_rows.append((name, us, "3 terms x 40 cells"))
    except (FileNotFoundError, IndexError):
        print("(dry-run artifacts missing — run "
              "`python -m repro.launch.dryrun --all` first)")

    print()
    print("name,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.0f},{derived}")

    failed = sorted(k for k, ok in gates.items() if not ok)
    print()
    print(f"gates: {len(gates) - len(failed)}/{len(gates)} passed"
          + (f"; FAILED: {', '.join(failed)}" if failed else ""))
    return 1 if failed else 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced workloads so CI finishes in minutes")
    args = ap.parse_args()
    sys.exit(main(quick=args.quick))
