"""Emit the EXPERIMENTS.md §Dry-run / §Roofline markdown tables from the
dry-run artifacts, and the §Fanout table from ``BENCH_fanout.json``.
Usage:
    python -m benchmarks.make_experiments_tables [--mesh single]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from benchmarks.bench_roofline import (ART, HBM_BW, LINK_BW, PEAK_FLOPS,
                                       model_flops, terms)


def load(mesh):
    out = []
    for f in sorted(glob.glob(os.path.join(ART, mesh, "*.json"))):
        if "@" in os.path.basename(f):
            continue
        r = json.load(open(f))
        if r.get("ok"):
            out.append(r)
    return out


def dryrun_table(mesh: str) -> str:
    rows = [
        "| arch | shape | kind | devs | HBM/dev (args+temp) GB | "
        "compile s | collectives (AG/AR/RS/A2A/CP count) |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in load(mesh):
        m = r["memory"]
        cc = r["analysis"]["collective_counts"]
        hbm = (m["argument_bytes"] + m["temp_bytes"]) / 1e9
        counts = "/".join(str(cc[k]) for k in
                          ("all-gather", "all-reduce", "reduce-scatter",
                           "all-to-all", "collective-permute"))
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['kind']} | "
            f"{r['n_devices']} | {hbm:.2f} | {r['compile_s']:.1f} | "
            f"{counts} |")
    return "\n".join(rows)


def roofline_table(mesh: str) -> str:
    rows = [
        "| arch | shape | compute s | memory s | collective s | dominant"
        " | MODEL_FLOPS | useful % | roofline % |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in load(mesh):
        t = terms(r)
        rows.append(
            f"| {r['arch']} | {r['shape']} | {t['compute_s']:.4f} | "
            f"{t['memory_s']:.4f} | {t['collective_s']:.4f} | "
            f"{t['dominant']} | {t['model_flops']:.3g} | "
            f"{100 * t['useful_ratio']:.1f} | "
            f"{100 * t['roofline_frac']:.2f} |")
    return "\n".join(rows)


def fanout_table(path: str = "BENCH_fanout.json") -> str:
    """Quorum-gather tail table from ``benchmarks/bench_fanout.py``."""
    if not os.path.exists(path):
        return f"(no {path} — run `python benchmarks/bench_fanout.py " \
               f"--json {path}` first)"
    r = json.load(open(path))
    t = r["tail"]
    rows = [
        "| gather | p50 ms | p99 ms | recall@10 | late stripes "
        "(cache/prior) | hedges (wins) | gates |",
        "|---|---|---|---|---|---|---|",
        f"| full {t['n_shards']}/{t['n_shards']} | "
        f"{t['full_p50_s'] * 1e3:.1f} | {t['full_p99_s'] * 1e3:.1f} | "
        f"1.000 | 0 | 0 | — |",
        f"| quorum {t['quorum_k']}/{t['n_shards']} hedged | "
        f"{t['quorum_p50_s'] * 1e3:.1f} | "
        f"{t['quorum_p99_s'] * 1e3:.1f} | "
        f"{t['overlap_at_10_mean']:.3f} | "
        f"{t['n_late_shards']} ({t['n_cache_fills']}/"
        f"{t['n_prior_answered']}) | "
        f"{t['n_shard_hedges']} ({t['n_shard_hedge_wins']}) | "
        f"p99 {t['p99_speedup']:.1f}x"
        f"{' PASS' if r['p99_ok'] else ' FAIL'}, recall"
        f"{' PASS' if r['recall_ok'] else ' FAIL'}, parity"
        f"{' PASS' if r['parity_ok'] else ' FAIL'}, replay"
        f"{' PASS' if r['determinism_ok'] else ' FAIL'} |",
    ]
    return "\n".join(rows)


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--mesh", default="single")
    p.add_argument("--which", default="both",
                   choices=["dryrun", "roofline", "fanout", "both"])
    a = p.parse_args()
    if a.which in ("dryrun", "both"):
        print("### Dry-run table (" + a.mesh + ")\n")
        print(dryrun_table(a.mesh))
        print()
    if a.which in ("roofline", "both"):
        print("### Roofline table (" + a.mesh + ")\n")
        print(roofline_table(a.mesh))
        print()
    if a.which in ("fanout", "both"):
        print("### Fanout tail-tolerance table "
              "(32 straggler-injected shards)\n")
        print(fanout_table())
        print()
