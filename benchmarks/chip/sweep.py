#!/usr/bin/env python3
"""Knee sweep of one cell's configuration and traffic, on the chip.

    python3 benchmarks/chip/sweep.py --workload <name> --seed <n> \\
        --seconds 15 --rates 2,4,6,8

One process boots and warms the cell's stack once, then offers each
rate for ``--seconds`` (the cell's own rate is ignored) and prints one
JSON line per rate: queries/s answered through the serving path inside
the window, the 50th and 95th percentile response times, the backlog
left at the close (requests not yet answered and items still queued),
rejections at admission and the trusted share. The knee is the highest
rate whose backlog does not grow; the cells' rates in
``cells/<workload>.json`` are fixed from such a sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse
                                .RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--rates", required=True,
                   help="comma-separated offered rates, queries/s")
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import bench, check, spec
    from benchmarks.chip.run import enable_compile_cache
    from benchmarks.chip.traffic import Schedule

    cell = spec.load(args.workload, ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]
    cell.rate_qps = rates[0]
    st = bench.set_up(cell, args.seed, spans=False)
    for k, rate in enumerate(rates):
        sched = Schedule(cell.mix, rate, args.seconds, args.seed, 10 + k)
        t0 = time.monotonic()
        rids = st.loop.run(sched, t0, t0 + args.seconds)
        t_close = time.monotonic()
        queued = st.coord.queued_items
        open_at_close = sum(1 for r in rids if r not in st.loop.answers)
        st.loop.finish(rids, t_close + bench.LATE_ANSWER_WAIT_S)
        resp = check.Responses({r: st.loop.answers.get(r, []) for r in rids},
                               {r: st.loop.sent[r] for r in rids},
                               t0, t_close, args.seconds)
        e2e = resp.end_to_end(0.0)
        print(json.dumps({
            "workload": cell.name, "rate_qps": rate, "requests": len(rids),
            "qps": e2e["qps"], "p50_ms": e2e["p50_ms"],
            "p95_ms": e2e["p95_ms"], "trusted_share": e2e["trusted_share"],
            "open_at_close": open_at_close, "queued_items_at_close": queued,
            "generator_lag_max_s": resp.lag_max(),
            "drain_after_close_s": time.monotonic() - t_close,
            **resp.tier_shares()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
