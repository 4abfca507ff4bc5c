#!/usr/bin/env python3
"""Readings that set the ``trust_gap`` limit of a configuration.

    python3 benchmarks/chip/readings.py --workload <name> \\
        --seeds 1,2,3 [--items N]

For each seed, in one process: the weights and the window's first
candidates as a run of the cell makes them, scored by the plain float32
reference, by the control (the reference one precision step below the
configuration's, ``mode="control"``) and by the program's evaluator at
the cell's batch shape. Prints one JSON line per seed with the widest
gap of the program and of the control from the reference. The limit
lies between the program's largest reading (over the benchmark's own
runs too) and the control's smallest.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse
                                .RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--items", type=int, default=0)
    p.add_argument("--seconds", type=float, default=30.0)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import spec
    from benchmarks.chip.bench import PHASE_WINDOW
    from benchmarks.chip.run import enable_compile_cache
    from benchmarks.chip.traffic import Schedule, seed_words

    cell = spec.load(args.workload, ROOT)
    import jax
    import jax.numpy as jnp
    enable_compile_cache()
    cfg, fam, ref = cell.config, cell.family, cell.reference
    n = args.items or cfg["serving"]["check_items"]
    s = cfg["serving"]
    cap = -(-(s["u_capacity"] + s["u_threshold"]) // s["chunk_size"]) \
        * s["chunk_size"]
    for seed in (int(x) for x in args.seeds.split(",")):
        key = jax.random.PRNGKey(
            int(seed_words(seed, 7).generate_state(1)[0]))
        weights = fam.make_weights(cfg, key)
        sched = Schedule(cell.mix, cell.rate_qps, args.seconds, seed,
                         PHASE_WINDOW)
        urls, got = [], 0
        for req in sched.requests:
            if got >= n:
                break
            urls.append(sched.urls(req))
            got += len(urls[-1])
        urls = np.concatenate(urls)[:n]
        feats = fam.features(cfg, urls)
        want = ref.trust(cfg, weights, feats, mode="f32")
        ctrl = ref.trust(cfg, weights, feats, mode="control")
        ev = fam.make_evaluator(cfg, weights)
        pad = -len(urls) % cap
        padded = {k: np.concatenate([v, np.repeat(v[-1:], pad, 0)])
                  for k, v in feats.items()}
        prog = np.concatenate([
            np.asarray(ev({k: jnp.asarray(v[i:i + cap])
                           for k, v in padded.items()}))
            for i in range(0, len(urls) + pad, cap)])[:len(urls)]
        print(json.dumps({
            "workload": cell.name, "seed": seed, "items": len(urls),
            "program_gap": float(np.max(np.abs(prog - want))),
            "control_gap": float(np.max(np.abs(ctrl - want))),
            "trust_mean": float(np.mean(want)),
            "trust_std": float(np.std(want))}), flush=True)
        del weights, ev
    return 0


if __name__ == "__main__":
    sys.exit(main())
