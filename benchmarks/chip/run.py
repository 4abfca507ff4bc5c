#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX sees the chips
the cell asks for. One process: it builds the weights and the traffic
from ``--seed``, boots the serving stack, warms up every shape the
cell's traffic forms, measures for ``--seconds``, then checks what the
timed path answered against the plain references. With ``--trace 0``
the last line's metrics are the cell's end-to-end metrics; with
``--trace 1`` the window (at most ``bench.TRACE_WINDOW_S`` of it) is
profiled and they are its per-layer metrics. The numbers compared, each beside its limit, are the last
lines on standard error and the last key of the result line.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the checkout holds no program.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse
                                .RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def enable_compile_cache() -> str:
    """JAX's persistent cache, at ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else at the fixed ``.jax_cache/`` of the checkout (the program's
    ``launch/compile_cache``), keeping every program so that only a
    cell's first run in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip benchmark: no program under {ROOT / 'src'}; run it "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import spec
    cell = spec.load(args.workload, ROOT)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chip benchmark: {cell.name} needs {cell.chips} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s); nothing was run", file=sys.stderr)
        return 1
    enable_compile_cache()

    from benchmarks.chip.bench import run_cell
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   T_PROCESS_START)
    for k, v in res.notes.items():
        print(f"note {k}: {v}", file=sys.stderr)
    for k, c in res.checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    out = {"correct": res.correct, "attempted": res.attempted,
           "failed": res.failed, "metrics": res.metrics,
           "device": res.device}
    if res.breakdown is not None:
        out["breakdown"] = res.breakdown
    out["checks"] = res.checks
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
