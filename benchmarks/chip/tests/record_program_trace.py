#!/usr/bin/env python3
"""Records the small chip trace of the serving path with the program's
spans on, which ``test_program_trace.py`` reduces.

    python3 benchmarks/chip/tests/record_program_trace.py

On a TPU: the benchmark's own serving stack and loop (``bench.set_up``,
``OpenLoop``) over a two-layer DLRM (``tiny-dlrm.json``) with the
deployment's micro-batch of 4,096 candidates and its Trust DB of 2^20
sets x 4 ways, warmed up, then ``WINDOW_S`` of the small test traffic
at ``RATE_QPS`` inside one ``bench.window`` span, traced with the
program's spans (``repro.obs``) on. The ``.xplane.pb`` goes to
``tests/data/tpu_program_trace.xplane.pb``; the program's spans, the
named idle gaps and the ``shed_partition`` kernel's events are printed.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
SEED = 2**32 + 11
RATE_QPS = 120.0
WINDOW_S = 0.25


def main() -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_program_trace: JAX found no TPU", file=sys.stderr)
        return 1
    from benchmarks.chip import bench, program_trace, tracing
    from benchmarks.chip.metrics import shed_partition_roofline
    from benchmarks.chip.spec import Cell
    from benchmarks.chip.traffic import Schedule
    from repro import obs

    cfg = json.loads((HERE / "tiny-dlrm.json").read_text())
    cfg["serving"].update(u_capacity=2048, u_threshold=2048, chunk_size=64,
                          trust_db_slots=1 << 20)
    mix = json.loads((HERE / "tiny-mix.json").read_text())
    cell = Cell(name="record", chips=1, config=cfg, mix=mix,
                rate_qps=RATE_QPS, end_to_end=[], per_layer=[])
    st = bench.set_up(cell, SEED, spans=True)
    sched = Schedule(mix, RATE_QPS, WINDOW_S, SEED, bench.PHASE_WINDOW)
    tmp = tempfile.mkdtemp(prefix="record-program-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    obs.enable(True)
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        with tracing.span("window", True):
            t0 = time.monotonic()
            rids = st.loop.run(sched, t0, t0 + WINDOW_S)
    finally:
        jax.profiler.stop_trace()
        obs.enable(False)
    st.loop.finish(rids, time.monotonic() + bench.LATE_ANSWER_WAIT_S)
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    (HERE / "data").mkdir(exist_ok=True)
    dst = HERE / "data" / "tpu_program_trace.xplane.pb"
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)

    tr = tracing.read_xplane(str(dst))
    spans = program_trace.read_program_spans(str(dst))
    for s in spans:
        print(f"span {s.name} {(s.end - s.start) * 1e6:.1f}us "
              f"thread={s.thread} {s.args}")
    for name, t in program_trace.name_gaps(tr, spans, top=20):
        print(f"gap {name} {t * 1e3:.3f}ms")
    for m, t in tracing.kernel_events(tr, shed_partition_roofline.EVENT):
        print(f"kernel {t * 1e6:.1f}us {m.string[:200]}")
    print(f"answered {len(rids)} requests; recorded "
          f"{os.path.getsize(dst)} bytes on {jax.devices()[0].device_kind}")
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
