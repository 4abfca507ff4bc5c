#!/usr/bin/env python3
"""Records the small chip trace that ``test_tracing.py`` reduces.

    python3 benchmarks/chip/tests/record_trace.py

On a TPU, inside one ``bench.window`` span: three matrix programs, each
inside a ``bench.drain`` span and followed by a 20 ms ``bench.idle``
span, then three calls of the program's ``shed_partition`` kernel over
4,096 keys against a Trust DB of 2^20 sets x 4 ways, each inside a
``bench.drain`` span. The ``.xplane.pb`` goes to
``tests/data/tpu_trace.xplane.pb``; the name of every device operation
in it is printed.
"""
from __future__ import annotations

import functools
import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
N_KEYS, N_SLOTS, N_WAYS = 4096, 1 << 20, 4


def main() -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("record_trace: JAX found no TPU", file=sys.stderr)
        return 1
    from repro.kernels.shed_partition import shed_partition
    from benchmarks.chip import tracing

    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    x = jnp.ones((4096, 4096), jnp.bfloat16)
    shed = jax.jit(functools.partial(shed_partition, budget_is_total=True))
    keys = jax.random.randint(jax.random.PRNGKey(0), (N_KEYS,), 1, 1 << 26
                              ).astype(jnp.uint32)
    valid = jnp.ones((N_KEYS,), bool)
    cache_k = jnp.zeros((N_WAYS, N_SLOTS), jnp.uint32)
    cache_v = jnp.zeros((N_WAYS, N_SLOTS), jnp.float32)
    shed_args = (keys, valid, cache_k, cache_v, 2048, 2048, 4096)
    f(x).block_until_ready()
    jax.block_until_ready(shed(*shed_args))
    tmp = tempfile.mkdtemp(prefix="record-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.drain"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(0.02)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.drain"):
                jax.block_until_ready(shed(*shed_args))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    (HERE / "data").mkdir(exist_ok=True)
    dst = HERE / "data" / "tpu_trace.xplane.pb"
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    tr = tracing.read_xplane(str(dst))
    for chip, ops in tr.device_ops.items():
        for name, s, e in ops:
            print(f"op chip={chip} {(e - s) * 1e6:.1f}us {name[:600]}")
    print(f"recorded {os.path.getsize(dst)} bytes on "
          f"{jax.devices()[0].device_kind}")
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
