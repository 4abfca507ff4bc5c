#!/usr/bin/env python3
"""Chip smoke check: the serving main path, end to end, on a TPU.

Run from the repository root of a machine with a TPU:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the mesh-sharded evaluator

One process runs every phase (a chip belongs to one process at a time)
and starts no child:

(a) device check: JAX's first device must be a TPU.
(b) ``shed_partition`` against the production Trust DB (65536 slots x
    4 ways) and ``topk_select`` at the retrieval shard's shape, compiled
    natively and compared exactly with their ``kernels/ref.py`` oracles
    on seeded inputs.
(c) the launcher's own code path (``repro.launch.serve``) in-process:
    smollm-135m at its published width, fused drain at depth 2, a
    4096-doc corpus in 16 doc-partitions on one replica, a few dozen
    query strings served twice. Every request gets exactly one
    response, no batch is rescued by the executor, items are
    evaluated, the second pass reads cached trust, and on one batch the
    fused tiers equal the host ``shed_plan`` oracle while the evaluated
    trust matches a direct call of the same evaluator.

``--four-chips`` runs only the sharded path instead: the published-width
evaluator placed over a (data=1, model=4) mesh of the local devices,
a few batches through the fused drain, scores compared with the same
evaluator on one chip.

Earlier lines report what each phase found (compile seconds, peak
device bytes, requests and tier counts); none of it is a benchmark
number. The last line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failure exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"
SEED = 0
CACHE_SLOTS, CACHE_WAYS = 65536, 4
N_QUERIES = 36
# bf16 evaluator forward: trust is sigmoid(mean logprob) * 5, and two
# programs computing it may round a few bf16 ulps apart.
TRUST_TOL = 2e-2


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


# -- (b) kernels ------------------------------------------------------------

def phase_kernels(native: bool = True, n_items: int = 4096,
                  n_docs: int = 4096, top_k: int = 64) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import trust_cache as TC
    from repro.kernels import ops, ref

    rng = np.random.default_rng(SEED)
    keys = (rng.choice(2 ** 31, size=n_items, replace=False) + 1
            ).astype(np.uint32)
    valid = np.arange(n_items) < (3 * n_items) // 4
    cache = TC.init(CACHE_SLOTS, CACHE_WAYS)
    cached = keys[::2]
    cache = TC.insert(cache, jnp.asarray(cached),
                      jnp.asarray(rng.uniform(0, 5, cached.size),
                                  jnp.float32),
                      jnp.ones(cached.size, bool))
    shed_kw = dict(u_capacity=n_items // 4, u_threshold=n_items // 4,
                   budget_dq=(3 * n_items) // 8, budget_is_total=True)
    args = (jnp.asarray(keys), jnp.asarray(valid), cache["keys"],
            cache["values"])
    t0 = time.perf_counter()
    if native:
        text = ops.shed_partition.lower(*args, **shed_kw).compile().as_text()
        check("tpu_custom_call" in text,
              "shed_partition did not compile to a Mosaic kernel")
    got = jax.block_until_ready(ops.shed_partition(*args, **shed_kw))
    t_shed = time.perf_counter() - t0
    want = ref.shed_partition_ref(*args, shed_kw["u_capacity"],
                                  shed_kw["u_threshold"],
                                  shed_kw["budget_dq"],
                                  budget_is_total=True)
    for name, g, w in zip(("tier", "cached value", "eval rank"), got,
                          want):
        check(np.array_equal(np.asarray(g), np.asarray(w)),
              f"shed_partition {name} differs from kernels/ref.py")
    tiers = np.bincount(np.asarray(got[0]), minlength=4)
    print(f"(b) shed_partition: {n_items} items vs {CACHE_SLOTS}x"
          f"{CACHE_WAYS} Trust DB, exact vs oracle "
          f"(eval/cached/prior/invalid {tiers.tolist()}), "
          f"{t_shed:.2f}s compile and first call", flush=True)

    scores = jnp.asarray(np.round(rng.normal(size=n_docs) * 4) / 4,
                         jnp.float32)              # many ties
    t0 = time.perf_counter()
    if native:
        text = ops.topk_select.lower(scores, k=top_k).compile().as_text()
        check("tpu_custom_call" in text,
              "topk_select did not compile to a Mosaic kernel")
    vals, idxs = jax.block_until_ready(ops.topk_select(scores, k=top_k))
    t_topk = time.perf_counter() - t0
    v_ref, i_ref = ref.topk_select_ref(scores, top_k)
    check(np.array_equal(np.asarray(idxs), np.asarray(i_ref))
          and np.array_equal(np.asarray(vals), np.asarray(v_ref)),
          "topk_select differs from kernels/ref.py")
    print(f"(b) topk_select: top-{top_k} of {n_docs} scores, exact vs "
          f"oracle, {t_topk:.2f}s compile and first call", flush=True)


# -- (c) the launcher's serving path ----------------------------------------

def serve_argv(full_width: bool = True, corpus: int = 4096,
               index_shards: int = 16, n_queries: int = N_QUERIES):
    return (["--arch", "smollm-135m", "--drain-mode", "fused",
             "--pipeline-depth", "2", "--replicas", "1",
             "--corpus", str(corpus), "--index-shards", str(index_shards),
             "--n-requests", str(n_queries), "--seed", str(SEED)]
            + (["--full-width"] if full_width else []))


def _check_one_response_each(responses, n_sent: int, label: str) -> None:
    rids = [r.request_id for r in responses]
    check(len(rids) == n_sent and len(set(rids)) == n_sent,
          f"{label}: {n_sent} requests sent, {len(rids)} responses "
          f"({len(set(rids))} distinct)")


def _tier_counts(responses):
    return (sum(r.shed.n_evaluated for r in responses),
            sum(r.shed.n_cached for r in responses),
            sum(r.shed.n_prior for r in responses))


def phase_serve(argv, expect_width=None) -> None:
    import jax
    from repro.launch import serve as S

    t0 = time.perf_counter()
    run = S.launch(S.parse_args(argv))
    print(f"(c) launch (calibrate, index, boot, jit warm-up): "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    if expect_width is not None:
        table = run.evaluate.params["embed"]["table"]
        blocks = jax.tree.leaves(run.evaluate.params["blocks"])[0]
        check((table.shape, blocks.shape[0]) == expect_width,
              f"evaluator is not at published width: embed "
              f"{table.shape}, {blocks.shape[0]} layers")
    eng = run.eng

    t0 = time.perf_counter()
    queries = S.serve(run)
    first = list(eng.completed)
    _check_one_response_each(first, len(queries), "first pass")
    e1, c1, p1 = _tier_counts(first)
    print(f"(c) first pass: {len(first)} requests answered in "
          f"{time.perf_counter() - t0:.1f}s; eval {e1} cached {c1} "
          f"prior {p1}", flush=True)
    check(e1 > 0, "no batch evaluated any item")

    t0 = time.perf_counter()
    S.serve(run, queries)
    second = list(eng.completed)[len(first):]
    _check_one_response_each(second, len(queries), "second pass")
    e2, c2, p2 = _tier_counts(second)
    print(f"(c) second pass, same queries: {len(second)} requests in "
          f"{time.perf_counter() - t0:.1f}s; eval {e2} cached {c2} "
          f"prior {p2}", flush=True)
    check(c2 > 0, "second pass read no cached trust")

    check(S.report(run) == 0, "the launcher reported a failed run")
    st = eng.scheduler_stats()
    check(st["n_executor_errors"] == 0,
          f"{st['n_executor_errors']} batch(es) rescued by the executor")
    _check_parity(run)
    print(f"(c) peak device memory: {peak_bytes(jax.devices()[0])}",
          flush=True)


def _check_parity(run) -> None:
    """One micro-batch of fresh queries straight through the replica's
    fused shedder: tiers against the host ``shed_plan`` oracle on the
    Trust DB as it stood, evaluated trust against a direct call of the
    same evaluator at the same padded shape."""
    import jax
    import jax.numpy as jnp
    from repro.core import trust_cache as TC
    from repro.core.shedder import TIER_EVAL, shed_plan

    rep = run.eng.replicas[0]
    sh = rep.engine.shedder
    cap = rep.scheduler.max_batch_items
    parts = [run.eng.searcher.search(run.queries.sample(),
                                     run.cfg.retrieve_top_k)
             for _ in range(8)]
    keys = np.concatenate([p.url_ids for p in parts])[:cap]
    buckets = np.concatenate([p.buckets for p in parts])[:cap]
    tokens = np.concatenate([p.features["tokens"] for p in parts])[:cap]
    n = len(keys)
    pad = cap - n
    keys = np.concatenate([keys, np.zeros(pad, np.uint32)])
    buckets = np.concatenate([buckets, np.zeros(pad, buckets.dtype)])
    tokens = np.concatenate([tokens, np.zeros((pad,) + tokens.shape[1:],
                                              tokens.dtype)])
    valid = np.arange(cap) < n

    cache_before = jax.tree.map(jnp.copy, sh.cache)
    ucap, uthr = sh.monitor.parameters()
    res = sh.process(keys, buckets, {"tokens": tokens}, n_valid=n)
    _, hit = TC.lookup(cache_before, jnp.asarray(keys))
    plan = shed_plan(jnp.asarray(valid), hit, ucap, uthr,
                     deadline_s=run.cfg.deadline_s,
                     overload_deadline_s=run.cfg.overload_deadline_s,
                     very_heavy_weight=run.cfg.very_heavy_weight)
    check(np.array_equal(res.tier, np.asarray(plan["tier"])),
          "fused-drain tiers differ from the host shed_plan oracle")
    ev_idx = np.flatnonzero(res.tier == TIER_EVAL)
    check(len(ev_idx) > 0, "parity batch evaluated nothing")
    gather = np.concatenate([ev_idx, np.full(cap - len(ev_idx), cap - 1)])
    direct = np.asarray(run.evaluate({"tokens": tokens[gather]}))
    np.testing.assert_allclose(res.trust[ev_idx], direct[:len(ev_idx)],
                               rtol=TRUST_TOL, atol=TRUST_TOL)
    print(f"(c) parity batch: {n} items, tiers equal the shed_plan "
          f"oracle (eval {res.n_evaluated} cached {res.n_cached} prior "
          f"{res.n_prior}); evaluated trust matches the direct "
          f"evaluator within {TRUST_TOL}", flush=True)


# -- --four-chips: the mesh-sharded evaluator --------------------------------

def phase_four_chips(smoke: bool = False, n_items: int = 512,
                     n_batches: int = 3) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs.base import TrustIRConfig
    from repro.core.fused_shedder import FusedLoadShedder
    from repro.core.shedder import TIER_EVAL
    from repro.launch.mesh import make_host_mesh
    from repro.serving.evaluators import (make_evaluator,
                                          make_sharded_evaluator)

    mesh = make_host_mesh((1, 4))
    devices = set(mesh.devices.flat)
    t0 = time.perf_counter()
    se = make_sharded_evaluator("smollm-135m", mesh=mesh, smoke=smoke,
                                seed=SEED)
    one_chip, mk = make_evaluator("smollm-135m", smoke=smoke, seed=SEED)
    _check_every_device_holds_a_shard(se.evaluate.params, devices,
                                      "parameters", split=True)
    print(f"(4) evaluator placed over mesh {dict(mesh.shape)} in "
          f"{time.perf_counter() - t0:.1f}s; every device holds a "
          f"parameter shard", flush=True)

    cfg = TrustIRConfig(u_capacity=n_items, u_threshold=n_items,
                        cache_slots=CACHE_SLOTS, cache_ways=CACHE_WAYS)
    fused = FusedLoadShedder(cfg, se.evaluate,
                             feature_sharding=se.feature_sharding)
    worst = 0.0
    for b in range(n_batches):
        t0 = time.perf_counter()
        keys = np.arange(1 + b * n_items, 1 + (b + 1) * n_items,
                         dtype=np.uint32)
        feats = mk(n_items, fseed=SEED + b)
        staged = fused.stage(keys, np.zeros(n_items, np.int32), feats)
        _check_every_device_holds_a_shard(staged.feats_j, devices,
                                          "staged features", split=False)
        res = fused.dispatch_staged(staged).result()
        # Fresh keys: every item the budget admits is evaluated (the
        # LoadMonitor's Ucapacity follows the measured rate).
        ev_idx = np.flatnonzero(res.tier == TIER_EVAL)
        check(len(ev_idx) > 0, f"batch {b}: nothing evaluated")
        want = np.asarray(one_chip(jax.tree.map(jnp.asarray, feats)))
        np.testing.assert_allclose(res.trust[ev_idx], want[ev_idx],
                                   rtol=TRUST_TOL, atol=TRUST_TOL)
        worst = max(worst, float(np.max(np.abs(res.trust[ev_idx]
                                               - want[ev_idx]))))
        print(f"(4) batch {b}: {len(ev_idx)} of {n_items} items "
              f"evaluated by the sharded fused drain in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(f"(4) sharded scores match the one-chip evaluator: max |diff| "
          f"{worst:.3g} (tolerance {TRUST_TOL})", flush=True)
    for d in sorted(devices, key=lambda d: d.id):
        print(f"(4) device {d.id} peak memory: {peak_bytes(d)}", flush=True)


def _check_every_device_holds_a_shard(tree, devices, what: str,
                                      split: bool) -> None:
    """Every device holds a shard of ``tree``; with ``split``, each
    also holds a partial one (a leaf cut along the mesh)."""
    import jax
    held, split_on = set(), set()
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held.add(shard.device)
            if shard.data.shape != leaf.shape:
                split_on.add(shard.device)
    check(held == devices, f"{what}: devices {sorted(d.id for d in held)}"
          f" hold shards, want all of {sorted(d.id for d in devices)}")
    if split:
        check(split_on == devices,
              f"{what}: only devices {sorted(d.id for d in split_on)} "
              f"hold a split (partial) shard")


# -- entry point -------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--four-chips", action="store_true",
                   help="run only the mesh-sharded evaluator path on "
                        "four local chips, against one chip")
    args = p.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run this "
              f"script from a checkout of the repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    from repro.launch.compile_cache import enable as enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    print(f"(a) {len(devices)} x {dev.device_kind} ({dev.platform}), "
          f"jax {jax.__version__}", flush=True)
    try:
        if args.four_chips:
            phase_four_chips()
        else:
            phase_kernels()
            phase_serve(serve_argv(), expect_width=((49152, 576), 30))
    except (SmokeFailure, AssertionError) as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
