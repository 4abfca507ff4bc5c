"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU): shape and
dtype sweeps per kernel, plus hypothesis sweeps for the paper's
shed_partition kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import trust_cache as TC
from repro.core.shedder import TIER_CACHED
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,D,win,cap", [
    (2, 256, 4, 2, 64, 0, 0.0),        # GQA causal
    (1, 256, 8, 4, 128, 64, 50.0),     # window + softcap (gemma2)
    (2, 128, 3, 3, 64, 0, 0.0),        # MHA, odd heads (smollm)
    (1, 512, 5, 1, 64, 128, 0.0),      # MQA + window
])
def test_flash_attention_matches_ref(B, S, Hq, Hkv, D, win, cap, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=win,
                              softcap=cap, block_q=64, block_k=64,
                              interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True, window=win,
                                     softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,L,Hq,Hkv,D,win,cap", [
    (3, 512, 4, 2, 64, 0, 0.0),
    (2, 512, 8, 1, 128, 100, 30.0),
    (2, 256, 8, 8, 64, 0, 0.0),
    (1, 1024, 9, 3, 64, 0, 0.0),       # smollm head layout
])
def test_flash_decode_matches_ref(B, L, Hq, Hkv, D, win, cap, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    kc = jax.random.normal(ks[1], (B, L, Hkv, D), dtype)
    vc = jax.random.normal(ks[2], (B, L, Hkv, D), dtype)
    lengths = jnp.asarray(
        (np.arange(B) * (L // max(B, 1)) % L + 1), jnp.int32)
    out = ops.flash_decode(q, kc, vc, lengths, window=win, softcap=cap,
                           block_k=128, interpret=True)
    expect = ref.flash_decode_ref(q, kc, vc, lengths, window=win,
                                  softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               **tol(dtype))


def test_flash_decode_respects_lengths():
    """Tokens beyond ``lengths`` must not influence the output."""
    ks = jax.random.split(KEY, 3)
    B, L, H, D = 2, 256, 4, 64
    q = jax.random.normal(ks[0], (B, H, D))
    kc = jax.random.normal(ks[1], (B, L, H, D))
    vc = jax.random.normal(ks[2], (B, L, H, D))
    lengths = jnp.asarray([100, 37], jnp.int32)
    out1 = ops.flash_decode(q, kc, vc, lengths, interpret=True)
    kc2 = kc.at[:, 200:].set(1e4)       # poison the invalid region
    vc2 = vc.at[:, 200:].set(-1e4)
    out2 = ops.flash_decode(q, kc2, vc2, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,F,D", [(37, 27, 128), (128, 27, 128),
                                   (16, 8, 64), (5, 12, 32)])
def test_dot_interaction_matches_ref(B, F, D, dtype):
    x = jax.random.normal(KEY, (B, F, D), dtype)
    out = ops.dot_interaction(x, block_b=16, interpret=True)
    expect = ref.dot_interaction_ref(x)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               **tol(dtype))


@given(st.integers(0, 2048), st.integers(1, 600), st.integers(0, 400),
       st.integers(0, 500), st.integers(0, 4))
@settings(max_examples=25, deadline=None)
def test_shed_partition_matches_oracle(n_valid, ucap, uthr, budget,
                                       cache_stride):
    N = 2048
    keys = jnp.arange(1, N + 1, dtype=jnp.uint32)
    valid = jnp.arange(N) < n_valid
    cache = TC.init(256, 4)
    if cache_stride:
        sel = keys[::cache_stride + 1]
        cache = TC.insert(cache, sel, jnp.full(sel.shape, 2.5),
                          jnp.ones(sel.shape, bool))
    tier, cval, rank = ops.shed_partition(
        keys, valid, cache["keys"], cache["values"],
        u_capacity=ucap, u_threshold=uthr, budget_dq=budget,
        block_rows=8, interpret=True)
    tier_r, cval_r, rank_r = ref.shed_partition_ref(
        keys, valid, cache["keys"], cache["values"], ucap, uthr, budget)
    assert bool(jnp.all(tier == tier_r))
    assert bool(jnp.all(rank == rank_r))
    np.testing.assert_allclose(np.asarray(cval), np.asarray(cval_r))


# -- shed_partition: fused-drain extensions (budget_total mode, compacted
#    eval ranks) vs the shed_plan + gather_eval_indices oracle ------------

@pytest.mark.parametrize("cache_mode", ["all_miss", "all_hit",
                                        "strided"])
@pytest.mark.parametrize("n,n_valid,budget_is_total", [
    (64, 64, True),        # smaller than one (8,128) block
    (200, 137, False),     # not lane-aligned, partial validity
    (1000, 1000, True),    # ragged tail inside the last block
    (1000, 0, True),       # all padding
    (3333, 2048, True),    # multi-block with ragged tail
    (4096, 4096, False),   # exactly block-aligned
])
def test_shed_partition_lane_tiled_ragged_tails(n, n_valid,
                                                budget_is_total,
                                                cache_mode):
    """The (8,128)-lane-tiled kernel pads arbitrary N internally: tier,
    cached value and compacted rank must match the 1-D oracle exactly
    for ragged tails, sub-block batches, all-hit and all-miss caches —
    no chunk/block alignment requirement survives the retile."""
    keys = jnp.arange(1, n + 1, dtype=jnp.uint32)
    valid = jnp.arange(n) < n_valid
    cache = _probe_cache(keys, cache_mode)
    ucap, uthr, budget = 256, 128, 300
    tier, cval, rank = ops.shed_partition(
        keys, valid, cache["keys"], cache["values"],
        u_capacity=ucap, u_threshold=uthr, budget_dq=budget,
        budget_is_total=budget_is_total, interpret=True)
    tier_r, cval_r, rank_r = ref.shed_partition_ref(
        keys, valid, cache["keys"], cache["values"], ucap, uthr,
        budget, budget_is_total=budget_is_total)
    assert tier.shape == (n,)
    assert bool(jnp.all(tier == tier_r))
    assert bool(jnp.all(rank == rank_r))
    np.testing.assert_allclose(np.asarray(cval), np.asarray(cval_r))


def test_shed_partition_vmem_budget_fits_production_config():
    """The VMEM claim at the production config (4 ways, (8,128)
    blocks): the Trust DB is probed by an XLA gather and stays in HBM,
    so the kernel claims only its double-buffered blocks — far under
    the ~16 MiB per-core budget, at any cache size."""
    from repro.kernels.shed_partition import shed_partition_vmem_bytes
    budget = shed_partition_vmem_bytes(4)
    # keys, valid, 4 candidate keys + 4 values in; tier, cval, rank out
    blocks = (5 + 2 * 4) * 8 * 128 * 4
    assert budget >= 2 * blocks            # never under-claims a block
    assert budget < 1 << 20
    # taller blocks scale the claim linearly, still nowhere near VMEM
    assert shed_partition_vmem_bytes(4, block_rows=64) < 16 * (1 << 20)


@pytest.mark.parametrize("cache_mode", ["all_miss", "all_hit",
                                        "strided"])
@pytest.mark.parametrize("n,n_valid", [
    (0, 0),                # empty batch (wrapper pads a whole block)
    (64, 64),              # smaller than one (8,128) block
    (1000, 0),             # all padding
    (3333, 2048),          # multi-block with ragged tail
])
def test_shed_partition_probe_one_row_per_way(n, n_valid, cache_mode):
    """The probe over the (n_ways, n_slots) Trust DB: the kernel agrees
    with the host oracle on tier, cached value and compacted rank across
    ragged tails, all-hit / all-miss caches and the empty batch, and
    every valid key found in its slot's column of the way rows (a host
    scan) is answered CACHED with the value stored there."""
    keys = jnp.arange(1, n + 1, dtype=jnp.uint32)
    valid = jnp.arange(n) < n_valid
    ucap, uthr, budget = 256, 128, 300
    cache = TC.init(256, 4)
    if cache_mode != "all_miss":
        sel = keys if cache_mode == "all_hit" else keys[::3]
        cache = TC.insert(cache, sel,
                          jnp.linspace(0.5, 4.5, sel.shape[0]),
                          jnp.ones(sel.shape, bool))
    assert cache["keys"].shape == (4, 256)
    tier, cval, rank = ops.shed_partition(
        keys, valid, cache["keys"], cache["values"],
        u_capacity=ucap, u_threshold=uthr, budget_dq=budget,
        budget_is_total=True, interpret=True)
    tier_r, cval_r, rank_r = ref.shed_partition_ref(
        keys, valid, cache["keys"], cache["values"], ucap, uthr,
        budget, budget_is_total=True)
    assert tier.shape == (n,)
    assert bool(jnp.all(tier == tier_r))
    assert bool(jnp.all(rank == rank_r))
    np.testing.assert_allclose(np.asarray(cval), np.asarray(cval_r))
    slot = np.asarray(TC._hash32(keys)) % 256
    rows_k = np.asarray(cache["keys"])[:, slot]           # (ways, n)
    rows_v = np.asarray(cache["values"])[:, slot]
    match = rows_k == np.asarray(keys)[None, :]
    hit = match.any(axis=0) & np.asarray(valid)
    want = np.where(hit, rows_v[match.argmax(axis=0), np.arange(n)], 0.0)
    np.testing.assert_array_equal(np.asarray(tier)[hit], TIER_CACHED)
    np.testing.assert_array_equal(np.asarray(cval)[np.asarray(valid)],
                                  want[np.asarray(valid)])
    assert hit.any() == (cache_mode != "all_miss" and n_valid > 0)


def _probe_cache(keys, mode: str, n_slots=256, n_ways=4):
    """Cold / fully-warm / strided cache states."""
    cache = TC.init(n_slots, n_ways)
    if mode == "all_miss":
        return cache
    sel = keys if mode == "all_hit" else keys[::3]
    return TC.insert(cache, sel,
                     jnp.linspace(0.5, 4.5, sel.shape[0]),
                     jnp.ones(sel.shape, bool))


@pytest.mark.parametrize("cache_mode", ["all_miss", "all_hit", "strided"])
@pytest.mark.parametrize("n_valid,ucap,uthr", [
    (200, 256, 128),       # Normal: uload <= Ucapacity
    (300, 256, 128),       # Heavy: Ucap < uload <= Ucap + Uthr
    (512, 256, 128),       # Very Heavy: uload > Ucap + Uthr
    (512, 256, 0),         # Very Heavy with zero threshold
    (0, 256, 128),         # empty batch: all padding
    (437, 256, 128),       # padding tail not block-aligned
])
def test_shed_partition_budget_total_matches_shed_plan(
        n_valid, ucap, uthr, cache_mode):
    """budget_is_total mode must reproduce shed_plan tiers bit-for-bit
    (the kernel nets normal-queue evals out of the total in-flight) and
    the compacted ranks must match gather_eval_indices' arrival order."""
    from repro.core.shedder import (eval_indices_from_rank,
                                    gather_eval_indices, shed_plan)
    N = 512
    keys = jnp.arange(1, N + 1, dtype=jnp.uint32)
    valid = jnp.arange(N) < n_valid
    cache = _probe_cache(keys, cache_mode)
    plan_kw = dict(deadline_s=0.5, overload_deadline_s=1.0,
                   very_heavy_weight=0.5)
    _, hit = TC.lookup(cache, keys)
    plan = shed_plan(valid, hit, ucap, uthr, **plan_kw)
    rate = jnp.float32(ucap) / jnp.float32(plan_kw["deadline_s"])
    budget_total = int(jnp.floor(rate * plan["deadline_eff"]))

    tier, cval, rank = ops.shed_partition(
        keys, valid, cache["keys"], cache["values"],
        u_capacity=ucap, u_threshold=uthr, budget_dq=budget_total,
        budget_is_total=True, block_rows=8, interpret=True)
    assert bool(jnp.all(tier == plan["tier"]))
    # kernel and pure-jnp oracle agree in budget_total mode too
    tier_r, cval_r, rank_r = ref.shed_partition_ref(
        keys, valid, cache["keys"], cache["values"], ucap, uthr,
        budget_total, budget_is_total=True)
    assert bool(jnp.all(tier == tier_r))
    assert bool(jnp.all(rank == rank_r))
    np.testing.assert_allclose(np.asarray(cval), np.asarray(cval_r))
    # cached values surface only on CACHED tiers, and padding is INVALID
    from repro.core.shedder import TIER_CACHED, TIER_INVALID
    assert bool(jnp.all((np.asarray(cval) != 0)
                        <= (tier == TIER_CACHED)))
    assert bool(jnp.all(tier[n_valid:] == TIER_INVALID))

    # compacted ranks: 0..k-1 in arrival order over EVAL items, -1 rest
    max_evals = N
    idx_o, valid_o = gather_eval_indices(plan["tier"], max_evals)
    idx_k, valid_k = eval_indices_from_rank(rank, max_evals)
    assert bool(jnp.all(valid_o == valid_k))
    assert bool(jnp.all(jnp.where(valid_o, idx_o, -1)
                        == jnp.where(valid_k, idx_k, -1)))


@given(st.integers(0, 256), st.integers(1, 300), st.integers(0, 128),
       st.integers(1, 256))
@settings(max_examples=25, deadline=None)
def test_eval_indices_from_rank_matches_gather(n_valid, ucap, budget,
                                               max_evals):
    """The O(N) scatter compaction equals the argsort-based gather for
    every budget/max_evals combination (including max_evals smaller
    than the number of EVAL items)."""
    from repro.core.shedder import (eval_indices_from_rank,
                                    gather_eval_indices)
    N = 256
    keys = jnp.arange(1, N + 1, dtype=jnp.uint32)
    valid = jnp.arange(N) < n_valid
    cache = _probe_cache(keys, "strided")
    tier, _, rank = ops.shed_partition(
        keys, valid, cache["keys"], cache["values"],
        u_capacity=ucap, u_threshold=64, budget_dq=budget,
        block_rows=8, interpret=True)
    idx_o, valid_o = gather_eval_indices(tier, max_evals)
    idx_k, valid_k = eval_indices_from_rank(rank, max_evals)
    assert bool(jnp.all(valid_o == valid_k))
    assert bool(jnp.all(jnp.where(valid_o, idx_o, -1)
                        == jnp.where(valid_k, idx_k, -1)))


# ---------------------------------------------------------------------------
# topk_select (retrieval candidate selection)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k", [
    (5, 3),            # sub-block, k < n
    (128, 8),          # one lane row
    (1024, 16),        # one (8,128) block exactly
    (1500, 100),       # ragged tail block
    (3000, 1024),      # k spans multiple candidate rows
    (17, 17),          # k == n
    (2048, 1),         # single winner
])
def test_topk_select_matches_ref(n, k):
    r = np.random.default_rng(n * 1000 + k)
    # heavy ties: quantized scores force index tie-breaks everywhere
    scores = jnp.asarray(np.round(r.normal(size=n) * 4) / 4, jnp.float32)
    vals, idxs = ops.topk_select(scores, k=k, interpret=True)
    vref, iref = ref.topk_select_ref(scores, k)
    np.testing.assert_array_equal(np.asarray(idxs), np.asarray(iref))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(vref))


def test_topk_select_all_neg_inf_and_duplicates():
    """Padding-valued inputs must not wedge the selection loop, and a
    run of identical scores must come out in ascending index order."""
    neg = jnp.full((256,), ref.NEG_INF, jnp.float32)
    vals, idxs = ops.topk_select(neg, k=8, interpret=True)
    assert sorted(np.asarray(idxs).tolist()) == \
        np.asarray(idxs).tolist()                  # unique ascending
    assert len(set(np.asarray(idxs).tolist())) == 8
    same = jnp.ones((300,), jnp.float32) * 2.5
    vals, idxs = ops.topk_select(same, k=12, interpret=True)
    np.testing.assert_array_equal(np.asarray(idxs), np.arange(12))
    np.testing.assert_allclose(np.asarray(vals), np.full(12, 2.5))


@given(st.integers(1, 600), st.integers(1, 64), st.integers(0, 10**6))
@settings(max_examples=20, deadline=None)
def test_topk_select_hypothesis(n, k, seed):
    k = min(k, n)
    r = np.random.default_rng(seed)
    scores = jnp.asarray(
        np.round(r.normal(size=n) * 8) / 8, jnp.float32)
    vals, idxs = ops.topk_select(scores, k=k, interpret=True)
    vref, iref = ref.topk_select_ref(scores, k)
    np.testing.assert_array_equal(np.asarray(idxs), np.asarray(iref))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(vref))
