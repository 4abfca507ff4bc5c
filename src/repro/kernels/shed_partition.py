"""Pallas TPU fused Trust-DB probe + load-shedding tier assignment.

The paper's hot scheduling op (§5): for a stream of N candidate URLs,
(1) probe the Trust DB cache, (2) split into Normal/Drop queues by arrival
position vs Ucapacity, (3) grant drop-queue evaluation slots up to the
deadline budget, (4) everything else falls to the average-trust prior.

Kernel structure: the (N,) arrival stream is laid out row-major as
(rows, 128) and the grid walks **(block_rows, 128) lane-shaped blocks**
— the native float32/int32 TPU tile is (8, 128). Arrival order is
row-major within a block; the running scans are two-pass 2-D cumsums
(cumsum along lanes, then a sublane offset of row totals) — vector ops
only, no 1-D reshapes.

The Trust-DB probe is split at the one data-dependent read. The
wrapper gathers each key's set with XLA (``trust_cache.candidates``:
hash -> slot -> one (n_ways, N) row per way, from either cache layout)
and hands the kernel those candidates as (n_ways, block_rows, 128)
blocks; the kernel compares them against the keys way by way (first
matching way wins, as in ``trust_cache.lookup``). Mosaic has no
vector gather from a VMEM ref, so a kernel that indexes the cache by
slot does not lower; with the gather outside, the cache stays in HBM,
its size never enters the kernel's VMEM claim, and
:func:`shed_partition_vmem_bytes` counts only the double-buffered
blocks, handed to the compiler as ``vmem_limit_bytes``.

Running counters (valid-so-far, drop-queue-evals-so-far, normal-queue
evals, EVAL-tier items) live in SMEM scratch and carry across the
sequential grid, making the tier assignment an exact scan without host
round-trips.

Ragged tails: the host wrapper pads N up to a whole number of blocks
and marks the tail invalid — padding rows never touch the counters and
come back ``TIER_INVALID``, so any N (chunk-aligned or not) runs
without a shape constraint.

Outputs per item: tier code, cached value, and — for the fused serving
drain — a **compacted eval rank**: the arrival-ordered position of
every EVAL-tier item among all EVAL-tier items (-1 otherwise), carried
by an SMEM write-cursor. Downstream the rank converts to a static-size
gather index list with ONE O(N) scatter
(``core.shedder.eval_indices_from_rank``) instead of the O(N log N)
argsort in ``gather_eval_indices``.

Budget modes:
  * ``budget_is_total=False`` (legacy) — ``budget`` is the drop-queue
    evaluation budget already net of normal-queue evaluations.
  * ``budget_is_total=True`` — ``budget`` is ``floor(rate *
    deadline_eff)``, the TOTAL evaluation budget of ``shed_plan``; the
    kernel derives the drop-queue share in-flight from its running
    normal-queue eval counter (every normal-queue item precedes every
    drop-queue item in arrival order, so the running count is already
    final when the first drop-queue candidate is scanned). This is what
    lets the fused drain match ``shed_plan`` bit-for-bit without a
    separate host-side cache probe.

Matches ``repro.core.shedder.shed_plan`` + ``trust_cache.lookup`` (the
oracle in ``ref.py``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.shedder import (TIER_CACHED, TIER_EVAL, TIER_INVALID,
                                TIER_PRIOR)
from repro.core import trust_cache as TC

LANES = 128          # last-dim tile width (every dtype)
SUBLANES = 8         # float32/int32 sublane tile height


def _cumsum_rowmajor(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumulative sum in row-major (arrival) order over a
    (rows, LANES) block of 0/1 int32 flags.

    Mosaic has no cumsum, so the scan is two matmuls against 0/1
    triangles: ``x @ U`` is the lane-axis prefix sum, and ``Ls @ (x @
    J)`` adds the totals of all preceding rows (``J`` all ones, ``Ls``
    strictly lower-triangular). Every operand is a small integer
    (flags, row totals <= 128), exact in bf16, and the f32 accumulator
    is exact far past any block's count — the result is the integer
    scan, not an approximation of it."""
    rows = x.shape[0]
    xf = x.astype(jnp.float32)
    r = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    upper = (r <= c).astype(jnp.float32)             # U[i, j] = i <= j
    ones = jnp.ones((LANES, LANES), jnp.float32)
    rr = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    rc = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    lower = (rc < rr).astype(jnp.float32)            # Ls[i, j] = j < i
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    lane = dot(xf, upper)
    row_off = dot(lower, dot(xf, ones))
    return (lane + row_off).astype(jnp.int32)


def shed_partition_vmem_bytes(n_ways: int,
                              block_rows: int = SUBLANES) -> int:
    """VMEM claim of one grid step: the double-buffered in/out blocks
    (keys, valid, n_ways candidate keys and values; tier, cval, rank —
    all 4-byte lanes) plus scratch slack. The Trust DB itself stays in
    HBM, so its size does not appear."""
    blocks = (5 + 2 * n_ways) * block_rows * LANES * 4
    return 2 * blocks + (128 << 10)          # 128 KiB slack


def _shed_kernel(params_ref,              # SMEM: [ucap, uthr, budget]
                 keys_ref, valid_ref, ck_ref, cv_ref,
                 tier_ref, cval_ref, rank_ref,
                 cnt_scr, *, block_rows: int, n_ways: int,
                 budget_is_total: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_scr[0] = 0        # valid items so far
        cnt_scr[1] = 0        # drop-queue eval candidates so far
        cnt_scr[2] = 0        # normal-queue evals so far
        cnt_scr[3] = 0        # EVAL-tier items so far (compaction cursor)

    ucap = params_ref[0]
    budget = params_ref[2]

    keys = keys_ref[...]                           # (block_rows, 128)
    valid = valid_ref[...] != 0

    # --- Trust DB probe: compare each way's gathered candidates ---
    hit = jnp.zeros((block_rows, LANES), jnp.bool_)
    val = jnp.zeros((block_rows, LANES), jnp.float32)
    for w in range(n_ways):                        # ways unrolled
        ck = ck_ref[w]                             # (block_rows, 128)
        cv = cv_ref[w]
        m = (ck == keys) & (keys != jnp.uint32(0))
        val = jnp.where(m & ~hit, cv, val)
        hit = hit | m
    hit = hit & valid

    # --- arrival position scan (exclusive running counts, row-major) ---
    base_valid = cnt_scr[0]
    v32 = valid.astype(jnp.int32)
    pos = base_valid + _cumsum_rowmajor(v32) - v32   # 0-based position
    in_normal = valid & (pos < ucap)

    tier = jnp.where(hit, TIER_CACHED, TIER_PRIOR)
    tier = jnp.where(in_normal & ~hit, TIER_EVAL, tier)

    # Normal-queue eval count: inclusive scan. All normal-queue items
    # precede all drop-queue items in arrival order, so at any drop-queue
    # candidate the inclusive count is already the batch total.
    ne32 = (in_normal & ~hit).astype(jnp.int32)
    base_ne = cnt_scr[2]
    ne_incl = base_ne + _cumsum_rowmajor(ne32)

    dq_cand = valid & ~in_normal & ~hit
    d32 = dq_cand.astype(jnp.int32)
    base_dq = cnt_scr[1]
    dq_rank = base_dq + _cumsum_rowmajor(d32) - d32
    if budget_is_total:
        # shed_plan: budget_dq = max(budget_total - n_normal_evals, 0);
        # dq_rank >= 0 makes the max() implicit.
        dq_budget = budget - ne_incl
    else:
        dq_budget = jnp.broadcast_to(budget, (block_rows, LANES))
    tier = jnp.where(dq_cand & (dq_rank < dq_budget), TIER_EVAL, tier)
    tier = jnp.where(valid, tier, TIER_INVALID)

    # --- compacted eval rank (SMEM write-cursor across the grid) ---
    is_eval = tier == TIER_EVAL
    e32 = is_eval.astype(jnp.int32)
    base_e = cnt_scr[3]
    erank = base_e + _cumsum_rowmajor(e32) - e32

    cnt_scr[0] = base_valid + jnp.sum(v32)
    cnt_scr[1] = base_dq + jnp.sum(d32)
    cnt_scr[2] = base_ne + jnp.sum(ne32)
    cnt_scr[3] = base_e + jnp.sum(e32)

    tier_ref[...] = tier.astype(jnp.int32)
    cval_ref[...] = jnp.where(hit, val, 0.0)
    rank_ref[...] = jnp.where(is_eval, erank, -1).astype(jnp.int32)


def shed_partition(keys: jnp.ndarray, valid: jnp.ndarray,
                   cache_keys: jnp.ndarray, cache_values: jnp.ndarray,
                   u_capacity, u_threshold, budget_dq, *,
                   budget_is_total: bool = False,
                   block_rows: int = SUBLANES, interpret: bool = False
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """keys: (N,) uint32; valid: (N,) bool; cache_*: (ways, slots) in
    the default ways-leading layout, or legacy (slots, ways) — the
    layout is inferred from the shape (``trust_cache.dims``) by the
    XLA probe gather, never by the kernel.

    Returns (tier (N,) int32, cached_vals (N,) f32, eval_rank (N,)
    int32). ``eval_rank`` is the arrival-ordered compacted position of
    each EVAL-tier item (-1 for every other tier). ``budget_dq`` is the
    drop-queue evaluation budget already derived from the effective
    deadline (``core.shedder.shed_plan`` computes it identically) — or,
    with ``budget_is_total=True``, the TOTAL eval budget
    ``floor(rate * deadline_eff)`` from which the kernel derives the
    drop-queue share itself.

    ``block_rows`` sets the sublane height of each (block_rows, 128)
    grid block (multiples of 8 — the float32 tile). Any N is accepted:
    the tail is padded to a whole block and masked invalid.
    """
    n = keys.shape[0]
    if block_rows % SUBLANES:
        raise ValueError(
            f"block_rows must be a multiple of {SUBLANES} "
            f"(the float32 sublane tile), got {block_rows}")
    block_items = block_rows * LANES
    n_pad = max(-n % block_items, block_items if n == 0 else 0)
    keys_p = jnp.concatenate(
        [keys.astype(jnp.uint32),
         jnp.zeros((n_pad,), jnp.uint32)]) if n_pad else \
        keys.astype(jnp.uint32)
    valid_p = jnp.concatenate(
        [valid.astype(jnp.int32),
         jnp.zeros((n_pad,), jnp.int32)]) if n_pad else \
        valid.astype(jnp.int32)
    rows = (n + n_pad) // LANES
    keys2 = keys_p.reshape(rows, LANES)
    valid2 = valid_p.reshape(rows, LANES)
    cand_k, cand_v = TC.candidates(cache_keys, cache_values, keys_p)
    n_ways = cand_k.shape[0]
    cand_k = cand_k.reshape(n_ways, rows, LANES)
    cand_v = cand_v.astype(jnp.float32).reshape(n_ways, rows, LANES)
    params = jnp.asarray([u_capacity, u_threshold, budget_dq], jnp.int32)

    kernel = functools.partial(_shed_kernel, block_rows=block_rows,
                               n_ways=n_ways,
                               budget_is_total=budget_is_total)
    kwargs = {}
    if not interpret:
        # Hand the compiler the kernel's own claim: double-buffered
        # blocks, nothing more is needed.
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=shed_partition_vmem_bytes(n_ways,
                                                       block_rows))
    block = pl.BlockSpec((block_rows, LANES), lambda i, *_: (i, 0))
    ways_block = pl.BlockSpec((n_ways, block_rows, LANES),
                              lambda i, *_: (0, i, 0))
    tier, cval, rank = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // block_rows,),
            in_specs=[block, block, ways_block, ways_block],
            out_specs=[block, block, block],
            scratch_shapes=[pltpu.SMEM((4,), jnp.int32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        ],
        interpret=interpret,
        **kwargs,
    )(params, keys2, valid2, cand_k, cand_v)
    return (tier.reshape(-1)[:n], cval.reshape(-1)[:n],
            rank.reshape(-1)[:n])
