"""Pallas TPU tiled partial top-k over a dense score vector.

The retrieval hot op: BM25 produces a dense (N,) score vector per query
(one slot per shard document) and the candidate set is its top-k by
``(score desc, index asc)`` — the same total order the pure-Python
postings scorer produces, so kernel and host oracle agree exactly,
ties included.

Kernel structure: the (N,) scores lay out row-major as (rows, 128) and
the grid walks independent **(block_rows, 128) lane-shaped blocks**
(the native float32 tile is (8, 128)). Each grid step extracts its
block's local top-``kb`` (``kb = min(k, block_items)`` — no global
top-k can take more than k items from one block) with a
``fori_loop``: per round, the running max of not-yet-taken scores is
selected, ties broken by the minimum flat index, and the winner is
recorded into a (cand_rows, 128) candidate block via a row-major
position mask — vector ops only, no 1-D reshapes, no dynamic stores.
An explicit ``taken`` mask (not NEG_INF overwriting, int32 because
Mosaic carries no bool vector through a loop) breaks ties:
once every untaken score IS ``NEG_INF``, masked re-selection would
loop on one position forever, while the taken mask keeps emitting
fresh indices in ascending order.

Blocks are independent — no SMEM carry — so the grid can in principle
run in any order; the host wrapper then merges the per-block candidate
lists with one ``lexsort`` by ``(score desc, index asc)`` and keeps the
first k. Filler candidate slots carry ``(NEG_INF, INT32_MAX)`` so they
sort strictly after every genuine candidate, including genuine
``NEG_INF`` ones.

Ragged tails: the host pads N up to a whole number of blocks with
``NEG_INF`` scores; padding can only surface when ``k`` exceeds the
number of finite scores, and comes back with value ``NEG_INF``.

Caveat: scores containing BOTH +0.0 and -0.0 may order differently
from the oracle (the kernel compares raw scores, the oracle sorts
negated ones). BM25 scores are non-negative sums of positive weights,
so the retrieval path never produces -0.0.

Matches ``ref.topk_select_ref``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF

LANES = 128          # last-dim tile width (every dtype)
SUBLANES = 8         # float32/int32 sublane tile height
_INT_MAX = jnp.iinfo(jnp.int32).max


def _cand_rows(kb: int) -> int:
    """Sublane height of one candidate block: kb slots rounded up to a
    whole (8, 128) float32 tile."""
    rows = -(-kb // LANES)
    return -(-rows // SUBLANES) * SUBLANES


def topk_select_vmem_bytes(block_rows: int, kb: int) -> int:
    """Measured VMEM budget of one grid step: the double-buffered score
    block plus the two candidate output blocks (all 4-byte lanes)."""
    blocks = (block_rows + 2 * _cand_rows(kb)) * LANES * 4
    return 2 * blocks + (128 << 10)          # 128 KiB slack


def _topk_kernel(scores_ref, cand_v_ref, cand_i_ref, *,
                 block_rows: int, kb: int):
    i = pl.program_id(0)
    scores = scores_ref[...]                       # (block_rows, 128)
    rows = _cand_rows(kb)

    # Row-major flat positions, built from 2-D iotas (1-D iota does not
    # lower on TPU).
    r_in = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 0)
    c_in = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 1)
    flat_in = r_in * LANES + c_in                  # position in block
    r_out = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    c_out = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    flat_out = r_out * LANES + c_out               # candidate slot id

    base = i * block_rows * LANES                  # global index offset

    def round_j(j, carry):
        # ``taken`` is an int32 0/1 mask: Mosaic cannot carry a bool
        # vector through a loop (scf.for does not legalize).
        taken, cand_v, cand_i = carry
        untaken = taken == 0
        masked = jnp.where(untaken, scores, NEG_INF)
        m = jnp.max(masked)
        # winner = minimum flat index among untaken maxima (tie-break)
        at_max = (masked == m) & untaken
        sel = jnp.min(jnp.where(at_max, flat_in, _INT_MAX))
        taken = jnp.where(flat_in == sel, 1, taken)
        write = flat_out == j
        cand_v = jnp.where(write, m, cand_v)
        cand_i = jnp.where(write, base + sel, cand_i)
        return taken, cand_v, cand_i

    taken0 = jnp.zeros((block_rows, LANES), jnp.int32)
    v0 = jnp.full((rows, LANES), NEG_INF, jnp.float32)
    i0 = jnp.full((rows, LANES), _INT_MAX, jnp.int32)
    _, cand_v, cand_i = jax.lax.fori_loop(
        0, kb, round_j, (taken0, v0, i0))
    cand_v_ref[...] = cand_v
    cand_i_ref[...] = cand_i


def topk_select(scores: jnp.ndarray, k: int, *,
                block_rows: int = SUBLANES, interpret: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """scores: (N,) float32; 1 <= k <= N (k static).

    Returns ``(values (k,) f32, indices (k,) int32)`` ordered by
    ``(score desc, index asc)`` — exactly ``ref.topk_select_ref``.

    ``block_rows`` sets the sublane height of each (block_rows, 128)
    grid block (multiples of 8 — the float32 tile). Any N is accepted:
    the tail pads to a whole block with ``NEG_INF`` scores.
    """
    n = scores.shape[0]
    if not 1 <= k <= n:
        raise ValueError(f"k must be in [1, {n}], got {k}")
    if block_rows % SUBLANES:
        raise ValueError(
            f"block_rows must be a multiple of {SUBLANES} "
            f"(the float32 sublane tile), got {block_rows}")
    block_items = block_rows * LANES
    n_pad = -n % block_items
    scores_p = scores.astype(jnp.float32)
    if n_pad:
        scores_p = jnp.concatenate(
            [scores_p, jnp.full((n_pad,), NEG_INF, jnp.float32)])
    rows = (n + n_pad) // LANES
    n_blocks = rows // block_rows
    kb = min(k, block_items)
    crows = _cand_rows(kb)

    kernel = functools.partial(_topk_kernel, block_rows=block_rows,
                               kb=kb)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=topk_select_vmem_bytes(block_rows, kb))
    cand_v, cand_i = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((block_rows, LANES),
                               lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((crows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((crows, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks * crows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks * crows, LANES), jnp.int32),
        ],
        interpret=interpret,
        **kwargs,
    )(scores_p.reshape(rows, LANES))

    # Merge: per-block candidates -> global (score desc, index asc).
    vals = cand_v.reshape(-1)
    idxs = cand_i.reshape(-1)
    order = jnp.lexsort((idxs, -vals))[:k]
    return vals[order], idxs[order]
