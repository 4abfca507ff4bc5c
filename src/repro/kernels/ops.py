"""Jit'd public wrappers around the Pallas kernels.

On TPU the kernels compile natively; on any other backend they run in
``interpret=True`` mode (:func:`interpret_mode`). The serving path calls
``shed_partition`` (fused drain) and ``topk_select`` (retrieval); the
attention and interaction kernels are not on it — the models run their
pure-jnp paths, which are also the oracles in the tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.dot_interaction import dot_interaction as _dot_interaction
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.shed_partition import shed_partition as _shed_partition
from repro.kernels.topk_select import topk_select as _topk_select


def interpret_mode() -> bool:
    """Pallas interpret mode, decided by the backend alone: native
    Mosaic kernels on a TPU, the interpreter everywhere else (CPU
    tests). Nothing on a TPU backend runs a kernel interpreted."""
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                   "sm_scale", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    sm_scale=None, block_q=128, block_k=128,
                    interpret=None):
    if interpret is None:
        interpret = interpret_mode()
    return _flash_attention(q, k, v, causal=causal, window=window,
                            softcap=softcap, sm_scale=sm_scale,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret)


@partial(jax.jit, static_argnames=("window", "softcap", "sm_scale",
                                   "block_k", "interpret"))
def flash_decode(q, k_cache, v_cache, lengths, *, window=0, softcap=0.0,
                 sm_scale=None, block_k=256, interpret=None):
    if interpret is None:
        interpret = interpret_mode()
    return _flash_decode(q, k_cache, v_cache, lengths, window=window,
                         softcap=softcap, sm_scale=sm_scale,
                         block_k=block_k, interpret=interpret)


@partial(jax.jit, static_argnames=("block_b", "interpret"))
def dot_interaction(feats, *, block_b=128, interpret=None):
    if interpret is None:
        interpret = interpret_mode()
    return _dot_interaction(feats, block_b=block_b, interpret=interpret)


@partial(jax.jit, static_argnames=("k", "block_rows", "interpret"))
def topk_select(scores, *, k, block_rows=8, interpret=None):
    if interpret is None:
        interpret = interpret_mode()
    return _topk_select(scores, k, block_rows=block_rows,
                        interpret=interpret)


@partial(jax.jit, static_argnames=("u_capacity", "u_threshold",
                                   "budget_dq", "budget_is_total",
                                   "block_rows", "interpret"))
def shed_partition(keys, valid, cache_keys, cache_values, *,
                   u_capacity, u_threshold, budget_dq,
                   budget_is_total=False, block_rows=8,
                   interpret=None):
    if interpret is None:
        interpret = interpret_mode()
    return _shed_partition(keys, valid, cache_keys, cache_values,
                           u_capacity, u_threshold, budget_dq,
                           budget_is_total=budget_is_total,
                           block_rows=block_rows, interpret=interpret)
