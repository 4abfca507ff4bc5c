"""Trust DB (paper §4): a jit-compatible set-associative cache in HBM.

The paper's Trust DB is an SQL store probed per URL; a host round-trip per
item would dominate the serving step on TPU, so the DB becomes a fixed-
capacity set-associative hash cache held in device arrays and probed with
vectorized hashing inside the step function (DESIGN.md §2). Eviction is
oldest-age within the set (LRU over ways). Key 0 is reserved for "empty".

Layout: **(n_ways, n_slots)** — each way is one contiguous slot-indexed
row, so :func:`candidates` (the probe's gather, shared by :func:`lookup`
and the ``shed_partition`` kernel wrapper) reads one row per way.

Purely functional: every op returns a new state pytree, so the cache
threads through jit/pjit and checkpoints like any other model state.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def _hash32(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix32-style avalanche hash on uint32."""
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def init(n_slots: int, n_ways: int) -> Dict[str, jnp.ndarray]:
    shape = (n_ways, n_slots)
    return {
        "keys": jnp.zeros(shape, jnp.uint32),
        "values": jnp.zeros(shape, jnp.float32),
        "age": jnp.zeros(shape, jnp.int32),
        "clock": jnp.zeros((), jnp.int32),
    }


def candidates(cache_keys: jnp.ndarray, cache_values: jnp.ndarray,
               keys: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The set each key hashes to, one row per way: ``(cand_keys,
    cand_values)``, each (n_ways, N). This XLA gather is the probe's
    only data-dependent read; the ``shed_partition`` kernel consumes
    its output as lane-shaped blocks."""
    n_slots = cache_keys.shape[1]
    slot = (_hash32(keys) % jnp.uint32(n_slots)).astype(jnp.int32)
    return cache_keys[:, slot], cache_values[:, slot]


def lookup(state: Dict, keys: jnp.ndarray
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """keys: (N,) uint32 (nonzero) -> (values (N,) f32, hit (N,) bool)."""
    cand_k, cand_v = candidates(state["keys"], state["values"], keys)
    match = cand_k == keys[None, :].astype(jnp.uint32)
    hit = jnp.any(match, axis=0) & (keys != 0)
    way = jnp.argmax(match, axis=0)                  # first matching way
    vals = jnp.take_along_axis(cand_v, way[None, :], axis=0)[0]
    return jnp.where(hit, vals, 0.0), hit


def insert(state: Dict, keys: jnp.ndarray, values: jnp.ndarray,
           mask: jnp.ndarray) -> Dict:
    """Insert/update (keys, values) where ``mask``; returns new state.

    Way choice: matching key if present (update) > empty way > oldest age.
    Duplicate slots within the batch resolve last-write-wins.
    """
    n_slots = state["keys"].shape[1]
    keys = keys.astype(jnp.uint32)
    slot = (_hash32(keys) % jnp.uint32(n_slots)).astype(jnp.int32)
    cand_k = state["keys"][:, slot].T                # (N, ways)
    cand_age = state["age"][:, slot].T
    match = cand_k == keys[:, None]
    empty = cand_k == 0
    # priority: match (2^30) > empty (2^20) > -age (older = larger)
    prio = (match.astype(jnp.int32) * (1 << 30)
            + empty.astype(jnp.int32) * (1 << 20)
            - cand_age)
    way = jnp.argmax(prio, axis=-1)                  # (N,)
    ok = mask & (keys != 0)
    # Drop masked writes by pushing the slot out of range.
    w_slot = jnp.where(ok, slot, n_slots)
    clock = state["clock"] + 1
    new_keys = state["keys"].at[way, w_slot].set(keys, mode="drop")
    new_vals = state["values"].at[way, w_slot].set(
        values.astype(jnp.float32), mode="drop")
    new_age = state["age"].at[way, w_slot].set(clock, mode="drop")
    return {"keys": new_keys, "values": new_vals, "age": new_age,
            "clock": clock}


def occupancy(state: Dict) -> jnp.ndarray:
    return jnp.mean((state["keys"] != 0).astype(jnp.float32))
