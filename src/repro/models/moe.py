"""Mixture-of-Experts FFN with top-k routing.

Three dispatches (``MoEConfig.dispatch``):

* ``dense_scatter``: sort-based (MegaBlocks-style grouped compute adapted
  to TPU): tokens are argsorted by destination expert, scattered into a
  fixed ``(n_experts, capacity, d_model)`` buffer (static shapes —
  XLA/SPMD friendly), pushed through a grouped SwiGLU einsum, and
  scattered back. The ``(E, C, D)`` buffer shards cleanly: E over the
  ``model`` axis (EP).
* ``ep_shard_map``: the same per expert-parallel shard (below).
* ``dropless``: the experts this device holds, as a grouped matmul over
  their sorted (token, expert) rows (:func:`dropless_apply`); no token is
  ever cut.

The first two cut each expert at a capacity: a (token, expert) pair past
it is *dropped from expert compute* and keeps only the residual path.
That is a different answer, not a degraded one: a dropped token's output
depends on which other tokens share its batch, and nothing reports it
(no PRIOR tier, no reason). So they serve training, where
``moe_drop_frac`` watches it, and an evaluator whose answer must not
depend on its batch (the trust scorer) uses ``dropless``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models import layers as L


def moe_init(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 5)
    E, F = cfg.n_experts, cfg.d_expert
    std_in = math.sqrt(1.0 / d_model)
    std_out = math.sqrt(1.0 / F)
    p = {
        "router": {"w": L.trunc_normal(ks[0], (d_model, E), std_in, dtype)},
        "w_gate": L.trunc_normal(ks[1], (E, d_model, F), std_in, dtype),
        "w_up": L.trunc_normal(ks[2], (E, d_model, F), std_in, dtype),
        "w_down": L.trunc_normal(ks[3], (E, F, d_model), std_out, dtype),
    }
    if cfg.n_shared_experts > 0:
        d_sh = (cfg.d_shared or cfg.d_expert) * cfg.n_shared_experts
        p["shared"] = L.glu_ffn_init(ks[4], d_model, d_sh, dtype)
    return p


def capacity(n_tokens: int, cfg: MoEConfig) -> int:
    c = int(math.ceil(cfg.capacity_factor * cfg.top_k * n_tokens
                      / cfg.n_experts))
    return max(8, ((c + 7) // 8) * 8)       # pad to MXU-friendly multiple


def moe_apply(p: Dict, x: jnp.ndarray, cfg: MoEConfig, *,
              act: str = "silu", compute_dtype=jnp.bfloat16
              ) -> Tuple[jnp.ndarray, Dict]:
    """x: (T, D) flattened tokens -> (out (T, D), metrics dict).

    Metrics carry the router aux loss (load balance) and the fraction of
    (token, expert) pairs cut at capacity.
    """
    T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = capacity(T, cfg)
    xc = x.astype(compute_dtype)

    # --- Router (fp32 for numerics) ---
    logits = (x.astype(jnp.float32)
              @ p["router"]["w"].astype(jnp.float32))          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, K)                  # (T, K)
    if cfg.norm_topk_prob:
        topk_w = topk_w / jnp.maximum(
            jnp.sum(topk_w, axis=-1, keepdims=True), 1e-9)

    # --- Sort-based dispatch plan ---
    flat_e = topk_idx.reshape(T * K)
    sort_idx = jnp.argsort(flat_e)                              # group by e
    sorted_e = flat_e[sort_idx]
    seg_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_in_e = jnp.arange(T * K) - seg_start                    # rank in expert
    token_of = sort_idx // K
    keep = pos_in_e < C
    safe_pos = jnp.where(keep, pos_in_e, C)                     # OOB -> drop

    # --- Scatter tokens into the expert buffer (E, C, D) ---
    buf = jnp.zeros((E, C, D), compute_dtype)
    buf = buf.at[sorted_e, safe_pos].set(xc[token_of], mode="drop")

    # --- Grouped expert SwiGLU ---
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(compute_dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(compute_dtype))
    if act == "silu":
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(g, approximate=True) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h,
                         p["w_down"].astype(compute_dtype))      # (E, C, D)

    # --- Gather back + weighted combine ---
    flat_w = topk_w.reshape(T * K)[sort_idx]
    contrib = out_buf[sorted_e, safe_pos]                        # (T*K, D)
    contrib = contrib * (flat_w * keep)[:, None].astype(compute_dtype)
    out = jnp.zeros((T, D), compute_dtype).at[token_of].add(contrib)

    # --- Shared experts (DeepSeek/Moonlight layout) ---
    if "shared" in p:
        out = out + L.glu_ffn_apply(p["shared"], xc, act=act,
                                    compute_dtype=compute_dtype)

    # --- Load-balance aux loss (Switch-style) + drop metrics ---
    me = jnp.mean(probs, axis=0)                                 # (E,)
    one_hot = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)     # (T,K,E)
    ce = jnp.mean(jnp.sum(one_hot, axis=1), axis=0) / K          # frac routed
    aux = cfg.router_aux_loss * E * jnp.sum(me * ce)
    dropped = 1.0 - jnp.sum(keep) / (T * K)
    return out.astype(x.dtype), {"moe_aux_loss": aux,
                                 "moe_drop_frac": dropped}


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map): §Perf hillclimb iteration — the
# sort-based dispatch above lets XLA partition a *global* sort/scatter,
# which degenerates into replication (23 TB/step of all-reduce measured
# on qwen3-moe train_4k). Here the parallelism is explicit:
#   * tokens stay sharded over (pod, data) and REPLICATED over `model`,
#   * each model shard owns E/n_model experts and dispatches its local
#     tokens to its local experts only (pure local sort/scatter),
#   * partial outputs combine with ONE psum over `model` per layer.
# Shared experts and the router run outside (plain TP). Selected via
# ``MoEConfig.dispatch = "ep_shard_map"``.
# ---------------------------------------------------------------------------

def _local_dispatch_compute(x_loc, topk_w, topk_idx, wg, wu, wd, *,
                            e_offset, e_local, capacity_local, act,
                            compute_dtype):
    """Dispatch local tokens to local experts. x_loc: (T, D); topk_*:
    (T, K); w*: (E_loc, D, F) / (E_loc, F, D). Returns (T, D) partial."""
    T, D = x_loc.shape
    K = topk_idx.shape[1]
    C = capacity_local
    flat_e = topk_idx.reshape(T * K) - e_offset          # local ids
    mine = (flat_e >= 0) & (flat_e < e_local)
    sort_key = jnp.where(mine, flat_e, e_local)          # foreign -> end
    sort_idx = jnp.argsort(sort_key)
    sorted_e = sort_key[sort_idx]
    seg_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_in_e = jnp.arange(T * K) - seg_start
    token_of = sort_idx // K
    keep = (sorted_e < e_local) & (pos_in_e < C)
    safe_e = jnp.where(keep, sorted_e, e_local)
    safe_pos = jnp.where(keep, pos_in_e, C)
    xc = x_loc.astype(compute_dtype)
    buf = jnp.zeros((e_local, C, D), compute_dtype)
    buf = buf.at[safe_e, safe_pos].set(xc[token_of], mode="drop")
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(compute_dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(compute_dtype))
    h = (jax.nn.silu(g) if act == "silu"
         else jax.nn.gelu(g, approximate=True)) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd.astype(compute_dtype))
    # Combine in ORIGINAL slot order: gating weights are used with NO
    # device-varying gather (shard_map's transpose of a gather by the
    # per-shard sort permutation mis-accumulates the tw cotangent —
    # verified against finite differences; tests/test_moe_ep.py).
    inv_pos = jnp.zeros((T * K,), jnp.int32).at[sort_idx].set(
        pos_in_e.astype(jnp.int32))
    inv_keep = jnp.zeros((T * K,), bool).at[sort_idx].set(keep)
    vals = out_buf[flat_e.clip(0, e_local - 1),
                   inv_pos.clip(0, C - 1)]                 # (T*K, D)
    w_flat = (topk_w.reshape(T * K).astype(compute_dtype)
              * inv_keep.astype(compute_dtype))
    return jnp.sum((vals * w_flat[:, None]).reshape(T, K, D), axis=1)


def moe_apply_ep(p: Dict, x: jnp.ndarray, cfg: MoEConfig, *,
                 act: str = "silu", compute_dtype=jnp.bfloat16
                 ) -> Tuple[jnp.ndarray, Dict]:
    """Expert-parallel MoE over the ambient mesh's ``model`` axis.

    Falls back to ``moe_apply`` when no mesh (or no model axis) is
    ambient, so smoke tests and single-device runs are unchanged.
    """
    from repro.distribution.constraints import ambient_mesh, dp_spec
    from jax.sharding import PartitionSpec as P

    mesh = ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return moe_apply(p, x, cfg, act=act, compute_dtype=compute_dtype)

    T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    n_model = mesh.shape["model"]
    dp = dp_spec()
    n_dp = 1
    if dp:
        for a in dp:
            n_dp *= mesh.shape[a]
    if E % n_model != 0 or T % max(n_dp, 1) != 0:
        # tiny/odd token counts (e.g. batch-1 decode) can't shard over
        # the dp axes — the reference dispatch is fine at that scale
        return moe_apply(p, x, cfg, act=act, compute_dtype=compute_dtype)
    e_local = E // n_model
    c_local = capacity(max(T // max(n_dp, 1), 1), cfg)

    # Router outside the EP region (fp32, replicated weights).
    logits = (x.astype(jnp.float32)
              @ p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, K)
    if cfg.norm_topk_prob:
        topk_w = topk_w / jnp.maximum(
            jnp.sum(topk_w, axis=-1, keepdims=True), 1e-9)

    def ep_region(x_loc, tw, ti, wg, wu, wd):
        m_idx = jax.lax.axis_index("model")
        partial = _local_dispatch_compute(
            x_loc, tw, ti, wg, wu, wd,
            e_offset=m_idx * e_local, e_local=e_local,
            capacity_local=c_local, act=act,
            compute_dtype=compute_dtype)
        return jax.lax.psum(partial, "model")

    from repro.distribution.constraints import shard_map

    tok_spec = P(dp, None)
    out = shard_map(
        ep_region, mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec,
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=tok_spec,
    )(x, topk_w, topk_idx.astype(jnp.int32),
      p["w_gate"], p["w_up"], p["w_down"])

    if "shared" in p:
        out = out + L.glu_ffn_apply(p["shared"], x.astype(compute_dtype),
                                    act=act, compute_dtype=compute_dtype)

    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)
    ce = jnp.mean(jnp.sum(one_hot, axis=1), axis=0) / K
    aux = cfg.router_aux_loss * E * jnp.sum(me * ce)
    return out.astype(x.dtype), {"moe_aux_loss": aux,
                                 "moe_drop_frac": jnp.zeros((),
                                                            jnp.float32)}


# ---------------------------------------------------------------------------
# Dropless held experts: the layer of one expert-parallel device. The
# router keeps its full width; this device computes only its own
# experts' part of the result, and what the others would add comes from
# their devices (or, on one chip, is left out: no stand-in is computed).
# ---------------------------------------------------------------------------

def dropless_init(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32
                  ) -> Dict:
    """Router over all ``n_experts``; the held experts' SwiGLU weights as
    ``w_in`` (E_held, D, 2F: gate then up) and ``w_out`` (E_held, F, D)."""
    ks = jax.random.split(key, 4)
    E_h, F = cfg.held, cfg.d_expert
    p = {
        "router": {"w": L.trunc_normal(ks[0], (d_model, cfg.n_experts),
                                       math.sqrt(1.0 / d_model), dtype)},
        "w_in": L.trunc_normal(ks[1], (E_h, d_model, 2 * F),
                               math.sqrt(1.0 / d_model), dtype),
        "w_out": L.trunc_normal(ks[2], (E_h, F, d_model),
                                math.sqrt(1.0 / F), dtype),
    }
    if cfg.n_shared_experts > 0:
        d_sh = (cfg.d_shared or cfg.d_expert) * cfg.n_shared_experts
        p["shared"] = L.glu_ffn_init(ks[3], d_model, d_sh, dtype)
    return p


def route(router_w: jnp.ndarray, x: jnp.ndarray, cfg: MoEConfig
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(gate weights (T, K) f32, expert ids (T, K)) over all experts."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    if cfg.topk_then_softmax:
        top, idx = jax.lax.top_k(logits, cfg.top_k)
        return jax.nn.softmax(top, axis=-1), idx
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, idx


def dropless_apply(p: Dict, x: jnp.ndarray, cfg: MoEConfig, *,
                   act: str = "silu", compute_dtype=jnp.bfloat16
                   ) -> Tuple[jnp.ndarray, Dict]:
    """x: (T, D) -> (this device's part of the layer (T, D), counts).

    The (token, expert) pairs routed to held experts are sorted by
    expert to the front of the T*K pairs and run as one grouped matmul
    (``jax.lax.ragged_dot``) per projection. Its rows are static, T *
    min(K, E_held): each token has K distinct experts, so no more of its
    pairs can land here, and none is ever cut. Each token's output reads
    only its own rows, whatever else the batch holds. The shared expert
    (when ``p`` has one) is added whole.

    Counts (int32): ``expert_rows``, the pairs routed to held experts;
    ``expert_slots``, the rows the grouped matmul ran, padding included.
    """
    T, D = x.shape
    K, E_h = cfg.top_k, cfg.held
    rows = T * min(K, E_h)
    with jax.named_scope("moe_route"):
        gate_w, idx = route(p["router"]["w"], x, cfg)
        local = idx.reshape(T * K) - cfg.held_offset
        mine = (local >= 0) & (local < E_h)
        group = jnp.where(mine, local, E_h)                 # foreign last
        order = jnp.argsort(group, stable=True)[:rows]
        sizes = jnp.zeros((E_h + 1,), jnp.int32).at[group].add(1)[:E_h]
        xs = x.astype(compute_dtype)[order // K]            # (rows, D)
    with jax.named_scope("moe_experts"):
        h = jax.lax.ragged_dot(xs, p["w_in"].astype(compute_dtype), sizes)
        g, u = jnp.split(h, 2, axis=-1)
        h = (jax.nn.silu(g) if act == "silu"
             else jax.nn.gelu(g, approximate=True)) * u
        y = jax.lax.ragged_dot(h, p["w_out"].astype(compute_dtype), sizes)
    with jax.named_scope("moe_route"):
        # Each held pair's row in the sorted order; rows past the groups
        # are never read (``where``, not a product: they hold no value).
        pos = jnp.zeros((T * K,), jnp.int32).at[order].set(
            jnp.arange(rows, dtype=jnp.int32), mode="drop")
        vals = y[jnp.minimum(pos, rows - 1)]
        w_flat = gate_w.reshape(T * K).astype(compute_dtype)
        out = jnp.sum(jnp.where(mine[:, None], vals * w_flat[:, None], 0)
                      .reshape(T, K, D), axis=1)
    if "shared" in p:
        out = out + L.glu_ffn_apply(p["shared"], x.astype(compute_dtype),
                                    act=act, compute_dtype=compute_dtype)
    return out.astype(x.dtype), {"expert_rows": jnp.sum(sizes),
                                 "expert_slots": jnp.int32(rows)}


def init(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> Dict:
    """Parameters in the layout of ``cfg.dispatch``."""
    if cfg.dispatch == "dropless":
        return dropless_init(key, d_model, cfg, dtype)
    return moe_init(key, d_model, cfg, dtype)


def apply(p: Dict, x: jnp.ndarray, cfg: MoEConfig, *, act: str = "silu",
          compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Dict]:
    """Dispatch-mode switch (``MoEConfig.dispatch``)."""
    if cfg.dispatch == "dropless":
        return dropless_apply(p, x, cfg, act=act,
                              compute_dtype=compute_dtype)
    if cfg.dispatch == "ep_shard_map":
        return moe_apply_ep(p, x, cfg, act=act,
                            compute_dtype=compute_dtype)
    return moe_apply(p, x, cfg, act=act, compute_dtype=compute_dtype)
