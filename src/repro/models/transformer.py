"""Decoder-only transformer LM covering all five assigned LM archs.

Features driven entirely by ``TransformerConfig``:
  - GQA with optional QKV bias (qwen2.5), RoPE (configurable theta),
  - SwiGLU / GeGLU FFN, or MoE FFN (moonshot, qwen3-moe),
  - gemma2: alternating local/global attention, attention + final logit
    softcaps, pre+post RMSNorm, sqrt(d_model) embedding scale, query
    pre-attention scalar,
  - hybrid stacks (granite-4.0-h): ``layer_kinds`` names each layer's
    mixer, Mamba-2 (``models.ssm``) or attention (optionally without
    positions and with a configured score scale), each followed by an MoE;
    embedding, residual-branch and logit multipliers; a tied head. Consecutive layers
    of one kind are one ``lax.scan``, so the program grows with the
    pattern's runs, not its depth. Scoring only (``hidden_states``,
    ``score_tokens``),
  - ``scan_layers``: layers stacked and executed with ``lax.scan`` so HLO
    size is O(1) in depth (required for 48-layer full configs to compile
    quickly in the dry-run), with ``jax.checkpoint`` remat per block,
  - decode path over a slotted KV cache with per-row lengths.

Parameters are nested dicts (see ``repro.models.layers``).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import TransformerConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as SSM


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _attn_init(ks, cfg: TransformerConfig, dt) -> Dict:
    d, Hq, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    return {
        "wq": L.dense_init(ks[0], d, Hq * Dh, bias=cfg.qkv_bias, dtype=dt),
        "wk": L.dense_init(ks[1], d, Hkv * Dh, bias=cfg.qkv_bias, dtype=dt),
        "wv": L.dense_init(ks[2], d, Hkv * Dh, bias=cfg.qkv_bias, dtype=dt),
        "wo": L.dense_init(ks[3], Hq * Dh, d, dtype=dt,
                           std=math.sqrt(1.0 / (Hq * Dh))
                           / math.sqrt(2.0 * cfg.n_layers)),
    }


def _block_init(key, cfg: TransformerConfig, moe_layer: bool,
                d_ff_override: int = 0) -> Dict:
    ks = jax.random.split(key, 8)
    dt = L.dtype_of(cfg.param_dtype)
    d = cfg.d_model
    p = {
        "ln1": L.rmsnorm_init(d, dt),
        "ln2": L.rmsnorm_init(d, dt),
        "attn": _attn_init(ks, cfg, dt),
    }
    if cfg.post_norm:
        p["ln1_post"] = L.rmsnorm_init(d, dt)
        p["ln2_post"] = L.rmsnorm_init(d, dt)
    if moe_layer:
        p["moe"] = M.init(ks[4], d, cfg.moe, dt)
    else:
        p["ffn"] = L.glu_ffn_init(ks[4], d, d_ff_override or cfg.d_ff, dt)
    return p


def layer_runs(cfg: TransformerConfig) -> Tuple[Tuple[str, int], ...]:
    """A hybrid stack's consecutive layers of one kind: (kind, count)."""
    runs = []
    for kind in cfg.layer_kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return tuple((k, n) for k, n in runs)


def _hybrid_layer_init(key, cfg: TransformerConfig, kind: str) -> Dict:
    k_mix, k_ffn = jax.random.split(key)
    dt = L.dtype_of(cfg.param_dtype)
    d = cfg.d_model
    p = {"ln1": L.rmsnorm_init(d, dt), "ln2": L.rmsnorm_init(d, dt)}
    if kind == "mamba":
        p["mamba"] = SSM.mamba2_init(k_mix, d, cfg.ssm, dt)
    elif kind == "attention":
        p["attn"] = _attn_init(jax.random.split(k_mix, 4), cfg, dt)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    p["moe"] = M.init(k_ffn, d, cfg.moe, dt)
    return p


def init_params(key, cfg: TransformerConfig) -> Dict:
    dt = L.dtype_of(cfg.param_dtype)
    k_emb, k_blocks, k_unemb = jax.random.split(key, 3)
    params: Dict = {"embed": L.embed_init(k_emb, cfg.vocab_size,
                                          cfg.d_model, dt)}
    if cfg.layer_kinds:
        # One stacked parameter tree per run of same-kind layers.
        keys = iter(jax.random.split(k_blocks, cfg.n_layers))
        params["runs"] = [
            jax.vmap(lambda k, kind=kind: _hybrid_layer_init(k, cfg, kind))(
                jnp.stack([next(keys) for _ in range(n)]))
            for kind, n in layer_runs(cfg)]
        params["final_norm"] = L.rmsnorm_init(cfg.d_model, dt)
        return params
    first_dense = cfg.moe.first_k_dense if cfg.moe else 0
    n_scan = cfg.n_layers - first_dense
    block_keys = jax.random.split(k_blocks, cfg.n_layers)

    if first_dense:
        params["dense_blocks"] = [
            _block_init(block_keys[i], cfg, moe_layer=False,
                        d_ff_override=cfg.moe.d_ff_dense)
            for i in range(first_dense)
        ]
    moe_layer = cfg.moe is not None
    if cfg.scan_layers:
        stacked_keys = jnp.stack(list(block_keys[first_dense:]))
        params["blocks"] = jax.vmap(
            lambda k: _block_init(k, cfg, moe_layer=moe_layer))(stacked_keys)
    else:
        params["blocks"] = [
            _block_init(block_keys[first_dense + i], cfg, moe_layer=moe_layer)
            for i in range(n_scan)
        ]
    params["final_norm"] = L.rmsnorm_init(cfg.d_model, dt)
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(k_unemb, cfg.d_model,
                                         cfg.vocab_size, dtype=dt)
    return params


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def layer_windows(cfg: TransformerConfig) -> jnp.ndarray:
    """Per-layer sliding-window size (0 = global)."""
    if cfg.local_global_pattern and cfg.sliding_window > 0:
        # gemma2: even layers local, odd layers global
        return jnp.asarray([cfg.sliding_window if i % 2 == 0 else 0
                            for i in range(cfg.n_layers)], jnp.int32)
    return jnp.full((cfg.n_layers,), cfg.sliding_window, jnp.int32)


def _attn_scale(cfg: TransformerConfig) -> float:
    if cfg.attn_scale > 0:
        return cfg.attn_scale
    if cfg.query_pre_attn_scalar > 0:
        return cfg.query_pre_attn_scalar ** -0.5
    return cfg.d_head ** -0.5


def _sp_residual(x: jnp.ndarray) -> jnp.ndarray:
    """Megatron-SP residual sharding: block inputs (the remat residuals,
    n_layers of them) are saved sequence-sharded over ``model``; the
    all-gather back to full S happens inside the remat region so the
    backward replays it instead of holding full activations. Cuts the
    dominant training-memory term n_model-fold (§Perf iter "sp-resid").
    No-op without an ambient mesh."""
    from repro.distribution.constraints import constrain, dp_spec
    if x.ndim != 3 or x.shape[1] < 16:
        return x
    return constrain(x, dp_spec(), "model", None)


def _qkv(bp: Dict, cfg: TransformerConfig, x: jnp.ndarray, positions,
         compute_dtype):
    B = x.shape[0]
    S = x.shape[1] if x.ndim == 3 else 1
    q = L.dense_apply(bp["attn"]["wq"], x, compute_dtype)
    k = L.dense_apply(bp["attn"]["wk"], x, compute_dtype)
    v = L.dense_apply(bp["attn"]["wv"], x, compute_dtype)
    q = q.reshape(*x.shape[:-1], cfg.n_heads, cfg.d_head)
    k = k.reshape(*x.shape[:-1], cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(*x.shape[:-1], cfg.n_kv_heads, cfg.d_head)
    if cfg.use_rope:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _qkv_tp(bp: Dict, cfg: TransformerConfig, x: jnp.ndarray, positions,
            compute_dtype):
    """TP-sharded QKV for full-sequence attention: KV heads repeated to
    the full query head count so every attention tensor shards on the
    head dim over ``model`` (unevenly padded when n_heads doesn't divide
    the axis — still 9/16 utilization for smollm vs full replication
    without the constraint; measured in EXPERIMENTS.md §Perf iter 1)."""
    from repro.distribution.constraints import constrain, dp_spec
    dp = dp_spec()
    q, k, v = _qkv(bp, cfg, x, positions, compute_dtype)
    G = cfg.q_per_kv
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    q = constrain(q, dp, None, "model", None)
    k = constrain(k, dp, None, "model", None)
    v = constrain(v, dp, None, "model", None)
    return q, k, v


def _block_fwd(bp: Dict, cfg: TransformerConfig, x: jnp.ndarray,
               positions: jnp.ndarray, window, compute_dtype,
               q_chunk: int) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence block forward. x: (B, S, D)."""
    from repro.distribution.constraints import constrain, dp_spec
    h = L.rmsnorm_apply(bp["ln1"], x, cfg.norm_eps)
    q, k, v = _qkv_tp(bp, cfg, h, positions, compute_dtype)
    o = A.attention(q, k, v, causal=True, window=window,
                    softcap=cfg.attn_logit_softcap, scale=_attn_scale(cfg),
                    q_chunk=q_chunk)
    o = L.dense_apply(bp["attn"]["wo"],
                      o.reshape(*x.shape[:-1], cfg.n_heads * cfg.d_head),
                      compute_dtype)
    if cfg.post_norm:
        o = L.rmsnorm_apply(bp["ln1_post"], o, cfg.norm_eps)
    x = x + o
    h = L.rmsnorm_apply(bp["ln2"], x, cfg.norm_eps)
    metrics: Dict = {}
    if "moe" in bp:
        B, S, D = h.shape
        f, metrics = M.apply(bp["moe"], h.reshape(B * S, D), cfg.moe,
                                 act=cfg.act, compute_dtype=compute_dtype)
        f = f.reshape(B, S, D)
    else:
        f = L.glu_ffn_apply(bp["ffn"], h, act=cfg.act,
                            compute_dtype=compute_dtype)
    if cfg.post_norm:
        f = L.rmsnorm_apply(bp["ln2_post"], f, cfg.norm_eps)
    return x + f, metrics


def _hybrid_layer(lp: Dict, kind: str, cfg: TransformerConfig,
                  x: jnp.ndarray, positions: jnp.ndarray, compute_dtype,
                  q_chunk: int) -> Tuple[jnp.ndarray, Dict]:
    """One layer of a hybrid stack: mixer, then MoE, each branch added as
    ``x + residual_multiplier * branch``. x: (B, S, D)."""
    rm = cfg.residual_multiplier
    h = L.rmsnorm_apply(lp["ln1"], x, cfg.norm_eps)
    if kind == "mamba":
        with jax.named_scope("mamba"):
            o = SSM.mamba2_apply(lp["mamba"], h, cfg.ssm, eps=cfg.norm_eps,
                                 compute_dtype=compute_dtype)
    else:
        with jax.named_scope("attention"):
            q, k, v = _qkv_tp(lp, cfg, h, positions, compute_dtype)
            o = A.attention(q, k, v, causal=True, window=0,
                            softcap=cfg.attn_logit_softcap,
                            scale=_attn_scale(cfg), q_chunk=q_chunk)
            o = L.dense_apply(
                lp["attn"]["wo"],
                o.reshape(*x.shape[:-1], cfg.n_heads * cfg.d_head),
                compute_dtype)
    x = x + o * rm
    h = L.rmsnorm_apply(lp["ln2"], x, cfg.norm_eps)
    B, S, D = h.shape
    f, metrics = M.apply(lp["moe"], h.reshape(B * S, D), cfg.moe,
                         act=cfg.act, compute_dtype=compute_dtype)
    return x + f.reshape(B, S, D) * rm, metrics


def _hybrid_stack(params: Dict, cfg: TransformerConfig, x: jnp.ndarray,
                  positions: jnp.ndarray, compute_dtype, q_chunk: int
                  ) -> Tuple[jnp.ndarray, Dict]:
    """The layers of ``cfg.layer_kinds``, one ``lax.scan`` per run of
    same-kind layers; metrics summed over every layer."""
    metrics: Dict = {}
    for (kind, _), run in zip(layer_runs(cfg), params["runs"]):
        def step(carry, lp, kind=kind):
            return _hybrid_layer(lp, kind, cfg, carry, positions,
                                 compute_dtype, q_chunk)

        x, ms = jax.lax.scan(step, x, run)
        metrics = _acc_metrics(metrics, {k: jnp.sum(v, axis=0)
                                         for k, v in ms.items()})
    return x, metrics


def _zero_metrics(cfg: TransformerConfig) -> Dict:
    """The metrics the stack's MoE layers return, at zero."""
    if cfg.moe is None:
        return {}
    if cfg.moe.dispatch == "dropless":
        return {"expert_rows": jnp.zeros((), jnp.int32),
                "expert_slots": jnp.zeros((), jnp.int32)}
    return {"moe_aux_loss": jnp.zeros((), jnp.float32),
            "moe_drop_frac": jnp.zeros((), jnp.float32)}


def _acc_metrics(acc: Dict, m: Dict) -> Dict:
    return {k: acc[k] + m[k] for k in acc} if acc else dict(m)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill scoring)
# ---------------------------------------------------------------------------

def forward(params: Dict, cfg: TransformerConfig, tokens: jnp.ndarray,
            positions: Optional[jnp.ndarray] = None, q_chunk: int = 1024
            ) -> Tuple[jnp.ndarray, Dict]:
    """tokens: (B, S) int32 -> (logits (B, S, V) in compute dtype, metrics)."""
    cdt = L.dtype_of(cfg.dtype)
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
    x = L.embed_apply(params["embed"], tokens, cdt)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cdt)
    windows = layer_windows(cfg)
    metrics = _zero_metrics(cfg)

    block = _block_fwd
    if cfg.remat:
        block = jax.checkpoint(_block_fwd,
                               static_argnums=(1, 5, 6))  # cfg, dtype, chunk

    first_dense = cfg.moe.first_k_dense if cfg.moe else 0
    for i in range(first_dense):
        x, m = block(params["dense_blocks"][i], cfg, x, positions,
                     windows[i], cdt, q_chunk)

    if cfg.scan_layers:
        scan_windows = windows[first_dense:]

        def step(carry, xs):
            bp, w = xs
            y, m = block(bp, cfg, _sp_residual(carry), positions, w, cdt,
                         q_chunk)
            return y, m

        x, ms = jax.lax.scan(step, x, (params["blocks"], scan_windows))
        if metrics:
            metrics = {k: jnp.sum(ms[k]) for k in metrics}
    else:
        for i, bp in enumerate(params["blocks"]):
            x, m = block(bp, cfg, x, positions, windows[first_dense + i],
                         cdt, q_chunk)
            metrics = _acc_metrics(metrics, m) if m else metrics

    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed_apply(params["embed"], x)
    else:
        logits = L.dense_apply(params["unembed"], x, cdt)
    if cfg.final_logit_softcap > 0:
        logits = L.softcap(logits, cfg.final_logit_softcap)
    return logits, metrics


def hidden_states(params: Dict, cfg: TransformerConfig,
                  tokens: jnp.ndarray, q_chunk: int = 1024
                  ) -> Tuple[jnp.ndarray, Dict]:
    """Forward up to (and including) the final norm; no unembedding."""
    cdt = L.dtype_of(cfg.dtype)
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
    x = L.embed_apply(params["embed"], tokens, cdt)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cdt)
    if cfg.layer_kinds:
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        x, metrics = _hybrid_stack(params, cfg, x, positions, cdt, q_chunk)
        return L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps), \
            metrics
    windows = layer_windows(cfg)
    metrics = _zero_metrics(cfg)

    block = _block_fwd
    if cfg.remat:
        block = jax.checkpoint(_block_fwd, static_argnums=(1, 5, 6))

    first_dense = cfg.moe.first_k_dense if cfg.moe else 0
    for i in range(first_dense):
        x, m = block(params["dense_blocks"][i], cfg, x, positions,
                     windows[i], cdt, q_chunk)
        metrics = _acc_metrics(metrics, m) if m else metrics

    if cfg.scan_layers:
        def step(carry, xs):
            bp, w = xs
            y, m = block(bp, cfg, _sp_residual(carry), positions, w, cdt,
                         q_chunk)
            return y, m

        x, ms = jax.lax.scan(step, x, (params["blocks"],
                                       windows[first_dense:]))
        if metrics:
            metrics = {k: metrics[k] + jnp.sum(ms[k]) for k in metrics}
    else:
        for i, bp in enumerate(params["blocks"]):
            x, m = block(bp, cfg, x, positions, windows[first_dense + i],
                         cdt, q_chunk)
            metrics = _acc_metrics(metrics, m) if m else metrics
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    return x, metrics


def _chunk_logits(params: Dict, cfg: TransformerConfig, x: jnp.ndarray):
    from repro.distribution.constraints import constrain, dp_spec
    if cfg.tie_embeddings:
        logits = L.unembed_apply(params["embed"], x)
    else:
        logits = L.dense_apply(params["unembed"], x, x.dtype)
    # keep the chunk's logits vocab-sharded: without the constraint XLA
    # may all-gather the full unembed matrix instead (3.1 GB/device for
    # qwen2.5 — observed in §Perf iter "chunked-score")
    logits = constrain(logits, dp_spec(), None, "model")
    if cfg.final_logit_softcap > 0:
        logits = L.softcap(logits, cfg.final_logit_softcap)
    return logits


def _onehot_ce_sum(logits: jnp.ndarray, labels: jnp.ndarray,
                   mask: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Partition-friendly CE over a vocab-sharded logits chunk.

    One-hot select instead of take_along_axis: stays elementwise on the
    sharded vocab dim (local select + psum) — no cross-shard gather, no
    full-vocab replication.
    """
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    oh = labels[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1)
    ll = jnp.sum(jnp.where(oh, shifted, 0.0), axis=-1) + m[..., 0]
    loss = (lse - ll) * mask
    return jnp.sum(loss), jnp.sum(mask)


def lm_loss(params: Dict, cfg: TransformerConfig, tokens: jnp.ndarray,
            labels: jnp.ndarray, mask: Optional[jnp.ndarray] = None,
            q_chunk: int = 1024, loss_chunk: int = 1024
            ) -> Tuple[jnp.ndarray, Dict]:
    """Chunked LM loss: the (B, S, V) logits tensor is never materialized
    — the unembed + CE run per sequence chunk under remat, bounding the
    loss-side temp to (B, loss_chunk, V/model) regardless of S."""
    B, S = tokens.shape
    x, metrics = hidden_states(params, cfg, tokens, q_chunk=q_chunk)
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    mask = mask.astype(jnp.float32)

    @jax.checkpoint
    def chunk_fn(x_c, labels_c, mask_c):
        logits = _chunk_logits(params, cfg, x_c)
        return _onehot_ce_sum(logits, labels_c, mask_c)

    if S <= loss_chunk:
        total, weight = chunk_fn(x, labels, mask)
    else:
        # Python-unrolled (not lax.scan): scanning over chunks makes the
        # unembed weight's cotangent a scan carry, which XLA materializes
        # as 2-3 REPLICATED f32 (V, D) buffers (9.3 GB/device for
        # qwen2.5 — §Perf iter "unroll-loss"); unrolled chunk matmuls
        # keep dW a sum of vocab-sharded partials.
        assert S % loss_chunk == 0, (S, loss_chunk)
        n = S // loss_chunk
        total = jnp.zeros((), jnp.float32)
        weight = jnp.zeros((), jnp.float32)
        for i in range(n):
            lo, hi = i * loss_chunk, (i + 1) * loss_chunk
            ct, cw = chunk_fn(x[:, lo:hi], labels[:, lo:hi],
                              mask[:, lo:hi])
            total = total + ct
            weight = weight + cw
    loss = total / jnp.maximum(weight, 1.0)
    if cfg.moe is not None:
        loss = loss + metrics["moe_aux_loss"] / cfg.n_layers
    return loss, metrics


def target_logprobs(params: Dict, cfg: TransformerConfig, x: jnp.ndarray,
                    targets: jnp.ndarray, vocab_chunk: int = 2048
                    ) -> jnp.ndarray:
    """``log p(targets)`` (B, S) from final hidden states x (B, S, D).

    The logsumexp runs over vocabulary chunks of ``vocab_chunk`` rows
    (a ``lax.scan`` with a running max and sum), so only (B, S,
    vocab_chunk) logits exist at a time, never (B, S, V): at the
    published smollm-135m width and a 4096-item evaluator batch the
    full f32 logits alone would be ~25 GB. Each chunk's logits are the
    same values the full unembedding gives (compute dtype, softcap,
    then f32), and the target's logit is picked from its chunk."""
    B, S, D = x.shape
    V = cfg.vocab_size
    vc = min(vocab_chunk, V)
    n_c = -(-V // vc)
    pad = n_c * vc - V
    if cfg.tie_embeddings:
        w = params["embed"]["table"]                          # (V, D)
    else:
        w = params["unembed"]["w"].T                          # (V, D)
    w = jnp.pad(w, ((0, pad), (0, 0))).reshape(n_c, vc, D)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, vc), 2)

    def chunk(carry, xs):
        m, s, tgt = carry
        w_c, base = xs
        logits = x @ w_c.astype(x.dtype).T                   # (B, S, vc)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        if cfg.final_logit_softcap > 0:
            logits = L.softcap(logits, cfg.final_logit_softcap)
        logits = logits.astype(jnp.float32)
        ids = base + col
        logits = jnp.where(ids < V, logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = (s * jnp.exp(m - m_new)
             + jnp.sum(jnp.exp(logits - m_new[..., None]), axis=-1))
        tgt = tgt + jnp.sum(
            jnp.where(ids == targets[..., None], logits, 0.0), axis=-1)
        return (m_new, s, tgt), None

    init = (jnp.full((B, S), -jnp.inf, jnp.float32),
            jnp.zeros((B, S), jnp.float32),
            jnp.zeros((B, S), jnp.float32))
    bases = jnp.arange(n_c, dtype=jnp.int32) * vc
    (m, s, tgt), _ = jax.lax.scan(chunk, init, (w, bases))
    return tgt - (m + jnp.log(s))


def score_tokens(params: Dict, cfg: TransformerConfig, tokens: jnp.ndarray,
                 mask: Optional[jnp.ndarray] = None, q_chunk: int = 1024,
                 vocab_chunk: int = 2048, return_metrics: bool = False):
    """Sequence log-likelihood score, the LM trust-evaluator head.

    Returns per-sequence mean token logprob (B,) — mapped to a
    trustworthiness value by the core pipeline — and, with
    ``return_metrics``, the layers' summed metrics beside it. The
    vocabulary logsumexp is chunked (:func:`target_logprobs`).
    """
    x, metrics = hidden_states(params, cfg, tokens[:, :-1], q_chunk=q_chunk)
    tok_lp = target_logprobs(params, cfg, x, tokens[:, 1:],
                             vocab_chunk=vocab_chunk)
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        score = jnp.sum(tok_lp * m, axis=-1) / jnp.maximum(
            jnp.sum(m, axis=-1), 1.0)
    else:
        score = jnp.mean(tok_lp, axis=-1)
    return (score, metrics) if return_metrics else score


# ---------------------------------------------------------------------------
# KV cache: prefill + decode
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int) -> Dict:
    cdt = L.dtype_of(cfg.dtype)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, cdt), "v": jnp.zeros(shape, cdt),
            "lengths": jnp.zeros((batch,), jnp.int32)}


def decode_step(params: Dict, cfg: TransformerConfig, token: jnp.ndarray,
                cache: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One decoding step.

    token: (B,) int32 — the newest token; cache: see ``init_kv_cache``
    (``lengths`` counts tokens already in the cache). Returns
    (logits (B, V), updated cache).
    """
    cdt = L.dtype_of(cfg.dtype)
    B = token.shape[0]
    lengths = cache["lengths"]                       # (B,)
    positions = lengths                               # new token position
    x = L.embed_apply(params["embed"], token, cdt)   # (B, D)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cdt)
    windows = layer_windows(cfg)
    new_len = lengths + 1

    def block_decode(bp, x, k_c, v_c, window):
        h = L.rmsnorm_apply(bp["ln1"], x, cfg.norm_eps)
        q, k, v = _qkv(bp, cfg, h[:, None, :], positions[:, None], cdt)
        k_c, v_c = A.update_kv_cache(k_c, v_c, k[:, 0], v[:, 0], lengths)
        o = A.decode_attention(q[:, 0], k_c, v_c, new_len, window=window,
                               softcap=cfg.attn_logit_softcap,
                               scale=_attn_scale(cfg))
        o = L.dense_apply(bp["attn"]["wo"],
                          o.reshape(B, cfg.n_heads * cfg.d_head), cdt)
        if cfg.post_norm:
            o = L.rmsnorm_apply(bp["ln1_post"], o, cfg.norm_eps)
        x = x + o
        h = L.rmsnorm_apply(bp["ln2"], x, cfg.norm_eps)
        if "moe" in bp:
            f, _ = M.apply(bp["moe"], h, cfg.moe, act=cfg.act,
                               compute_dtype=cdt)
        else:
            f = L.glu_ffn_apply(bp["ffn"], h, act=cfg.act, compute_dtype=cdt)
        if cfg.post_norm:
            f = L.rmsnorm_apply(bp["ln2_post"], f, cfg.norm_eps)
        return x + f, k_c, v_c

    first_dense = cfg.moe.first_k_dense if cfg.moe else 0
    k_cache, v_cache = cache["k"], cache["v"]
    new_k_list, new_v_list = [], []
    for i in range(first_dense):
        x, k_i, v_i = block_decode(params["dense_blocks"][i], x,
                                   k_cache[i], v_cache[i], windows[i])
        new_k_list.append(k_i)
        new_v_list.append(v_i)

    if cfg.scan_layers:
        def step(carry, xs):
            bp, k_c, v_c, w = xs
            y, k_c, v_c = block_decode(bp, carry, k_c, v_c, w)
            return y, (k_c, v_c)

        x, (ks, vs) = jax.lax.scan(
            step, x, (params["blocks"], k_cache[first_dense:],
                      v_cache[first_dense:], windows[first_dense:]))
        if first_dense:
            ks = jnp.concatenate([jnp.stack(new_k_list), ks], axis=0)
            vs = jnp.concatenate([jnp.stack(new_v_list), vs], axis=0)
    else:
        layer_ks, layer_vs = list(new_k_list), list(new_v_list)
        for i, bp in enumerate(params["blocks"]):
            x, k_i, v_i = block_decode(bp, x, k_cache[first_dense + i],
                                       v_cache[first_dense + i],
                                       windows[first_dense + i])
            layer_ks.append(k_i)
            layer_vs.append(v_i)
        ks, vs = jnp.stack(layer_ks), jnp.stack(layer_vs)

    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed_apply(params["embed"], x)
    else:
        logits = L.dense_apply(params["unembed"], x, cdt)
    if cfg.final_logit_softcap > 0:
        logits = L.softcap(logits, cfg.final_logit_softcap)
    return logits, {"k": ks, "v": vs, "lengths": new_len}


def prefill(params: Dict, cfg: TransformerConfig, tokens: jnp.ndarray,
            max_len: Optional[int] = None, q_chunk: int = 1024
            ) -> Tuple[jnp.ndarray, Dict]:
    """Prefill scoring pass: returns (per-seq score (B,), KV cache).

    The cache is filled for all prompt positions so decode can continue.
    """
    cdt = L.dtype_of(cfg.dtype)
    B, S = tokens.shape
    max_len = max_len or S
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
    x = L.embed_apply(params["embed"], tokens, cdt)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cdt)
    windows = layer_windows(cfg)

    def block_prefill(bp, x, window):
        from repro.distribution.constraints import constrain, dp_spec
        dp = dp_spec()
        h = L.rmsnorm_apply(bp["ln1"], x, cfg.norm_eps)
        q, k, v = _qkv(bp, cfg, h, positions, cdt)
        # repeated KV for head-sharded TP compute; cache keeps the
        # compact (n_kv_heads) layout
        G = cfg.q_per_kv
        k_r = jnp.repeat(k, G, axis=2) if G > 1 else k
        v_r = jnp.repeat(v, G, axis=2) if G > 1 else v
        q = constrain(q, dp, None, "model", None)
        k_r = constrain(k_r, dp, None, "model", None)
        v_r = constrain(v_r, dp, None, "model", None)
        o = A.attention(q, k_r, v_r, causal=True, window=window,
                        softcap=cfg.attn_logit_softcap,
                        scale=_attn_scale(cfg), q_chunk=q_chunk)
        o = constrain(o, dp, None, "model", None)
        o = L.dense_apply(bp["attn"]["wo"],
                          o.reshape(B, S, cfg.n_heads * cfg.d_head), cdt)
        if cfg.post_norm:
            o = L.rmsnorm_apply(bp["ln1_post"], o, cfg.norm_eps)
        x = x + o
        h = L.rmsnorm_apply(bp["ln2"], x, cfg.norm_eps)
        if "moe" in bp:
            f, _ = M.apply(bp["moe"], h.reshape(B * S, -1), cfg.moe,
                               act=cfg.act, compute_dtype=cdt)
            f = f.reshape(B, S, -1)
        else:
            f = L.glu_ffn_apply(bp["ffn"], h, act=cfg.act, compute_dtype=cdt)
        if cfg.post_norm:
            f = L.rmsnorm_apply(bp["ln2_post"], f, cfg.norm_eps)
        return x + f, k, v

    if cfg.remat:
        block_prefill = jax.checkpoint(block_prefill)

    first_dense = cfg.moe.first_k_dense if cfg.moe else 0
    dense_k, dense_v = [], []
    for i in range(first_dense):
        x, k, v = block_prefill(params["dense_blocks"][i], x, windows[i])
        dense_k.append(k)
        dense_v.append(v)

    if cfg.scan_layers:
        def step(carry, xs):
            bp, w = xs
            y, k, v = block_prefill(bp, _sp_residual(carry), w)
            return y, (k, v)

        x, (ks, vs) = jax.lax.scan(step, x, (params["blocks"],
                                             windows[first_dense:]))
        if first_dense:
            ks = jnp.concatenate([jnp.stack(dense_k), ks], axis=0)
            vs = jnp.concatenate([jnp.stack(dense_v), vs], axis=0)
    else:
        all_k, all_v = list(dense_k), list(dense_v)
        for i, bp in enumerate(params["blocks"]):
            x, k, v = block_prefill(bp, x, windows[first_dense + i])
            all_k.append(k)
            all_v.append(v)
        ks, vs = jnp.stack(all_k), jnp.stack(all_v)

    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    # per-seq mean next-token logprob over the prompt = trust score
    # signal; computed in sequence chunks so the (B, S, V) logits tensor
    # never materializes (same discipline as lm_loss — §Perf iter
    # "chunked-score").
    loss_chunk = min(1024, S)

    def score_chunk(x_c, labels_c):
        logits = _chunk_logits(params, cfg, x_c)
        mask_c = jnp.ones(labels_c.shape, jnp.float32)
        total, _ = _onehot_ce_sum(logits, labels_c, mask_c)
        return -total                                   # sum logprob

    xs_in = x[:, :-1]
    labels = tokens[:, 1:]
    Sm1 = S - 1
    total_lp = jnp.zeros((), jnp.float32)
    # (B,) per-sequence scores need per-seq sums; reuse the chunked CE
    # with per-chunk per-seq reduction
    per_seq = jnp.zeros((B,), jnp.float32)
    start = 0
    while start < Sm1:
        end = min(start + loss_chunk, Sm1)
        logits = _chunk_logits(params, cfg, xs_in[:, start:end])
        logits = logits.astype(jnp.float32)
        mx = jax.lax.stop_gradient(
            jnp.max(logits, axis=-1, keepdims=True))
        shifted = logits - mx
        lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + mx[..., 0]
        oh = labels[:, start:end, None] == jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 2)
        ll = jnp.sum(jnp.where(oh, shifted, 0.0), axis=-1) + mx[..., 0]
        per_seq = per_seq + jnp.sum(ll - lse, axis=-1)
        start = end
    score = per_seq / jnp.maximum(Sm1, 1)

    if max_len > S:
        pad = ((0, 0), (0, 0), (0, max_len - S), (0, 0), (0, 0))
        ks = jnp.pad(ks, pad)
        vs = jnp.pad(vs, pad)
    cache = {"k": ks, "v": vs,
             "lengths": jnp.full((B,), S, jnp.int32)}
    return score, cache
