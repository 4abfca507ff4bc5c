"""Mamba-2 mixer (state-space duality form), as granite-4.0-h uses it.

``in_proj`` splits into the gate z, xBC and dt; a causal depthwise
convolution (with bias) runs over xBC, then SiLU; xBC splits into x
(heads of ``d_head``), B and C (``n_groups`` of ``d_state``). With
dt = softplus(dt + dt_bias) and A = -exp(A_log), the recurrence is

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T,    y_t = C_t h_t + D x_t,

then a gated RMSNorm, rmsnorm(y * silu(z)) * (1 + scale) over each
group's channels, and ``out_proj``.

A sequence of at most ``chunk_size`` positions is one SSD chunk, and the
chunk's output is its matrix form

    y = (L o C B^T) (dt x) + D x,    L[t, s] = exp(sum_{s<u<=t} dt_u A), s <= t,

with no state carried in or out. The scorer's documents (31 positions
against a chunk of 256) are always one chunk; a longer sequence is
refused rather than computed without the inter-chunk state.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models import layers as L


def mamba2_init(key, d_model: int, cfg: SSMConfig, dtype=jnp.float32
                ) -> Dict:
    ks = jax.random.split(key, 5)
    h = cfg.n_heads
    d_in_proj = 2 * cfg.d_inner + 2 * cfg.n_groups * cfg.d_state + h
    # dt spans [1e-3, 1e-1] log-uniformly and A [1, 16] (the Mamba-2
    # initialisation).
    dt = jnp.exp(jax.random.uniform(ks[2], (h,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    a = jax.random.uniform(ks[3], (h,), jnp.float32, 1.0, 16.0)
    p = {
        "in_proj": L.dense_init(ks[0], d_model, d_in_proj, dtype=dtype),
        "conv_w": L.trunc_normal(ks[1], (cfg.d_conv, cfg.conv_dim),
                                 cfg.d_conv ** -0.5, dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(a).astype(dtype),
        "D": jnp.ones((h,), dtype),
        "norm": L.rmsnorm_init(cfg.d_inner, dtype),
        "out_proj": L.dense_init(ks[4], cfg.d_inner, d_model, dtype=dtype),
    }
    if cfg.conv_bias:
        p["conv_b"] = jnp.zeros((cfg.conv_dim,), dtype)
    return p


def mamba2_apply(p: Dict, x: jnp.ndarray, cfg: SSMConfig, *,
                 eps: float = 1e-5, compute_dtype=jnp.bfloat16
                 ) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D); S at most ``cfg.chunk_size``."""
    b, s, _ = x.shape
    if s > cfg.chunk_size:
        raise ValueError(f"mamba2_apply computes one SSD chunk: {s} "
                         f"positions > chunk_size {cfg.chunk_size}")
    h, dh, g, n = cfg.n_heads, cfg.d_head, cfg.n_groups, cfg.d_state
    zxbcdt = L.dense_apply(p["in_proj"], x, compute_dtype)
    z, xbc, dt = jnp.split(zxbcdt, [cfg.d_inner, cfg.d_inner + cfg.conv_dim],
                           axis=-1)

    # Causal depthwise convolution: tap k reads position t - (K-1) + k.
    k_taps = cfg.d_conv
    w = p["conv_w"].astype(compute_dtype)
    xp = jnp.pad(xbc, ((0, 0), (k_taps - 1, 0), (0, 0)))
    conv = sum(xp[:, k:k + s] * w[k] for k in range(k_taps))
    if "conv_b" in p:
        conv = conv + p["conv_b"].astype(compute_dtype)
    xbc = jax.nn.silu(conv)
    xs, bm, cm = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + g * n], axis=-1)
    xs = xs.reshape(b, s, h, dh).astype(jnp.float32)
    bm = bm.reshape(b, s, g, n).astype(jnp.float32)
    cm = cm.reshape(b, s, g, n).astype(jnp.float32)

    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))      # (B,S,H)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))                   # (H,)
    cs = jnp.cumsum(dt * a, axis=1)                                # (B,S,H)
    seg = cs[:, :, None, :] - cs[:, None, :, :]                    # (B,t,s,H)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("btgn,bsgn->bgts", cm, bm)                     # (B,G,t,s)
    cb = jnp.repeat(cb, h // g, axis=1)                            # (B,H,t,s)
    m = cb * jnp.moveaxis(decay, 3, 1) * jnp.moveaxis(dt, 2, 1)[:, :, None]
    y = jnp.einsum("bhts,bshp->bthp", m, xs)
    y = y + p["D"].astype(jnp.float32)[:, None] * xs
    y = y.reshape(b, s, cfg.d_inner)

    # Gated RMSNorm over each group's channels.
    y = y * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(b, s, g, cfg.d_inner // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
    y = yg.reshape(b, s, cfg.d_inner) \
        * (1.0 + p["norm"]["scale"].astype(jnp.float32))
    return L.dense_apply(p["out_proj"], y.astype(compute_dtype),
                         compute_dtype)
