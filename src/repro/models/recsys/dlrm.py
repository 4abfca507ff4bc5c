"""DLRM (MLPerf config): bottom MLP + 26 embedding lookups + dot
interaction + top MLP. [arXiv:1906.00091]
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RecsysConfig
from repro.models import layers as L
from repro.models.recsys import embedding as E


def init_params(key, cfg: RecsysConfig) -> Dict:
    dt = L.dtype_of(cfg.param_dtype)
    keys = jax.random.split(key, len(cfg.tables) + 2)
    tables = {t.name: E.table_init(k, t, dt)
              for t, k in zip(cfg.tables, keys[2:])}
    n_f = len(cfg.tables) + 1
    d_int = n_f * (n_f - 1) // 2 + cfg.bot_mlp[-1]
    return {
        "tables": tables,
        "bot_mlp": L.mlp_init(keys[0], cfg.bot_mlp[1:], cfg.bot_mlp[0],
                              dtype=dt),
        "top_mlp": L.mlp_init(keys[1], cfg.top_mlp, d_int, dtype=dt),
    }


def forward(params: Dict, cfg: RecsysConfig, dense: jnp.ndarray,
            sparse_idx: jnp.ndarray) -> jnp.ndarray:
    """dense: (B, n_dense) float; sparse_idx: (B, n_tables) int32.

    Returns CTR logits (B,).
    """
    cdt = L.dtype_of(cfg.dtype)
    bot = L.mlp_apply(params["bot_mlp"], dense.astype(cdt), final_act=True,
                      compute_dtype=cdt)                       # (B, d_emb)
    embs = [E.lookup(params["tables"][t.name], sparse_idx[:, i], cdt)
            for i, t in enumerate(cfg.tables)]                 # each (B, d)
    feats = jnp.stack([bot] + embs, axis=1)                    # (B, F, d)
    # dot interaction: upper triangle of feats @ feats^T
    z = jnp.einsum("bfd,bgd->bfg", feats, feats,
                   preferred_element_type=jnp.float32)         # (B, F, F)
    n_f = feats.shape[1]
    iu, ju = np.triu_indices(n_f, k=1)          # constants, not ops
    inter = z[:, iu, ju].astype(cdt)                           # (B, F(F-1)/2)
    top_in = jnp.concatenate([bot, inter], axis=-1)
    out = L.mlp_apply(params["top_mlp"], top_in, compute_dtype=cdt)
    return out[:, 0].astype(jnp.float32)


def loss_fn(params: Dict, cfg: RecsysConfig, batch: Dict) -> jnp.ndarray:
    logits = forward(params, cfg, batch["dense"], batch["sparse"])
    return L.bce_with_logits(logits, batch["labels"])


def relevance_scores(params: Dict, cfg: RecsysConfig, dense, sparse_idx,
                     trust_scale: float = 5.0) -> jnp.ndarray:
    """Trust-evaluator head: CTR probability scaled to [0, trust_scale]."""
    return jax.nn.sigmoid(forward(params, cfg, dense, sparse_idx)) * trust_scale
