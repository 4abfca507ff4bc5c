"""Plain float32 reference of the DLRM trust scorer.

From the DLRM paper (arXiv:1906.00091) and the MLPerf configuration:
bottom MLP with ReLU after every layer, one embedding row per sparse
feature, the pairwise dot products of the 27 vectors (bottom output and
26 rows), concatenated after the bottom output, then the top MLP with
ReLU between layers and none after the last; trust is
``sigmoid(logit) * trust_scale``. In ``jax.numpy`` at
``precision="highest"``, imports nothing of the program, and reads the
weights the benchmark made by the names of the program's layout. The
pairs are taken in row-major upper-triangle order (i < j), the order
the program's top MLP consumes; MLPerf lists the same pairs as a lower
triangle, which only permutes the top MLP's input rows.

``mode="control"`` runs every matrix product as three bfloat16 passes
(``hi*hi + hi*lo + lo*hi`` of each operand split into a bfloat16 head
and tail), the precision one step below the float32 at ``highest`` that
the configuration states.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def _bf16(a):
    """``a`` rounded to bfloat16 and kept in float32; unlike a round trip
    through ``astype``, the compiler may not drop the rounding."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _mm(a, b, mode: str, spec: str):
    if mode == "control":
        ah, al = _split(a)
        bh, bl = _split(b)
        f = partial(jnp.einsum, spec, precision="highest")
        return f(ah, bh) + (f(ah, bl) + f(al, bh))
    return jnp.einsum(spec, a, b, precision="highest")


def _mlp(layers, x, mode: str, relu_last: bool):
    for i, p in enumerate(layers):
        x = _mm(x, p["w"], mode, "bi,io->bo") + p["b"]
        if i < len(layers) - 1 or relu_last:
            x = jnp.maximum(x, 0.0)
    return x


@partial(jax.jit, static_argnames=("trust_scale", "mode"))
def _block_trust(w, dense, sparse, *, trust_scale, mode):
    bot = _mlp(w["bot_mlp"]["layers"], dense, mode, relu_last=True)
    names = sorted(w["tables"], key=lambda s: int(s.split("_")[1]))
    rows = [w["tables"][t]["table"][sparse[:, i]]
            for i, t in enumerate(names)]
    z = jnp.stack([bot] + rows, axis=1)                         # (B, F, d)
    gram = _mm(z, z, mode, "bfd,bgd->bfg")
    iu, ju = np.triu_indices(z.shape[1], k=1)
    top_in = jnp.concatenate([bot, gram[:, iu, ju]], axis=-1)
    logit = _mlp(w["top_mlp"]["layers"], top_in, mode, relu_last=False)
    return jax.nn.sigmoid(logit[:, 0]) * trust_scale


def trust(cfg: Dict, weights, feats: Dict[str, np.ndarray],
          mode: str = "f32", block: int = 4096) -> np.ndarray:
    dense = np.asarray(feats["dense"], np.float32)
    sparse = np.asarray(feats["sparse"], np.int32)
    n = len(dense)
    pad = -n % block
    dense = np.concatenate([dense, np.zeros((pad, dense.shape[1]),
                                            np.float32)])
    sparse = np.concatenate([sparse, np.zeros((pad, sparse.shape[1]),
                                              np.int32)])
    out = [np.asarray(_block_trust(
        weights, jnp.asarray(dense[i:i + block]),
        jnp.asarray(sparse[i:i + block]),
        trust_scale=cfg["serving"]["trust_scale"], mode=mode))
        for i in range(0, n + pad, block)]
    return np.concatenate(out)[:n]
