"""DLRM as the trust evaluator: ``sigmoid(CTR logit) * trust_scale``.

Builds the program's DLRM forward (``models/recsys/dlrm.py``) as a
``serving.evaluators.Evaluator`` with tables of the rows one chip holds
(``num_embeddings_per_feature`` of the configuration file), weights that
the benchmark makes on the device, and matrix products at the precision
the file states. ``serving.evaluators.make_evaluator(..., smoke=False)``
would allocate every published row (about 96 GB).
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import numpy as np

from benchmarks.chip import hashing


def program_config(cfg: Dict):
    from repro.configs.base import EmbeddingTableConfig, RecsysConfig
    dim = cfg["embedding_dim"]
    tables = tuple(EmbeddingTableConfig(name=f"sparse_{i}", vocab=v, dim=dim)
                   for i, v in enumerate(cfg["num_embeddings_per_feature"]))
    return RecsysConfig(
        name=cfg["name"], model="dlrm", embed_dim=dim, tables=tables,
        n_dense=cfg["num_dense_features"],
        bot_mlp=(cfg["num_dense_features"], *cfg["bottom_mlp"]),
        top_mlp=tuple(cfg["top_mlp"]), interaction=cfg["interaction"],
        dtype=cfg["serving"]["compute_dtype"],
        param_dtype=cfg["serving"]["param_dtype"])


def make_weights(cfg: Dict, key):
    """Every weight from ``key`` in one jitted call on the device, in
    the program's layout: tables N(0, 1/dim), matrices N(0, 1/fan_in),
    biases N(0, 0.01**2)."""
    import jax
    import jax.numpy as jnp
    from repro.models.recsys import dlrm as Mdl

    rcfg = program_config(cfg)
    template = jax.eval_shape(partial(Mdl.init_params, cfg=rcfg), key)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)

    def std_of(path, shape) -> float:
        name = getattr(path[-1], "key", "")
        if name == "b":
            return 0.01
        if name == "table":
            return float(shape[-1]) ** -0.5
        return float(shape[-2]) ** -0.5

    @jax.jit
    def fill(k):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            x = jax.random.normal(jax.random.fold_in(k, i), leaf.shape,
                                  jnp.float32)
            out.append((x * std_of(path, leaf.shape)).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return fill(key)


def make_evaluator(cfg: Dict, params):
    import jax
    from repro.models.recsys import dlrm as Mdl
    from repro.serving.evaluators import Evaluator

    rcfg = program_config(cfg)
    precision = cfg["serving"]["matmul_precision"]
    scale = cfg["serving"]["trust_scale"]

    def apply(params, chunk: Dict):
        with jax.default_matmul_precision(precision):
            return Mdl.relevance_scores(params, rcfg, chunk["dense"],
                                        chunk["sparse"], trust_scale=scale)

    return Evaluator(apply, params)


def features(cfg: Dict, urls: np.ndarray) -> Dict[str, np.ndarray]:
    """Dense features are log-transformed counts (as Criteo's are fed to
    the model) and sparse ids index the rows this chip holds, both
    hashes of the URL id."""
    u = urls.astype(np.uint64)[:, None] * np.uint64(1 << 8)
    n_dense = cfg["num_dense_features"]
    counts = hashing.mix64(u + np.arange(n_dense, dtype=np.uint64)) \
        % np.uint64(1000)
    rows = np.asarray(cfg["num_embeddings_per_feature"], np.uint64)
    sparse = hashing.mix64(u + np.uint64(128)
                           + np.arange(len(rows), dtype=np.uint64)) % rows
    return {"dense": np.log1p(counts.astype(np.float32)),
            "sparse": sparse.astype(np.int32)}


def flops_per_item(cfg: Dict) -> float:
    """Bottom MLP, the pairwise dot interaction of the dense vector and
    every table's row (the upper triangle the model reads), top MLP."""
    d = cfg["embedding_dim"]
    macs, width = 0, cfg["num_dense_features"]
    for h in cfg["bottom_mlp"]:
        macs, width = macs + width * h, h
    n_f = len(cfg["num_embeddings_per_feature"]) + 1
    pairs = n_f * (n_f - 1) // 2
    macs += pairs * d
    width = pairs + cfg["bottom_mlp"][-1]
    for h in cfg["top_mlp"]:
        macs, width = macs + width * h, h
    return 2.0 * macs
