"""Trust/relevance evaluator backends: every assigned architecture wraps
into the shedder's ``evaluate_chunk(features) -> scores`` protocol, making
the paper's algorithm arch-agnostic (DESIGN.md §4).

Each factory returns (evaluate, make_features): ``evaluate`` is an
:class:`Evaluator` — a jitted ``apply(params, features) -> scores``
bound to its parameters, callable as ``evaluate(features)`` — and
``make_features(n, seed)`` synthesizes evaluator inputs for n items
(documents/candidates) with leading dim n.

:func:`make_sharded_evaluator` is the production-config variant: the
evaluator's parameters are placed with the ``distribution.sharding``
rules on a real mesh (TP/EP for transformers, row-sharded embedding
tables for recsys), and the returned ``feature_sharding`` callable
gives the matching data-parallel input placement. The fused drain
(``core.fused_shedder``) stages each micro-batch's gathered eval
features with that sharding, so the host->device transfer of batch N+2
lands directly in the layout the sharded forward of batch N is already
using — no device-side reshard on the hot path.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import GNNConfig, RecsysConfig, TransformerConfig


class Evaluator:
    """A trust evaluator: ``apply(params, features) -> (n,) scores``
    and the parameters it runs with. Calling it scores one feature
    batch. The fused drain passes ``params`` into its jitted step as an
    argument (``core.fused_shedder``): a closure over them would fold
    every weight into the compiled step as a constant."""

    def __init__(self, apply: Callable, params: Any):
        self.apply = apply
        self.params = params
        self._jitted = jax.jit(apply)

    def __call__(self, features) -> jnp.ndarray:
        return self._jitted(self.params, features)


def make_evaluator(arch_id: str, *, smoke: bool = True, seed: int = 0,
                   trust_scale: float = 5.0, doc_len: int = 32,
                   place_params: Optional[Callable] = None
                   ) -> Tuple[Evaluator, Callable]:
    """``smoke=False`` builds the published-width configuration.
    ``place_params(params, cfg) -> params`` (optional) re-homes the
    freshly initialized parameters — the mesh-sharding hook
    :func:`make_sharded_evaluator` uses; identity when omitted."""
    cfg = get_config(arch_id, smoke=smoke)
    key = jax.random.PRNGKey(seed)
    _place = place_params or (lambda p, _cfg: p)

    if isinstance(cfg, TransformerConfig):
        from repro.models import transformer as T
        params = _place(T.init_params(key, cfg), cfg)

        def apply(params, chunk: Dict) -> jnp.ndarray:
            # mean token logprob -> squashed to [0, trust_scale]
            lp = T.score_tokens(params, cfg, chunk["tokens"],
                                q_chunk=doc_len)
            return jax.nn.sigmoid(lp + jnp.log(float(cfg.vocab_size))
                                  ) * trust_scale

        def make_features(n: int, fseed: int = 0) -> Dict:
            r = np.random.default_rng(fseed)
            return {"tokens": r.integers(0, cfg.vocab_size,
                                         size=(n, doc_len)
                                         ).astype(np.int32)}
        return Evaluator(apply, params), make_features

    if isinstance(cfg, GNNConfig):
        from repro.models import gnn as G
        params = _place(G.init_params(key, cfg), cfg)
        deg = 8

        def apply(params, chunk: Dict) -> jnp.ndarray:
            # per-chunk star subgraphs: each URL node + its neighbors;
            # trust propagates from neighbor features (TrustRank-style)
            x = chunk["x"].reshape(-1, cfg.d_feat)       # (n*(deg+1), F)
            n = chunk["x"].shape[0]
            src = chunk["edge_src"].reshape(-1)
            dst = chunk["edge_dst"].reshape(-1)
            ei = jnp.stack([src, dst])
            scores = G.trust_scores(params, cfg, x, ei,
                                    trust_scale=trust_scale)
            centers = jnp.arange(n) * (deg + 1)
            return scores[centers]

        def make_features(n: int, fseed: int = 0) -> Dict:
            r = np.random.default_rng(fseed)
            x = r.normal(size=(n, deg + 1, cfg.d_feat)).astype(np.float32)
            base = (np.arange(n) * (deg + 1))[:, None]
            src = (base + 1 + np.arange(deg)[None]).astype(np.int32)
            dst = np.broadcast_to(base, (n, deg)).astype(np.int32)
            return {"x": x, "edge_src": src, "edge_dst": dst}
        return Evaluator(apply, params), make_features

    if isinstance(cfg, RecsysConfig):
        if cfg.model == "dlrm":
            from repro.models.recsys import dlrm as Mdl
            params = _place(Mdl.init_params(key, cfg), cfg)

            def apply(params, chunk: Dict) -> jnp.ndarray:
                return Mdl.relevance_scores(params, cfg, chunk["dense"],
                                            chunk["sparse"],
                                            trust_scale=trust_scale)

            def make_features(n: int, fseed: int = 0) -> Dict:
                r = np.random.default_rng(fseed)
                return {
                    "dense": r.normal(size=(n, cfg.n_dense)
                                      ).astype(np.float32),
                    "sparse": np.stack(
                        [r.integers(0, t.vocab, size=n)
                         for t in cfg.tables], axis=1).astype(np.int32),
                }
            return Evaluator(apply, params), make_features

        if cfg.model == "bst":
            from repro.models.recsys import bst as Mdl
            params = _place(Mdl.init_params(key, cfg), cfg)

            def apply(params, chunk: Dict) -> jnp.ndarray:
                return Mdl.relevance_scores(params, cfg, chunk["hist"],
                                            chunk["target"],
                                            chunk["other"],
                                            trust_scale=trust_scale)

            def make_features(n: int, fseed: int = 0) -> Dict:
                r = np.random.default_rng(fseed)
                iv = cfg.tables[0].vocab
                return {
                    "hist": r.integers(0, iv, size=(n, cfg.seq_len)
                                       ).astype(np.int32),
                    "target": r.integers(0, iv, size=n).astype(np.int32),
                    "other": np.stack(
                        [r.integers(0, t.vocab, size=n)
                         for t in cfg.tables[1:]], axis=1
                    ).astype(np.int32),
                }
            return Evaluator(apply, params), make_features

        if cfg.model == "two_tower":
            from repro.models.recsys import two_tower as Mdl
            params = _place(Mdl.init_params(key, cfg), cfg)

            def apply(params, chunk: Dict) -> jnp.ndarray:
                q = {"user_id": chunk["user_id"][:1],
                     "user_feats": chunk["user_feats"][:1]}
                s = Mdl.retrieval_scores(params, cfg, q,
                                         chunk["item_id"],
                                         chunk["item_feats"],
                                         trust_scale=trust_scale)
                return s[0]

            def make_features(n: int, fseed: int = 0) -> Dict:
                r = np.random.default_rng(fseed)
                return {
                    "user_id": np.full((n,), 1, np.int32),
                    "user_feats": np.zeros((n, 8), np.int32),
                    "item_id": r.integers(0, cfg.tables[1].vocab,
                                          size=n).astype(np.int32),
                    "item_feats": r.integers(0, cfg.tables[3].vocab,
                                             size=(n, 8)).astype(np.int32),
                }
            return Evaluator(apply, params), make_features

        if cfg.model == "mind":
            from repro.models.recsys import mind as Mdl
            params = _place(Mdl.init_params(key, cfg), cfg)

            def apply(params, chunk: Dict) -> jnp.ndarray:
                return Mdl.relevance_scores(params, cfg, chunk["hist"],
                                            chunk["hist_mask"],
                                            chunk["item"],
                                            trust_scale=trust_scale)

            def make_features(n: int, fseed: int = 0) -> Dict:
                r = np.random.default_rng(fseed)
                iv = cfg.tables[0].vocab
                return {
                    "hist": r.integers(0, iv, size=(n, cfg.hist_len)
                                       ).astype(np.int32),
                    "hist_mask": np.ones((n, cfg.hist_len), np.float32),
                    "item": r.integers(0, iv, size=n).astype(np.int32),
                }
            return Evaluator(apply, params), make_features

    raise ValueError(f"no evaluator for {arch_id}")


class ShardedEvaluator(NamedTuple):
    """Production-config evaluator bundle for the fused drain:
    ``evaluate`` (params mesh-sharded per ``distribution.sharding``),
    ``make_features``, the ``feature_sharding`` callable to hand to
    :class:`~repro.core.fused_shedder.FusedLoadShedder` (and through
    ``ServingEngine(feature_sharding=...)``), and the mesh itself."""
    evaluate: Evaluator
    make_features: Callable
    feature_sharding: Callable
    mesh: Any


def make_sharded_evaluator(arch_id: str, *, mesh=None,
                           smoke: bool = False, seed: int = 0,
                           trust_scale: float = 5.0,
                           doc_len: int = 32) -> ShardedEvaluator:
    """Mesh-sharded production evaluator (default ``smoke=False``).

    Parameters are placed with the arch family's
    ``distribution.sharding`` rules — TP columns/rows and EP experts
    over the ``model`` axis for transformers, 2D row-sharded embedding
    tables for recsys — so the evaluator forward inside the fused drain
    window runs as a sharded SPMD program instead of a replicated one.
    ``feature_sharding(features)`` returns the matching input placement
    pytree: every leaf data-parallel over the mesh's DP axes (falling
    back to replication when the batch does not divide them — jax
    rejects ragged ``device_put`` placements). ``mesh=None`` builds the
    1x1 host mesh (tests/CPU smoke); pass
    ``launch.mesh.make_production_mesh()`` on real hardware."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distribution.sharding import (dp_axes, param_specs,
                                             shardings_of)
    if mesh is None:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((1, 1))
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1

    def place_params(params, cfg):
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        return jax.device_put(
            params, shardings_of(param_specs(cfg, shapes, mesh), mesh))

    def feature_sharding(features):
        def one(a):
            arr = np.asarray(a)
            ax = dp if (dp and arr.ndim >= 1
                        and arr.shape[0] % dp_size == 0) else None
            return NamedSharding(
                mesh, P(ax, *([None] * max(arr.ndim - 1, 0))))
        return jax.tree.map(one, features)

    evaluate, make_features = make_evaluator(
        arch_id, smoke=smoke, seed=seed, trust_scale=trust_scale,
        doc_len=doc_len, place_params=place_params)
    return ShardedEvaluator(evaluate, make_features, feature_sharding,
                            mesh)
