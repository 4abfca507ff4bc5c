"""A Llama-architecture language model as the trust evaluator.

The program scores a candidate URL's document by the mean log
probability of its tokens under the model and serves
``sigmoid(mean logprob + ln V) * trust_scale`` (the head of
``serving.evaluators.make_evaluator`` for transformers). This file
builds that evaluator from a configuration file of the family
``llama_scorer``: the program's model code with weights that the
benchmark makes, so the plain reference (``refs/llama_scorer.py``) can
read the same weights without taking anything the program made.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import numpy as np

from benchmarks.chip import hashing


def program_config(cfg: Dict):
    from repro.configs.base import TransformerConfig
    m = cfg
    return TransformerConfig(
        name=cfg["name"],
        n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        d_head=m["head_dim"],
        d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"],
        tie_embeddings=m["tie_word_embeddings"],
        rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"],
        act=m["hidden_act"],
        dtype=cfg["serving"]["compute_dtype"],
        param_dtype=cfg["serving"]["param_dtype"],
    )


def make_weights(cfg: Dict, key):
    """Every weight from ``key`` in one jitted call on the device, in
    the layout the program's model code reads and in its parameter
    dtype. Matrices are N(0, 1/fan_in), the embedding N(0, 0.02**2),
    norm scales N(0, 0.1**2) (the program's RMSNorm multiplies by
    1 + scale)."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T

    tcfg = program_config(cfg)
    template = jax.eval_shape(partial(T.init_params, cfg=tcfg), key)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)

    def std_of(path, shape) -> float:
        names = [getattr(p, "key", str(p)) for p in path]
        if names[-1] == "scale":
            return 0.1
        if names[0] == "embed":
            return 0.02
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
    import jax.numpy as jnp
    from repro.models import transformer as T
    from repro.serving.evaluators import Evaluator

    tcfg = program_config(cfg)
    doc_len = cfg["serving"]["doc_len"]
    scale = cfg["serving"]["trust_scale"]

    def apply(params, chunk: Dict):
        lp = T.score_tokens(params, tcfg, chunk["tokens"], q_chunk=doc_len)
        return jax.nn.sigmoid(lp + jnp.log(float(tcfg.vocab_size))) * scale

    return Evaluator(apply, params)


def features(cfg: Dict, urls: np.ndarray) -> Dict[str, np.ndarray]:
    """The document tokens of each URL: ``doc_len`` ids, a hash of the
    URL id and the position."""
    doc_len = cfg["serving"]["doc_len"]
    h = hashing.mix64(urls.astype(np.uint64)[:, None] * np.uint64(1 << 16)
                      + np.arange(doc_len, dtype=np.uint64)[None, :])
    tokens = h % np.uint64(cfg["vocab_size"])
    return {"tokens": tokens.astype(np.int32)}


def flops_per_item(cfg: Dict) -> float:
    """Operations of one candidate's forward: every matrix product over
    the ``doc_len - 1`` scored positions, the causal attention products,
    and the full vocabulary head (the log-sum-exp needs every logit)."""
    m = cfg
    d, f, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    hq, hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    s = cfg["serving"]["doc_len"] - 1
    per_token_layer = d * hq * dh * 2 + d * hkv * dh * 2 + 3 * d * f
    attn_pairs = s * (s + 1) // 2            # causal (query, key) pairs
    layers = m["num_hidden_layers"]
    macs = (s * layers * per_token_layer + layers * hq * attn_pairs * dh * 2
            + s * d * v)
    return 2.0 * macs
