"""A granite-4.0-h hybrid language model as the trust evaluator.

Mamba-2 and attention layers in the published pattern, each followed by
a routed MoE with a shared expert (``layer_types``, ``num_local_experts``,
``num_experts_per_tok``), the three multipliers, and a tied head whose
logits are divided by ``logits_scaling``. The program scores a
candidate's document by the mean log probability of its tokens and
serves ``sigmoid(mean logprob + ln V) * trust_scale``, as the
``llama_scorer`` family does, through the same ``T.score_tokens``.

The configuration holds one chip's share of a stated deployment: the
layers ``first_layer`` .. ``first_layer + num_hidden_layers - 1`` of
``layer_types``, the ``num_local_experts`` experts from
``first_held_expert`` of each layer's ``num_local_experts_published``
(the router keeps its published width), and a slice of the vocabulary
(``vocab_size``). The evaluator reports its expert layer's counts
(``expert_rows``, ``expert_slots``) beside the scores.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import numpy as np

from benchmarks.chip import hashing


def program_config(cfg: Dict):
    from repro.configs.base import MoEConfig, SSMConfig, TransformerConfig
    m = cfg
    if m["attention_bias"] or m["mamba_proj_bias"]:
        raise ValueError("the program has no projection biases for this "
                         "family")
    if m["mamba_expand"] * m["hidden_size"] \
            != m["mamba_n_heads"] * m["mamba_d_head"]:
        raise ValueError("mamba_expand * hidden_size must equal "
                         "mamba_n_heads * mamba_d_head")
    first, n = m["first_layer"], m["num_hidden_layers"]
    kinds = tuple(m["layer_types"][first:first + n])
    if len(kinds) != n:
        raise ValueError(f"layer_types has no layers {first}..{first + n - 1}")
    moe = MoEConfig(
        n_experts=m["num_local_experts_published"],
        top_k=m["num_experts_per_tok"],
        d_expert=m["intermediate_size"],
        n_shared_experts=1,
        d_shared=m["shared_intermediate_size"],
        dispatch="dropless",
        topk_then_softmax=True,
        n_held=m["num_local_experts"],
        held_offset=m["first_held_expert"])
    ssm = SSMConfig(
        n_heads=m["mamba_n_heads"], d_head=m["mamba_d_head"],
        d_state=m["mamba_d_state"], n_groups=m["mamba_n_groups"],
        d_conv=m["mamba_d_conv"], conv_bias=m["mamba_conv_bias"],
        chunk_size=m["mamba_chunk_size"])
    return TransformerConfig(
        name=cfg["name"],
        n_layers=n,
        d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        d_head=m["hidden_size"] // m["num_attention_heads"],
        d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"],
        tie_embeddings=m["tie_word_embeddings"],
        rope_theta=float(m["rope_theta"]),
        norm_eps=m["rms_norm_eps"],
        act=m["hidden_act"],
        moe=moe,
        layer_kinds=kinds,
        ssm=ssm,
        use_rope=m["position_embedding_type"] != "nope",
        attn_scale=m["attention_multiplier"],
        embedding_multiplier=float(m["embedding_multiplier"]),
        residual_multiplier=m["residual_multiplier"],
        logits_scaling=float(m["logits_scaling"]),
        dtype=cfg["serving"]["compute_dtype"],
        param_dtype=cfg["serving"]["param_dtype"],
    )


def make_weights(cfg: Dict, key):
    """Every weight from ``key`` in one jitted call on the device, in the
    layout the program's model code reads and in its parameter dtype.
    Matrices (and each expert's) are N(0, 1/fan_in), the embedding
    N(0, 0.02**2) (as ``llama_scorer``'s), norm scales N(0, 0.1**2)
    (applied as 1 + scale), the convolution taps N(0, 1/d_conv) and its
    bias N(0, 0.1**2); per
    Mamba-2 head, A = exp(A_log) is uniform on [1, 16], dt_bias the
    inverse softplus of a dt log-uniform on [1e-3, 1e-1] (the Mamba-2
    initialisation), and D is N(1, 0.1**2)."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T

    tcfg = program_config(cfg)
    template = jax.eval_shape(partial(T.init_params, cfg=tcfg), key)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)

    def fill_leaf(k, path, leaf):
        # Each leaf draws only its own distribution, in its own dtype:
        # a float32 draw of the 3 B matrix entries would double the bits
        # drawn and the time set-up spends on them.
        name = getattr(path[-1], "key", str(path[-1]))
        shape, dtype = leaf.shape, leaf.dtype
        if name == "A_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                              1.0, 16.0)).astype(dtype)
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                            math.log(1e-3), math.log(1e-1)))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        if name == "D":
            std, mean = 0.1, 1.0
        elif name in ("scale", "conv_b"):
            std, mean = 0.1, 0.0
        elif getattr(path[0], "key", "") == "embed":
            std, mean = 0.02, 0.0
        else:                               # conv taps and matrices
            std, mean = float(shape[-2]) ** -0.5, 0.0
        normal = jax.random.normal(k, shape, dtype) * jnp.asarray(std, dtype)
        return normal + jnp.asarray(mean, dtype) if mean else normal

    @jax.jit
    def fill(k):
        return jax.tree_util.tree_unflatten(treedef, [
            fill_leaf(jax.random.fold_in(k, i), path, leaf)
            for i, (path, leaf) in enumerate(leaves)])

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
        lp, m = T.score_tokens(params, tcfg, chunk["tokens"],
                               q_chunk=doc_len, return_metrics=True)
        trust = jax.nn.sigmoid(lp + jnp.log(float(tcfg.vocab_size))) * scale
        return trust, {"expert_rows": m["expert_rows"],
                       "expert_slots": m["expert_slots"]}

    return Evaluator(apply, params, counts=("expert_rows", "expert_slots"))


def features(cfg: Dict, urls: np.ndarray) -> Dict[str, np.ndarray]:
    """The document tokens of each URL: ``doc_len`` ids of the sliced
    vocabulary, a hash of the URL id and the position."""
    doc_len = cfg["serving"]["doc_len"]
    h = hashing.mix64(urls.astype(np.uint64)[:, None] * np.uint64(1 << 16)
                      + np.arange(doc_len, dtype=np.uint64)[None, :])
    tokens = h % np.uint64(cfg["vocab_size"])
    return {"tokens": tokens.astype(np.int32)}


def flops_per_item(cfg: Dict) -> float:
    """Operations of one candidate's forward on this chip, two per
    multiply-accumulate: every projection over the ``doc_len - 1``
    scored positions, the causal (query, key) pairs of attention and of
    the SSD chunk (C B^T over the state, then the decay-masked product
    over each head), the depthwise convolution, the router, the shared
    expert, the held experts at their expected rows (top_k x held /
    experts per token) and the sliced vocabulary head (the log-sum-exp
    needs every logit)."""
    m = cfg
    d, v = m["hidden_size"], m["vocab_size"]
    s = cfg["serving"]["doc_len"] - 1
    pairs = s * (s + 1) // 2
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    dh = d // hq
    h, p, n, g = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
                  m["mamba_n_groups"])
    d_inner = h * p
    conv_dim = d_inner + 2 * g * n
    first, layers = m["first_layer"], m["num_hidden_layers"]
    kinds = m["layer_types"][first:first + layers]
    n_mamba = sum(k == "mamba" for k in kinds)
    mamba = (s * (d * (d_inner + conv_dim + h) + d_inner * d
                  + m["mamba_d_conv"] * conv_dim)
             + pairs * (g * n + h * p))
    attn = s * (d * hq * dh * 2 + d * hkv * dh * 2) + hq * pairs * dh * 2
    routed = (m["num_experts_per_tok"] * m["num_local_experts"]
              / m["num_local_experts_published"])
    moe = s * (d * m["num_local_experts_published"]              # router
               + 3 * d * m["shared_intermediate_size"]
               + routed * 3 * d * m["intermediate_size"])
    macs = (n_mamba * mamba + (layers - n_mamba) * attn + layers * moe
            + s * d * v)
    return 2.0 * macs
