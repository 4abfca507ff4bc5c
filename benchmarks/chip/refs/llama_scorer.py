"""Plain float32 reference of the Llama-architecture trust scorer.

Straight from the published equations (Llama: RMSNorm, rotary
positions in the rotate-half form, grouped-query causal attention,
SwiGLU; SmolLM-135M ties the output head to the embedding), in
``jax.numpy`` at ``precision="highest"``, one layer after another, with
no kernel, cache, chunking or batching trick. It imports nothing of the
program. It reads the weights the benchmark made, by the names of the
program's layout; one departure follows that layout: a norm weight is
stored as ``scale`` and applied as ``1 + scale``.

``mode="control"`` is the same computation with every matrix product's
operands rounded to float8 (e4m3, one scale per tensor from its largest
magnitude) and accumulated in float32: the precision one step below the
bfloat16 the configuration serves in.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0          # largest finite float8_e4m3fn


def _round_fp8(a):
    s = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(a, b, mode: str, spec: str):
    if mode == "control":
        a, b = _round_fp8(a), _round_fp8(b)
    return jnp.einsum(spec, a, b, precision="highest")


def _rmsnorm(x, scale, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * (1.0 + scale)


def _rope(x, theta):
    """x: (B, S, H, D), rotate-half rotary embedding at positions 0..S-1."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = np.arange(x.shape[1], dtype=np.float32)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("dims", "mode"))
def _block_trust(w, tokens, *, dims, mode):
    (n_q, n_kv, d_head, vocab, eps, theta, trust_scale) = dims
    emb = w["embed"]["table"].astype(jnp.float32)               # (V, D)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    x = emb[inp]                                                 # (B, S, D)
    b, s, _ = x.shape
    group = n_q // n_kv
    causal = np.tril(np.ones((s, s), bool))

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        h = _rmsnorm(x, p["ln1"]["scale"], eps)
        q = _mm(h, p["attn"]["wq"]["w"], mode, "bsd,de->bse")
        k = _mm(h, p["attn"]["wk"]["w"], mode, "bsd,de->bse")
        v = _mm(h, p["attn"]["wv"]["w"], mode, "bsd,de->bse")
        q = _rope(q.reshape(b, s, n_q, d_head), theta)
        k = _rope(k.reshape(b, s, n_kv, d_head), theta)
        v = v.reshape(b, s, n_kv, d_head)
        k = jnp.repeat(k, group, axis=2)       # query head i reads kv i // g
        v = jnp.repeat(v, group, axis=2)
        sc = _mm(q, k, mode, "bshd,bthd->bhst") / math.sqrt(d_head)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = _mm(pr, v, mode, "bhst,bthd->bshd").reshape(b, s, n_q * d_head)
        x = x + _mm(o, p["attn"]["wo"]["w"], mode, "bse,ed->bsd")
        h = _rmsnorm(x, p["ln2"]["scale"], eps)
        g = _mm(h, p["ffn"]["gate"]["w"], mode, "bsd,df->bsf")
        u = _mm(h, p["ffn"]["up"]["w"], mode, "bsd,df->bsf")
        x = x + _mm(jax.nn.silu(g) * u, p["ffn"]["down"]["w"], mode,
                    "bsf,fd->bsd")
        return x, None

    x, _ = jax.lax.scan(layer, x, w["blocks"])      # one layer at a time
    x = _rmsnorm(x, w["final_norm"]["scale"].astype(jnp.float32), eps)
    logits = _mm(x, emb, mode, "bsd,vd->bsv")                    # tied head
    logp = jax.nn.log_softmax(logits, axis=-1)
    lp = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    mean_lp = jnp.mean(lp, axis=-1)
    return jax.nn.sigmoid(mean_lp + math.log(vocab)) * trust_scale


def trust(cfg: Dict, weights, feats: Dict[str, np.ndarray],
          mode: str = "f32", block: int = 64) -> np.ndarray:
    """Trust of every row of ``feats["tokens"]`` (n, doc_len), computed
    ``block`` rows at a time."""
    dims = (cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"],
            cfg["rms_norm_eps"], float(cfg["rope_theta"]),
            cfg["serving"]["trust_scale"])
    tokens = np.asarray(feats["tokens"], np.int32)
    n = len(tokens)
    pad = -n % block
    tokens = np.concatenate([tokens, np.zeros((pad, tokens.shape[1]),
                                              np.int32)])
    out = [np.asarray(_block_trust(weights, jnp.asarray(tokens[i:i + block]),
                                   dims=dims, mode=mode))
           for i in range(0, len(tokens), block)]
    return np.concatenate(out)[:n]
