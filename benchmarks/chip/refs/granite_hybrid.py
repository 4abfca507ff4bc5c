"""Plain float32 reference of the granite-4.0-h hybrid trust scorer.

Straight from the published equations (GraniteMoeHybrid), in
``jax.numpy`` at ``precision="highest"``, one layer after another, with
no kernel, cache, chunking or batching trick. It imports nothing of the
program. Per layer, with m = ``residual_multiplier``:

    x = x + m * mixer(rmsnorm(x)),   x = x + m * (moe(h) + shared(h)),
    h = rmsnorm(x)

* Mamba-2 mixer: ``in_proj`` into z, xBC and dt; a causal depthwise
  convolution with bias over xBC, then SiLU; dt = softplus(dt +
  dt_bias), A = -exp(A_log); the recurrence run token by token,
  h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T and y_t = C_t h_t + D x_t
  (the sequential form, not the program's chunked matrix form); the
  gated RMSNorm rmsnorm(y * silu(z)) over each group's channels; and
  ``out_proj``.
* Attention: grouped-query causal attention without positions
  (``position_embedding_type`` "nope"), scores scaled by
  ``attention_multiplier``.
* MoE: router logits over all ``num_local_experts_published`` experts,
  the top ``num_experts_per_tok``, softmax over those; every held expert
  runs on every token (SwiGLU: the first half of ``input_linear`` is the
  gate), and each token keeps the outputs of the held experts it was
  routed to, weighted by its gates; plus the shared SwiGLU expert.
* The embedding times ``embedding_multiplier``; the tied head's logits
  divided by ``logits_scaling``; the log-softmax over the vocabulary.

Departures, each following the program's layout: a norm weight is
stored as ``scale`` and applied as ``1 + scale``; the model is the
configuration's share of a deployment (``num_hidden_layers`` layers from
``first_layer``, the held experts, the sliced vocabulary), and what the
absent experts would add is left out, as in the program.

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


def _mamba(p, h, dims, mode):
    (heads, d_head, d_state, groups, d_conv, eps) = dims
    b, s, _ = h.shape
    d_inner = heads * d_head
    conv_dim = d_inner + 2 * groups * d_state
    zxbcdt = _mm(h, p["in_proj"]["w"], mode, "bsd,de->bse")
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
    dt = zxbcdt[..., d_inner + conv_dim:]
    # out[t] = sum_j conv_w[d_conv - 1 - j] * xbc[t - j] over j <= t.
    conv = p.get("conv_b", 0.0) + sum(
        p["conv_w"][d_conv - 1 - j]
        * jnp.pad(xbc, ((0, 0), (j, 0), (0, 0)))[:, :s]
        for j in range(d_conv))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_inner].reshape(b, s, heads, d_head)
    bm = xbc[..., d_inner:d_inner + groups * d_state] \
        .reshape(b, s, groups, d_state)
    cm = xbc[..., d_inner + groups * d_state:].reshape(b, s, groups, d_state)
    per = heads // groups                       # head i reads group i // per
    bm = jnp.repeat(bm, per, axis=2)                       # (b, s, H, N)
    cm = jnp.repeat(cm, per, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                # (b, s, H)
    a = -jnp.exp(p["A_log"])                               # (H,)

    def step(state, xs):                        # state: (b, H, P, N)
        x_t, b_t, c_t, dt_t = xs
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        y_t = _mm(state, c_t, mode, "bhpn,bhn->bhp") \
            + p["D"][:, None] * x_t
        return state, y_t

    state0 = jnp.zeros((b, heads, d_head, d_state), jnp.float32)
    _, y = jax.lax.scan(step, state0,
                        (jnp.moveaxis(x, 1, 0), jnp.moveaxis(bm, 1, 0),
                         jnp.moveaxis(cm, 1, 0), jnp.moveaxis(dt, 1, 0)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, d_inner) * jax.nn.silu(z)
    yg = y.reshape(b, s, groups, d_inner // groups)
    yg = yg / jnp.sqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
    y = yg.reshape(b, s, d_inner) * (1.0 + p["norm"]["scale"])
    return _mm(y, p["out_proj"]["w"], mode, "bse,ed->bsd")


def _attention(p, h, dims, mode):
    (n_q, n_kv, d_head, scale) = dims
    b, s, _ = h.shape
    q = _mm(h, p["wq"]["w"], mode, "bsd,de->bse").reshape(b, s, n_q, d_head)
    k = _mm(h, p["wk"]["w"], mode, "bsd,de->bse").reshape(b, s, n_kv, d_head)
    v = _mm(h, p["wv"]["w"], mode, "bsd,de->bse").reshape(b, s, n_kv, d_head)
    k = jnp.repeat(k, n_q // n_kv, axis=2)     # query head i reads kv i // g
    v = jnp.repeat(v, n_q // n_kv, axis=2)
    sc = _mm(q, k, mode, "bshd,bthd->bhst") * scale
    sc = jnp.where(np.tril(np.ones((s, s), bool))[None, None], sc, -jnp.inf)
    o = _mm(jax.nn.softmax(sc, axis=-1), v, mode, "bhst,bthd->bshd")
    return _mm(o.reshape(b, s, n_q * d_head), p["wo"]["w"], mode,
               "bse,ed->bsd")


def _moe(p, h, dims, mode):
    (top_k, first_held) = dims
    held, _, two_f = p["w_in"].shape
    f = two_f // 2
    logits = _mm(h, p["router"]["w"], mode, "bsd,de->bse")
    top, idx = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(top, axis=-1)                    # (b, s, K)
    # Each token's gate for each held expert (0 where not routed to it).
    mine = idx[..., None] == first_held + np.arange(held)   # (b, s, K, E)
    weight = jnp.sum(jnp.where(mine, gates[..., None], 0.0), axis=-2)
    gu = _mm(h, p["w_in"], mode, "bsd,edf->bsef")           # every expert
    act = jax.nn.silu(gu[..., :f]) * gu[..., f:]
    routed = _mm(act * weight[..., None], p["w_out"], mode,
                 "bsef,efd->bsd")
    sh = p["shared"]
    g = _mm(h, sh["gate"]["w"], mode, "bsd,df->bsf")
    u = _mm(h, sh["up"]["w"], mode, "bsd,df->bsf")
    return routed + _mm(jax.nn.silu(g) * u, sh["down"]["w"], mode,
                        "bsf,fd->bsd")


@partial(jax.jit, static_argnames=("kinds", "dims", "mode"))
def _block_trust(w, tokens, *, kinds, dims, mode):
    (mamba_dims, attn_dims, moe_dims, eps, emb_mult, res_mult, logit_div,
     vocab, trust_scale) = dims
    emb = w["embed"]["table"].astype(jnp.float32)               # (V, D)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    x = emb[inp] * emb_mult                                      # (B, S, D)

    def layer(kind):
        def one(x, p):
            p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
            h = _rmsnorm(x, p["ln1"]["scale"], eps)
            if kind == "mamba":
                o = _mamba(p["mamba"], h, mamba_dims, mode)
            else:
                o = _attention(p["attn"], h, attn_dims, mode)
            x = x + res_mult * o
            h = _rmsnorm(x, p["ln2"]["scale"], eps)
            return x + res_mult * _moe(p["moe"], h, moe_dims, mode), None
        return one

    for kind, run in zip(kinds, w["runs"]):     # one layer at a time
        x, _ = jax.lax.scan(layer(kind), x, run)
    x = _rmsnorm(x, w["final_norm"]["scale"].astype(jnp.float32), eps)
    logits = _mm(x, emb, mode, "bsd,vd->bsv") / logit_div       # tied head
    logp = jax.nn.log_softmax(logits, axis=-1)
    lp = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    mean_lp = jnp.mean(lp, axis=-1)
    return jax.nn.sigmoid(mean_lp + math.log(vocab)) * trust_scale


def run_kinds(cfg: Dict):
    """The kind of each run of same-kind layers the configuration holds,
    in the order of the weights' ``runs``."""
    first, n = cfg["first_layer"], cfg["num_hidden_layers"]
    kinds = []
    for k in cfg["layer_types"][first:first + n]:
        if not kinds or kinds[-1] != k:
            kinds.append(k)
    return tuple(kinds)


def static_args(cfg: Dict):
    """(run kinds, dims): the static arguments of ``_block_trust``."""
    c = cfg
    hq = c["num_attention_heads"]
    dims = (
        (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
         c["mamba_n_groups"], c["mamba_d_conv"], c["rms_norm_eps"]),
        (hq, c["num_key_value_heads"], c["hidden_size"] // hq,
         c["attention_multiplier"]),
        (c["num_experts_per_tok"], c["first_held_expert"]),
        c["rms_norm_eps"], float(c["embedding_multiplier"]),
        c["residual_multiplier"], float(c["logits_scaling"]),
        c["vocab_size"], c["serving"]["trust_scale"])
    return run_kinds(cfg), dims


def trust(cfg: Dict, weights, feats: Dict[str, np.ndarray],
          mode: str = "f32", block: int = 32) -> np.ndarray:
    """Trust of every row of ``feats["tokens"]`` (n, doc_len), computed
    ``block`` rows at a time."""
    kinds, dims = static_args(cfg)
    tokens = np.asarray(feats["tokens"], np.int32)
    n = len(tokens)
    pad = -n % block
    tokens = np.concatenate([tokens, np.zeros((pad, tokens.shape[1]),
                                              np.int32)])
    out = [np.asarray(_block_trust(weights, jnp.asarray(tokens[i:i + block]),
                                   kinds=kinds, dims=dims, mode=mode))
           for i in range(0, len(tokens), block)]
    return np.concatenate(out)[:n]
