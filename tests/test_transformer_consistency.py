"""Transformer numerical-consistency tests: decode == forward, prefill
continuation, scan == unrolled, chunked loss == full loss."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models import transformer as T

KEY = jax.random.PRNGKey(11)


def max_err(a, b):
    return float(jnp.max(jnp.abs(a - b)))


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen2.5-14b",
                                  "moonshot-v1-16b-a3b"])
def test_decode_matches_forward(arch):
    cfg = get_config(arch, smoke=True)
    if cfg.moe is not None:
        # MoE capacity dropping is batch-dependent by design (overflow
        # tokens keep the residual only — DESIGN §4); equivalence holds
        # when capacity is not binding.
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = T.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 10), 0, cfg.vocab_size)
    full, _ = T.forward(params, cfg, toks)
    cache = T.init_kv_cache(cfg, 2, 16)
    errs = []
    for t in range(10):
        lg, cache = T.decode_step(params, cfg, toks[:, t], cache)
        errs.append(max_err(lg, full[:, t]))
    assert max(errs) < 2e-3, errs


def test_windowed_decode_matches_forward():
    cfg = dataclasses.replace(get_config("gemma2-2b", smoke=True),
                              sliding_window=4)
    params = T.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 12), 0, cfg.vocab_size)
    full, _ = T.forward(params, cfg, toks)
    cache = T.init_kv_cache(cfg, 2, 16)
    errs = []
    for t in range(12):
        lg, cache = T.decode_step(params, cfg, toks[:, t], cache)
        errs.append(max_err(lg, full[:, t]))
    assert max(errs) < 2e-3, errs


def test_prefill_then_decode_continues_correctly():
    cfg = get_config("smollm-135m", smoke=True)
    params = T.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 12), 0, cfg.vocab_size)
    full, _ = T.forward(params, cfg, toks)
    # prefill the first 8 tokens, then decode the rest one by one
    _, cache = T.prefill(params, cfg, toks[:, :8], max_len=16)
    errs = []
    for t in range(8, 12):
        lg, cache = T.decode_step(params, cfg, toks[:, t], cache)
        errs.append(max_err(lg, full[:, t]))
    assert max(errs) < 2e-3, errs


def test_prefill_score_matches_score_tokens():
    cfg = get_config("smollm-135m", smoke=True)
    params = T.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (3, 16), 0, cfg.vocab_size)
    s1, _ = T.prefill(params, cfg, toks)
    s2 = T.score_tokens(params, cfg, toks)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("arch,vocab_chunk", [
    ("smollm-135m", 64),       # tied embeddings, chunk divides V
    ("smollm-135m", 96),       # ragged last chunk (masked padding rows)
    ("qwen2.5-14b", 100),      # untied unembedding
    ("gemma2-2b", 64),         # final-logit softcap per chunk
])
def test_chunked_target_logprobs_match_full_log_softmax(arch, vocab_chunk):
    """The vocab-chunked logsumexp equals log_softmax over the full
    (B, S, V) logits, target by target."""
    cfg = get_config(arch, smoke=True)
    params = T.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (3, 12), 0, cfg.vocab_size)
    logits, _ = T.forward(params, cfg, toks[:, :-1])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    want = jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1)[..., 0]
    x, _ = T.hidden_states(params, cfg, toks[:, :-1])
    got = T.target_logprobs(params, cfg, x, toks[:, 1:],
                            vocab_chunk=vocab_chunk)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen3-moe-30b-a3b"])
def test_scan_matches_unrolled(arch):
    """scan_layers=True must be numerically identical to the unrolled
    python loop (same stacked params)."""
    cfg_u = dataclasses.replace(get_config(arch, smoke=True),
                                n_layers=3, scan_layers=False)
    cfg_s = dataclasses.replace(cfg_u, scan_layers=True)
    params_s = T.init_params(KEY, cfg_s)
    first_dense = cfg_s.moe.first_k_dense if cfg_s.moe else 0
    n_scan = cfg_s.n_layers - first_dense
    # unstack scanned params into the list layout
    params_u = dict(params_s)
    params_u["blocks"] = [
        jax.tree.map(lambda a: a[i], params_s["blocks"])
        for i in range(n_scan)]
    toks = jax.random.randint(KEY, (2, 8), 0, cfg_u.vocab_size)
    lo_s, _ = T.forward(params_s, cfg_s, toks)
    lo_u, _ = T.forward(params_u, cfg_u, toks)
    assert max_err(lo_s, lo_u) < 1e-4


def test_chunked_loss_matches_full():
    cfg = get_config("smollm-135m", smoke=True)
    params = T.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    l1, _ = T.lm_loss(params, cfg, toks, toks, loss_chunk=8)
    logits, _ = T.forward(params, cfg, toks)
    l2 = L.cross_entropy(logits, toks)
    assert abs(float(l1) - float(l2)) < 1e-4


def test_chunked_loss_gradients_match():
    cfg = get_config("smollm-135m", smoke=True)
    params = T.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    g1 = jax.grad(lambda p: T.lm_loss(p, cfg, toks, toks,
                                      loss_chunk=4)[0])(params)
    g2 = jax.grad(lambda p: T.lm_loss(p, cfg, toks, toks,
                                      loss_chunk=16)[0])(params)
    leaves1, leaves2 = jax.tree.leaves(g1), jax.tree.leaves(g2)
    for a, b in zip(leaves1, leaves2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-5)


def test_rope_positions_shift_invariance():
    """RoPE attention scores depend only on relative positions."""
    d, theta = 32, 10_000.0
    q = jax.random.normal(KEY, (1, 4, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 1, d))
    pos = jnp.arange(4)[None]
    q1 = L.apply_rope(q, pos, theta)
    k1 = L.apply_rope(k, pos, theta)
    q2 = L.apply_rope(q, pos + 100, theta)
    k2 = L.apply_rope(k, pos + 100, theta)
    s1 = jnp.einsum("bshd,bthd->bst", q1, k1)
    s2 = jnp.einsum("bshd,bthd->bst", q2, k2)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)
