"""granite-4.0-h's hybrid stack against its plain reference, at a small
size with seeded random weights on the CPU.

The reference is ``benchmarks/chip/refs/granite_hybrid.py`` (float32 at
``precision="highest"``, the Mamba-2 recurrence token by token, every
held expert on every token then masked by routing). The program runs in
float32 here, so each comparison's tolerance (1e-5 on trust in [0, 5],
2e-5 on activations of unit scale) leaves room for summation order only.
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig, SSMConfig, TransformerConfig, \
    TrustIRConfig
from repro.core.fused_shedder import FusedLoadShedder
from repro.core.shedder import TIER_EVAL
from repro.models import moe as M
from repro.models import ssm as SSM
from repro.models import transformer as T
from repro.serving.evaluators import Evaluator

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "granite_reference", ROOT / "benchmarks/chip/refs/granite_hybrid.py")
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

KEY = jax.random.PRNGKey(5)
TRUST_SCALE = 5.0
# The published pattern's period, from its attention layer: 1 attention
# then 3 Mamba-2 layers (granite's is 1 then 9).
KINDS = ("attention", "mamba", "mamba", "mamba")


def configs(n_held: int = 4, held_offset: int = 2, kinds=KINDS):
    """(program config, the reference's configuration dict), one model."""
    ref_cfg = {
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "attention_multiplier": 0.0625,
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_n_groups": 2, "mamba_d_conv": 4,
        "num_experts_per_tok": 3, "first_held_expert": held_offset,
        "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 16,
        "vocab_size": 256, "layer_types": list(kinds), "first_layer": 0,
        "num_hidden_layers": len(kinds),
        "serving": {"trust_scale": TRUST_SCALE}}
    tcfg = TransformerConfig(
        name="tiny-granite", n_layers=len(kinds), d_model=64, n_heads=4,
        n_kv_heads=2, d_head=16, d_ff=32, vocab_size=256,
        tie_embeddings=True, norm_eps=1e-5, dtype="float32",
        param_dtype="float32",
        moe=MoEConfig(n_experts=8, top_k=3, d_expert=32, n_shared_experts=1,
                      d_shared=48, dispatch="dropless",
                      topk_then_softmax=True, n_held=n_held,
                      held_offset=held_offset),
        layer_kinds=tuple(kinds),
        ssm=SSMConfig(n_heads=8, d_head=16, d_state=16, n_groups=2),
        use_rope=False, attn_scale=0.0625, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0)
    return tcfg, ref_cfg


def weights(tcfg, key=KEY):
    """The program's initialisation with the norm scales, the
    convolution bias and D drawn too (they start at 0 and 1)."""
    params = T.init_params(key, tcfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(leaves):
        name = getattr(path[-1], "key", "")
        noise = 0.1 * jax.random.normal(jax.random.fold_in(key, i), a.shape)
        out.append(a + noise if name in ("scale", "conv_b", "D") else a)
    return jax.tree_util.tree_unflatten(treedef, out)


def tokens(n: int, seed: int = 0, length: int = 12) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, (n, length)).astype(np.int32)


def program_trust(params, tcfg, toks):
    lp = T.score_tokens(params, tcfg, jnp.asarray(toks), q_chunk=16)
    return np.asarray(jax.nn.sigmoid(lp + jnp.log(256.0)) * TRUST_SCALE)


def test_mamba2_chunked_form_matches_sequential_recurrence():
    tcfg, rc = configs()
    p = weights(tcfg)["runs"][1]
    p = jax.tree.map(lambda a: a[0], p)["mamba"]          # first Mamba layer
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 11, 64))
    got = SSM.mamba2_apply(p, x, tcfg.ssm, eps=1e-5,
                           compute_dtype=jnp.float32)
    dims = (rc["mamba_n_heads"], rc["mamba_d_head"], rc["mamba_d_state"],
            rc["mamba_n_groups"], rc["mamba_d_conv"], rc["rms_norm_eps"])
    want = ref._mamba(p, x, dims, "f32")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5)


def test_mamba2_refuses_more_than_one_chunk():
    tcfg, _ = configs()
    p = jax.tree.map(lambda a: a[0], weights(tcfg)["runs"][1])["mamba"]
    x = jnp.zeros((1, tcfg.ssm.chunk_size + 1, 64))
    with pytest.raises(ValueError, match="one SSD chunk"):
        SSM.mamba2_apply(p, x, tcfg.ssm)


def test_one_period_matches_reference():
    """Layer kinds in the period's order, multipliers, NoPE attention
    with the configured scale, the held experts and the tied head."""
    tcfg, rc = configs()
    params = weights(tcfg)
    toks = tokens(10)
    got = program_trust(params, tcfg, toks)
    want = ref.trust(rc, params, {"tokens": toks}, block=5)
    assert np.std(want) > 100 * 1e-5      # spreads beyond the tolerance
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_layer_runs_follow_the_pattern():
    tcfg, _ = configs(kinds=("mamba",) * 2 + ("attention",) + ("mamba",) * 3)
    assert T.layer_runs(tcfg) == (("mamba", 2), ("attention", 1),
                                  ("mamba", 3))
    params = T.init_params(KEY, tcfg)
    assert [jax.tree.leaves(r)[0].shape[0] for r in params["runs"]] == \
        [2, 1, 3]


def counted_evaluator(params, tcfg):
    def apply(params, chunk):
        lp, m = T.score_tokens(params, tcfg, chunk["tokens"], q_chunk=16,
                               return_metrics=True)
        trust = jax.nn.sigmoid(lp + jnp.log(256.0)) * TRUST_SCALE
        return trust, {"expert_rows": m["expert_rows"],
                       "expert_slots": m["expert_slots"]}
    return Evaluator(apply, params, counts=("expert_rows", "expert_slots"))


def test_whole_scorer_through_fused_shedder_matches_reference():
    """The fused step's evaluator slices, with the counts summed over
    them: evaluated rows carry the reference's trust, and the expert
    rows run are 4 layers x 11 positions x min(3, 4) per evaluated
    row, padding rows of the last slice included."""
    tcfg, rc = configs()
    params = weights(tcfg)
    cfg = TrustIRConfig(u_capacity=24, u_threshold=24, chunk_size=8,
                        cache_slots=64, cache_ways=4)
    shed = FusedLoadShedder(cfg, counted_evaluator(params, tcfg))
    toks = tokens(20, seed=3)
    keys = np.arange(100, 120, dtype=np.uint32)
    res = shed.process(keys, keys % 64, {"tokens": toks})
    ev = res.tier == TIER_EVAL
    assert ev.sum() == 20
    want = ref.trust(rc, params, {"tokens": toks}, block=10)
    np.testing.assert_allclose(res.trust[ev], want[ev], rtol=0, atol=1e-5)
    c = res.counts
    assert c["n_expert_slots"] == 4 * 11 * 3 * c["n_eval_rows"]
    assert 0 < c["n_expert_rows"] < c["n_expert_slots"]


def test_trust_is_batch_invariant():
    """A document's trust alone equals its trust in a slice of its own
    copies, where every other token routes to the same experts as its
    tokens. Experts cut at a capacity change it there."""
    tcfg, _ = configs(n_held=8, held_offset=0)
    params = weights(tcfg)
    doc = tokens(1, seed=7)
    crowd = np.repeat(doc, 16, axis=0)
    alone = program_trust(params, tcfg, doc)[0]
    np.testing.assert_allclose(program_trust(params, tcfg, crowd), alone,
                               rtol=0, atol=1e-6)

    capped = dataclasses.replace(tcfg, moe=dataclasses.replace(
        tcfg.moe, dispatch="dense_scatter", norm_topk_prob=False))

    def to_capped(p):
        f = tcfg.moe.d_expert
        moe = dict(p["moe"], w_gate=p["moe"]["w_in"][..., :f],
                   w_up=p["moe"]["w_in"][..., f:], w_down=p["moe"]["w_out"])
        return dict(p, moe=moe)
    capped_params = dict(params, runs=[to_capped(r) for r in params["runs"]])
    # The capped layer routes by a softmax over all experts, not over the
    # top k: compare it with itself, alone and crowded.
    c_alone = program_trust(capped_params, capped, doc)[0]
    c_crowd = program_trust(capped_params, capped, crowd)
    assert np.max(np.abs(c_crowd - c_alone)) > 100 * 1e-6


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four devices of 2 held experts each (the shared expert on one of
    them) give parts that add up to the layer with all 8 experts held,
    and to the reference's uncut layer."""
    tcfg, rc = configs(n_held=8, held_offset=0)
    full = jax.tree.map(lambda a: a[0], weights(tcfg)["runs"][0])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 64))
    whole, counts = M.dropless_apply(full, x, tcfg.moe,
                                     compute_dtype=jnp.float32)
    parts, rows = 0.0, 0
    for i in range(4):
        share = dataclasses.replace(tcfg.moe, n_held=2, held_offset=2 * i)
        p = {"router": full["router"],
             "w_in": full["w_in"][2 * i:2 * i + 2],
             "w_out": full["w_out"][2 * i:2 * i + 2]}
        if i == 0:
            p["shared"] = full["shared"]
        out, c = M.dropless_apply(p, x, share, compute_dtype=jnp.float32)
        parts, rows = parts + out, rows + int(c["expert_rows"])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=0, atol=2e-5)
    assert rows == int(counts["expert_rows"]) == 40 * 3
    want = ref._moe(full, x[None], (3, 0), "f32")[0]
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=0, atol=2e-5)


def test_configs_without_layer_kinds_keep_their_layout():
    """A plain transformer keeps its stacked ``blocks`` and no ``runs``;
    its score call is unchanged (one output)."""
    tcfg = TransformerConfig(name="plain", n_layers=2, d_model=32,
                             n_heads=4, n_kv_heads=2, d_head=8, d_ff=64,
                             vocab_size=64, dtype="float32")
    params = T.init_params(KEY, tcfg)
    assert "blocks" in params and "runs" not in params
    out = T.score_tokens(params, tcfg, jnp.asarray(tokens(2) % 64))
    assert out.shape == (2,)


def test_expert_counts_reach_the_scheduler_stats():
    """The serving path's counters: the coordinator's fused drain sums
    each batch's expert rows and slots into ``scheduler_stats``, and an
    evaluator without experts names no such counter."""
    from repro.cluster import ClusterConfig, ClusterCoordinator

    tcfg, _ = configs()
    counted = counted_evaluator(weights(tcfg), tcfg)
    plain = Evaluator(lambda p, chunk: jnp.sum(chunk["tokens"], -1) * 0.0,
                      None)
    cfg = TrustIRConfig(u_capacity=24, u_threshold=24, chunk_size=8,
                        cache_slots=64, cache_ways=4, n_replicas=1)
    for evaluator, has_experts in ((counted, True), (plain, False)):
        coord = ClusterCoordinator(
            cfg, lambda chunk: np.zeros(len(chunk["tokens"]), np.float32),
            cluster_cfg=ClusterConfig(hedge_after_s=0.0, autoscale=False),
            drain_mode="fused", evaluate_batch=evaluator)
        keys = np.arange(500, 516, dtype=np.uint32)
        coord.enqueue(keys, (keys % 64).astype(np.int32),
                      {"tokens": tokens(16, seed=4)})
        coord.drain()
        stats = coord.scheduler_stats()
        assert stats["n_evaluated"] == 16
        if has_experts:
            assert stats["n_expert_slots"] == \
                4 * 11 * 3 * stats["n_eval_rows"]
            assert 0 < stats["n_expert_rows"] < stats["n_expert_slots"]
        else:
            assert "n_expert_rows" not in stats
            assert "n_expert_slots" not in stats
