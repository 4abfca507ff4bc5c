"""Config system: typed, frozen dataclasses for every architecture family.

Every assigned architecture gets one module in this package exporting:
  ``config()``       -> the exact published configuration,
  ``smoke_config()`` -> a reduced same-family configuration for CPU smoke tests,
  ``shapes()``       -> the arch's assigned input-shape set (list[ShapeSpec]).

The registry (``repro.configs.registry``) maps ``--arch <id>`` to these.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple


# ---------------------------------------------------------------------------
# Shape specs (one per dry-run cell)
# ---------------------------------------------------------------------------

# Kinds determine which step function is lowered in the dry-run.
SHAPE_KINDS = (
    "train",            # train_step: full fwd+bwd+optimizer
    "prefill",          # prefill_step: forward, fills KV cache
    "decode",           # serve_step: one new token against a KV cache
    "serve",            # serve_step: pure forward scoring (recsys / gnn inference)
    "retrieval",        # serve_step: 1 query vs n_candidates scoring
    "graph_full",       # full-batch graph train_step
    "graph_minibatch",  # sampled-subgraph train_step
    "graph_batched",    # batched small graphs train_step
)


@dataclass(frozen=True)
class ShapeSpec:
    """One (input-shape) cell for an architecture."""

    name: str
    kind: str
    # LM shapes
    seq_len: int = 0
    global_batch: int = 0
    # recsys shapes
    batch: int = 0
    n_candidates: int = 0
    # graph shapes
    n_nodes: int = 0
    n_edges: int = 0
    d_feat: int = 0
    batch_nodes: int = 0
    fanout: Tuple[int, ...] = ()
    nodes_per_graph: int = 0
    edges_per_graph: int = 0

    def __post_init__(self) -> None:
        if self.kind not in SHAPE_KINDS:
            raise ValueError(f"unknown shape kind {self.kind!r}")


# ---------------------------------------------------------------------------
# Model configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int                      # FFN hidden size per expert
    n_shared_experts: int = 0
    d_shared: int = 0                  # FFN hidden of the shared expert(s)
    first_k_dense: int = 0             # leading layers that stay dense
    d_ff_dense: int = 0                # FFN hidden for those dense layers
    capacity_factor: float = 1.25
    router_aux_loss: float = 0.001     # load-balance loss coefficient
    norm_topk_prob: bool = True        # renormalize top-k gate weights
    dispatch: str = "dense_scatter"    # "dense_scatter" | "ep_shard_map"


@dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    act: str = "silu"                  # "silu" (SwiGLU) | "gelu" (GeGLU)
    # gemma2-style extras
    sliding_window: int = 0            # >0: window size for local layers
    local_global_pattern: bool = False # alternate local/global attention
    attn_logit_softcap: float = 0.0    # >0: tanh softcap on attention logits
    final_logit_softcap: float = 0.0   # >0: tanh softcap on output logits
    post_norm: bool = False            # gemma2 post-block RMSNorm
    scale_embeddings: bool = False     # gemma2 sqrt(d_model) embed scaling
    query_pre_attn_scalar: float = 0.0 # gemma2 overrides 1/sqrt(d_head)
    # MoE
    moe: Optional[MoEConfig] = None
    # numerics / execution
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = True                 # activation checkpointing per block
    # scan over layers: keeps HLO size O(1) in depth — required for the
    # 48-layer full configs to compile quickly in the dry-run.
    scan_layers: bool = True

    @property
    def family(self) -> str:
        return "moe" if self.moe is not None else "dense"

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def n_params(self) -> int:
        """Approximate parameter count (embeddings included once if tied)."""
        d, L = self.d_model, self.n_layers
        attn = L * (self.n_heads * self.d_head * d * 2         # q, o
                    + self.n_kv_heads * self.d_head * d * 2)   # k, v
        if self.moe is None:
            ffn = L * 3 * d * self.d_ff
        else:
            m = self.moe
            dense_layers = m.first_k_dense
            moe_layers = L - dense_layers
            ffn = dense_layers * 3 * d * (m.d_ff_dense or self.d_ff)
            ffn += moe_layers * (m.n_experts * 3 * d * m.d_expert
                                 + m.n_shared_experts * 3 * d * (m.d_shared or m.d_expert)
                                 + d * m.n_experts)            # router
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        norms = L * 2 * d + d
        return attn + ffn + emb + norms

    def n_active_params(self) -> int:
        """Active (per-token) parameter count — MoE activates top_k experts."""
        if self.moe is None:
            return self.n_params()
        d, L, m = self.d_model, self.n_layers, self.moe
        attn = L * (self.n_heads * self.d_head * d * 2
                    + self.n_kv_heads * self.d_head * d * 2)
        dense_layers = m.first_k_dense
        moe_layers = L - dense_layers
        ffn = dense_layers * 3 * d * (m.d_ff_dense or self.d_ff)
        ffn += moe_layers * (m.top_k * 3 * d * m.d_expert
                             + m.n_shared_experts * 3 * d * (m.d_shared or m.d_expert)
                             + d * m.n_experts)
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return attn + ffn + emb + L * 2 * d + d


@dataclass(frozen=True)
class GNNConfig:
    name: str
    n_layers: int
    d_hidden: int
    d_feat: int
    n_classes: int
    aggregator: str = "mean"       # "mean" | "sum" | "max"
    norm: str = "sym"              # "sym" (D^-1/2 A D^-1/2) | "rw" | "none"
    dropout: float = 0.0
    dtype: str = "float32"
    param_dtype: str = "float32"

    @property
    def family(self) -> str:
        return "gnn"

    def n_params(self) -> int:
        p = self.d_feat * self.d_hidden + self.d_hidden
        for _ in range(self.n_layers - 2):
            p += self.d_hidden * self.d_hidden + self.d_hidden
        p += self.d_hidden * self.n_classes + self.n_classes
        return p


@dataclass(frozen=True)
class EmbeddingTableConfig:
    """One sparse embedding table (or a stack of same-shape tables)."""
    name: str
    vocab: int
    dim: int
    count: int = 1                 # number of identical tables stacked


@dataclass(frozen=True)
class RecsysConfig:
    name: str
    model: str                     # "dlrm" | "bst" | "two_tower" | "mind"
    embed_dim: int
    tables: Tuple[EmbeddingTableConfig, ...] = ()
    n_dense: int = 0
    bot_mlp: Tuple[int, ...] = ()
    top_mlp: Tuple[int, ...] = ()
    tower_mlp: Tuple[int, ...] = ()
    interaction: str = "dot"
    # BST
    seq_len: int = 0
    n_blocks: int = 0
    n_heads: int = 0
    mlp: Tuple[int, ...] = ()
    # MIND
    n_interests: int = 0
    capsule_iters: int = 0
    hist_len: int = 0
    item_vocab: int = 0
    user_vocab: int = 0
    dtype: str = "float32"
    param_dtype: str = "float32"

    @property
    def family(self) -> str:
        return "recsys"

    def n_params(self) -> int:
        p = sum(t.vocab * t.dim * t.count for t in self.tables)
        def mlp_params(dims: Tuple[int, ...], d_in: int) -> int:
            total, d = 0, d_in
            for h in dims:
                total += d * h + h
                d = h
            return total
        if self.model == "dlrm":
            p += mlp_params(self.bot_mlp[1:], self.bot_mlp[0])
            n_f = len(self.tables) + 1
            d_int = n_f * (n_f - 1) // 2 + self.bot_mlp[-1]
            p += mlp_params(self.top_mlp, d_int)
        elif self.model == "bst":
            d = self.embed_dim
            p += self.n_blocks * (4 * d * d + 8 * d * d)   # attn + ffn approx
            p += mlp_params(self.mlp + (1,), d * (self.seq_len + 1))
        elif self.model == "two_tower":
            p += 2 * mlp_params(self.tower_mlp + (self.embed_dim,), self.embed_dim)
        elif self.model == "mind":
            d = self.embed_dim
            p += d * d  # routing bilinear
            p += mlp_params((4 * d, d), d)
        return p


# The paper's own system config: the trust-IR serving pipeline.
@dataclass(frozen=True)
class TrustIRConfig:
    name: str = "trust_ir"
    # Load shedder parameters (paper §4)
    u_capacity: int = 2048              # URLs evaluable within base deadline
    u_threshold: int = 1024             # extra URLs within overload deadline
    deadline_s: float = 0.5             # optimum response time (base deadline)
    overload_deadline_s: float = 1.0    # optimum response time under overload
    very_heavy_weight: float = 0.5      # deadline-extension weight w (§4.3)
    chunk_size: int = 256               # microbatch granularity for deadline checks
    # Trust DB cache
    cache_slots: int = 65536
    cache_ways: int = 4
    # Cache array layout: True (default) stores keys/values/age as
    # (n_ways, n_slots) — each way one contiguous slot-indexed row, so
    # the probe's XLA gather reads one row per way. False is the legacy
    # (n_slots, n_ways) layout, kept for parity testing and old
    # snapshots; every cache op infers the layout from the shape.
    cache_ways_leading: bool = True
    # Average-trust prior
    prior_buckets: int = 1              # 1 = paper-faithful global average
    prior_ewma: float = 0.05
    # Quality subsystem weights (content, context, ratings)
    quality_weights: Tuple[float, float, float] = (0.5, 0.3, 0.2)
    # Evaluator backbone (arch id from the registry)
    evaluator_arch: str = "smollm-135m"
    trust_scale: float = 5.0            # paper reports trust on a scale of 5
    # Micro-batch drain executor:
    #   "host"  — LoadShedder.process: host-side chunk loop with a real
    #             (or simulated) wall-clock deadline; the paper-figure
    #             baseline (response-time benchmarks measure this path).
    #   "fused" — FusedLoadShedder: ONE jitted device step per
    #             micro-batch (Pallas shed_partition probe+tier with
    #             compacted eval indices, static-shape gather, batched
    #             evaluator forward, scatter, Trust-DB/prior fold-back);
    #             budget_dq derives from the same shed_plan math, so
    #             tiers match the host oracle. The serving hot path.
    drain_mode: str = "host"
    # Depth of the drain executor's in-flight window
    # (``scheduling.executor.DrainExecutor``): how many dispatched
    # micro-batches may be outstanding before the oldest is finalized.
    # Depth 1 reproduces the PR-3 behaviour bit-for-bit (one batch
    # overlapped inside a drain call, every ``drain`` call synced on
    # return); depth >= 2 keeps the window open ACROSS drain calls, so
    # a serving loop that drains one batch per iteration no longer
    # syncs per iteration — batch N+2 forms and transfers while N
    # computes and N+1 waits. Sequential executors (host drain_mode,
    # simulated clocks) ignore the depth: their timelines are
    # sequential by construction.
    pipeline_depth: int = 2
    # Adaptive pipeline depth (cluster.depth.DepthController): when
    # True the drain window depth is re-decided per drain tick inside
    # [adaptive_depth_min, pipeline_depth] — deepen when the backlog
    # could keep a deeper window full (throughput-bound), shallow when
    # the measured queue delay eats more than
    # adaptive_depth_latency_frac of the deadline (latency-bound).
    # The static pipeline_depth above remains the hard clamp. Flap
    # control: a move needs adaptive_depth_hysteresis CONSECUTIVE
    # same-direction votes and every applied move starts an
    # adaptive_depth_cooldown_ticks hold. False = the static-depth
    # behaviour, bit-for-bit.
    adaptive_depth: bool = False
    adaptive_depth_min: int = 1
    adaptive_depth_backlog_batches: float = 2.0
    adaptive_depth_latency_frac: float = 0.5
    adaptive_depth_hysteresis: int = 2
    adaptive_depth_cooldown_ticks: int = 2
    # Serving fleet (repro.cluster): number of independent replica
    # engines (each with its own shedder/cache/prior state). 1 = the
    # single-host degenerate case; weights bias the consistent-hash
    # ring's virtual-node counts (empty = equal weights).
    n_replicas: int = 1
    replica_weights: Tuple[float, ...] = ()
    # Elastic membership bounds: with max_replicas > 0 the cluster
    # autoscaler may join/gracefully-leave replicas at runtime between
    # [max(min_replicas, 1), max_replicas]; 0 = membership fixed at
    # n_replicas (the pre-elastic behaviour).
    min_replicas: int = 0
    max_replicas: int = 0
    # Cross-replica Trust-DB gossip: broadcast fresh cache fills to
    # sibling replicas (bounded per-round budget) so correlated hot-URL
    # floods are evaluated once fleet-wide.
    gossip: bool = False
    # Gossip delivery mode:
    #   "broadcast" — every kept delta reaches EVERY sibling in the
    #                 same round (O(n^2) messages/round; exact, the
    #                 pre-chaos behaviour and the default).
    #   "epidemic"  — each delta is pushed to ceil(log2 n) sampled
    #                 peers per round and the rest catch up through a
    #                 per-round anti-entropy pull (one sampled peer
    #                 each), bounding messages/round at O(n log n) so
    #                 48+ replica fleets do not hit the broadcast wall.
    gossip_mode: str = "broadcast"
    # Poison-pill quarantine (repro.scheduling.quarantine): a circuit
    # breaker in front of the evaluator. After quarantine_k executor
    # errors sharing one work signature (a hash of the candidate-set
    # prefix — a query-of-death retrieves the same candidates every
    # time), matching requests are prior-answered instead of
    # re-poisoning the DrainExecutor window; after
    # quarantine_probe_after_s one half-open probe re-tests the
    # signature (success closes the breaker, failure re-opens it).
    # 0 = disabled (the pre-chaos behaviour).
    quarantine_k: int = 0
    quarantine_probe_after_s: float = 2.0
    # WatermarkAutoscaler hysteresis (cluster.autoscale_watermarks).
    # Documented defaults, previously hard-coded in the autoscaler:
    #   up_pressure 0.75   — fleet queue-fill above which the
    #                        membership vote is scale-UP,
    #   down_pressure 0.15 — projected post-shrink fill below which
    #                        the vote is scale-DOWN (the dead band is
    #                        everything in between),
    #   cooldown_ticks 2   — autoscaler updates to wait after any
    #                        membership change before voting again.
    # Tight hysteresis (small dead band / cooldown) tracks flash
    # crowds faster at the cost of membership churn; loose values lag
    # the spike but keep the fleet steady.
    autoscale_up_pressure: float = 0.75
    autoscale_down_pressure: float = 0.15
    autoscale_cooldown_ticks: int = 2
    # Feedforward capacity planning (repro.cluster.capacity): when
    # enabled, the coordinator fits a ServiceTimeModel from drain
    # measurements, extrapolates the arrival curve over a sliding
    # window (NHPP rate estimate), and feeds the predicted utilization
    # into the autoscaler's membership vote — so a join triggers
    # warmup_lead_s BEFORE the predicted watermark breach and the new
    # replica is jit-prewarmed at production shapes before the ring
    # routes traffic to it. Purely additive: forecast=False keeps the
    # PR-5 reactive-only behaviour bit-for-bit.
    forecast: bool = False
    warmup_lead_s: float = 0.5          # provision lead (jit prewarm time)
    forecast_window_s: float = 2.0      # sliding NHPP estimation window
    # Retrieval front end (repro.retrieval): the sharded inverted-index
    # stage ahead of the trust pipeline. The synthetic corpus is fully
    # determined by (corpus_docs, corpus_vocab, corpus_zipf_a,
    # corpus_seed) — same numbers, bit-identical corpus and postings
    # anywhere.
    corpus_docs: int = 4096             # synthetic corpus size
    corpus_vocab: int = 2048            # Zipf-ranked content vocabulary
    corpus_zipf_a: float = 1.15         # term-frequency skew (rank 1 =
                                        # the paper's "book" hot keyword)
    corpus_seed: int = 0
    # Blocked index construction: documents per build block. Postings
    # are block-size invariant (sequential merge), so this knob trades
    # peak build memory only — never retrieval results.
    index_block_docs: int = 512
    # Doc-partition count for the consistent-hash ring ("docpart:p"
    # keys). More partitions = finer-grained rebalancing on membership
    # change; each replica's shard is the merge of the stripes it owns.
    index_partitions: int = 16
    # Candidate-set size a raw query string retrieves (BM25 top-k)
    # before the shed ladder sees it. Quantized up to a power of two on
    # the device path, so the jit cache stays O(log k).
    retrieve_top_k: int = 64
    # Tail-tolerant scatter-gather (repro.fanout): the gather answers
    # at the first-quorum_k-of-n shard completions instead of waiting
    # for the slowest shard. 0 = synchronous full gather (pre-fanout
    # behaviour); quorum_k >= n is bit-identical to it. Late shards
    # are prior-answered (stripe answer cache / trust prior) — the
    # no-drop invariant is unchanged.
    fanout_quorum_k: int = 0
    # Adaptive quorum (regime ladder): when True the coordinator walks
    # quorum_k one step per drain round — toward n (the bit-exact full
    # gather) while the fleet's worst offered regime is Normal, back
    # toward the configured fanout_quorum_k floor under Very-Heavy.
    # Inert while fanout_quorum_k is 0 (quorum off).
    fanout_adaptive_quorum: bool = False
    # Per-shard probe hedging: a stripe probe slower than this races a
    # twin on a sibling's mirror (first completion wins, loser
    # deduplicated), charged to the SAME HedgedDispatch token bucket
    # as whole-request hedges. 0 disables.
    fanout_hedge_after_s: float = 0.0
    # Selective stripe replication: a shard whose service-time EWMA
    # exceeds slow_factor x the fleet median is mirrored onto a ring
    # sibling (at most max_mirrors concurrent mirrors); the mirror
    # drops once the EWMA recovers below recover_factor x median.
    fanout_slow_factor: float = 2.5
    fanout_recover_factor: float = 1.4
    fanout_max_mirrors: int = 2


# ---------------------------------------------------------------------------
# Arch bundle: what the registry returns
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ArchBundle:
    arch_id: str
    config: Any                         # TransformerConfig | GNNConfig | RecsysConfig
    smoke: Any                          # reduced same-family config
    shapes: Tuple[ShapeSpec, ...]
    source: str = ""                    # provenance note


def reduced(cfg, **overrides):
    """Return a copy of a frozen dataclass config with overrides applied."""
    return dataclasses.replace(cfg, **overrides)


# LM shape set shared by the five LM-family archs (per assignment).
LM_SHAPES: Tuple[ShapeSpec, ...] = (
    ShapeSpec(name="train_4k", kind="train", seq_len=4096, global_batch=256),
    ShapeSpec(name="prefill_32k", kind="prefill", seq_len=32768, global_batch=32),
    ShapeSpec(name="decode_32k", kind="decode", seq_len=32768, global_batch=128),
    ShapeSpec(name="long_500k", kind="decode", seq_len=524288, global_batch=1),
)

RECSYS_SHAPES: Tuple[ShapeSpec, ...] = (
    ShapeSpec(name="train_batch", kind="train", batch=65536),
    ShapeSpec(name="serve_p99", kind="serve", batch=512),
    ShapeSpec(name="serve_bulk", kind="serve", batch=262144),
    ShapeSpec(name="retrieval_cand", kind="retrieval", batch=1, n_candidates=1_000_000),
)

GNN_SHAPES: Tuple[ShapeSpec, ...] = (
    ShapeSpec(name="full_graph_sm", kind="graph_full",
              n_nodes=2708, n_edges=10556, d_feat=1433),
    ShapeSpec(name="minibatch_lg", kind="graph_minibatch",
              n_nodes=232_965, n_edges=114_615_892, batch_nodes=1024,
              fanout=(15, 10), d_feat=602),
    ShapeSpec(name="ogb_products", kind="graph_full",
              n_nodes=2_449_029, n_edges=61_859_140, d_feat=100),
    ShapeSpec(name="molecule", kind="graph_batched",
              n_nodes=30, n_edges=64, batch=128, d_feat=32,
              nodes_per_graph=30, edges_per_graph=64),
)
