"""The comparison that decides ``correct``: a sound run passes it, and a
run with the timed path broken underneath, or the control in the
program's place, fails it.

The runs skip the harness's look for a chip and drive everything else
of a run (``bench.run_cell``) on the CPU, at test sizes: two-layer
models and small sets over a small URL universe, so URLs repeat and the
Trust DB answers. The control test runs the evaluators at their
published widths on a few candidates.
"""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import bench
from benchmarks.chip.spec import BENCH_DIR, Cell
from repro.core.fused_shedder import FusedLoadShedder
from repro.scheduling.scheduler import Scheduler

HERE = Path(__file__).resolve().parent


def cell(config: str, rate_qps: float = 20.0) -> Cell:
    cfg = json.loads((HERE / f"{config}.json").read_text())
    mix = json.loads((HERE / "tiny-mix.json").read_text())
    e2e = [{"name": n, "unit": "x"} for n in
           ("qps", "p95_ms", "p50_ms", "trusted_share", "setup_s")]
    return Cell(name=config, chips=1, config=cfg, mix=mix, rate_qps=rate_qps,
                end_to_end=e2e, per_layer=[])


def run(config: str, seed: int = 2**31 + 7, rate_qps: float = 20.0):
    return bench.run_cell(cell(config, rate_qps), seed, 2.0, False,
                          time.monotonic())


ORIGINAL = FusedLoadShedder._step_impl


def state_unchanged(self, cache, prior, *args, **kw):
    trust, tier, n, _, new_prior = ORIGINAL(self, cache, prior, *args, **kw)
    return trust, tier, n, cache, new_prior


def half_batch(self, *args, **kw):
    """The second half of each batch's evaluated candidates is left out
    and answered with the mean of the first half."""
    trust, tier, n, cache, prior = ORIGINAL(self, *args, **kw)
    ev = tier == 0
    rank = jnp.cumsum(ev) - 1
    kept = ev & (rank < jnp.sum(ev) // 2)
    mean = jnp.sum(jnp.where(kept, trust, 0.0)) / jnp.maximum(jnp.sum(kept), 1)
    return jnp.where(ev & ~kept, mean, trust), tier, n, cache, prior


def answer_altered(self, *args, **kw):
    trust, tier, n, cache, prior = ORIGINAL(self, *args, **kw)
    first_eval = jnp.argmax(tier == 0)
    return trust.at[first_eval].add(0.25), tier, n, cache, prior


@pytest.mark.parametrize("config", ["tiny-llama", "tiny-dlrm"])
def test_sound_run_is_correct(config):
    res = run(config)
    assert res.correct, res.checks
    assert res.failed == 0 and res.attempted > 0
    assert res.notes["share_cached"] > 0       # the Trust DB answered


SPLIT = Scheduler._split_responses


def answers_swapped(self, batch, shed):
    """The step is sound, but the first two requests of a batch get each
    other's answers (as far as the shorter one reaches). The test offers
    enough load for batches of several requests."""
    out = SPLIT(self, batch, shed)
    if len(out) >= 2:
        a, b = out[0], out[1]
        n = min(len(a.trust), len(b.trust))
        for f in ("trust", "tier"):
            x, y = getattr(a, f).copy(), getattr(b, f).copy()
            x[:n], y[:n] = getattr(b, f)[:n], getattr(a, f)[:n]
            setattr(a, f, x)
            setattr(b, f, y)
    return out


@pytest.mark.parametrize("fault,reading", [
    (state_unchanged, "missed_hits"),
    (half_batch, "trust_gap"),
    (answer_altered, "trust_gap"),
    (answers_swapped, "served_mismatch"),
])
@pytest.mark.parametrize("config", ["tiny-llama", "tiny-dlrm"])
def test_broken_step_is_not_correct(monkeypatch, config, fault, reading):
    if fault is answers_swapped:
        monkeypatch.setattr(Scheduler, "_split_responses", fault)
        res = run(config, rate_qps=200.0)
    else:
        monkeypatch.setattr(FusedLoadShedder, "_step_impl", fault)
        res = run(config)
    assert not res.correct
    c = res.checks[reading]
    assert c["value"] > c["limit"], res.checks
    assert res.failed > 0


def published(config: str, tables: int = 0) -> dict:
    cfg = json.loads((BENCH_DIR / "configs" / f"{config}.json").read_text())
    if tables:       # published widths, fewer rows, so the test holds it
        cfg["num_embeddings_per_feature"] = [
            min(v, tables) for v in cfg["num_embeddings_per_feature"]]
    return cfg


@pytest.mark.parametrize("config,tables,n", [("smollm-135m", 0, 8),
                                             ("dlrm-mlperf", 4096, 512)])
def test_control_fails_the_limit(config, tables, n):
    """The reference one precision step below the configuration's, put
    in the program's place, reads a gap over the configuration's limit,
    and the program's own evaluator reads one under it."""
    from benchmarks.chip.traffic import url_of_rank
    c = Cell(name=config, chips=1, config=published(config, tables),
             mix={}, rate_qps=1.0, end_to_end=[], per_layer=[])
    cfg = c.config
    weights = c.family.make_weights(cfg, jax.random.PRNGKey(3))
    feats = c.family.features(cfg, url_of_rank(np.arange(1, n + 1), 26))
    want = c.reference.trust(cfg, weights, feats, mode="f32")
    ctrl = c.reference.trust(cfg, weights, feats, mode="control")
    prog = np.asarray(c.family.make_evaluator(cfg, weights)(
        jax.tree.map(jnp.asarray, feats)))
    limit = cfg["limits"]["trust_gap"]
    assert np.max(np.abs(ctrl - want)) > limit
    assert np.max(np.abs(prog - want)) <= limit


def test_traced_run_reads_its_per_layer_metrics(monkeypatch):
    """The traced path end to end on the CPU: profiler, trace reduction
    and every reader. The CPU is given made-up peaks so that the readers
    run; none of these numbers is a device measurement."""
    from benchmarks.chip import peaks
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    c = cell("tiny-dlrm")
    c.per_layer = [{"name": n, "unit": "%"} for n in
                   ("idle_share.steady", "mfu.steady",
                    "shed_partition_roofline.steady", "batch_items.steady")]
    res = bench.run_cell(c, 5, 2.0, True, time.monotonic())
    assert res.correct, res.checks
    assert {"mfu.steady", "batch_items.steady"} <= set(res.metrics)
    assert res.device["window_s"] > 0
    assert set(res.breakdown) == {"device_ops", "idle_gaps"}
