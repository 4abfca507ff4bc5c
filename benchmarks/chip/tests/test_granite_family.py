"""The granite-4.0-h family on the CPU: its evaluator against the plain
reference, the control against the limit, a sound run of the serving
path, the per-layer readers of its counters, and the operation counts
against hand counts.

Sizes are the test configuration's (``tiny-granite.json``: 4 layers of a
hybrid pattern, 4 of 8 experts held, float32). The published widths'
limit is set from chip readings (PERF.md section 2): at a size the CPU
can hold, its control's gap stays under it.
"""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import bench, moe_kernels
from benchmarks.chip.families import granite_hybrid as fam
from benchmarks.chip.refs import granite_hybrid as ref
from benchmarks.chip.spec import Cell
from benchmarks.chip.traffic import url_of_rank

HERE = Path(__file__).resolve().parent


def tiny() -> dict:
    return json.loads((HERE / "tiny-granite.json").read_text())


def tiny_cell(rate_qps: float = 20.0) -> Cell:
    mix = json.loads((HERE / "tiny-mix.json").read_text())
    e2e = [{"name": n, "unit": "x"} for n in ("trusted_share", "setup_s")]
    return Cell(name="tiny-granite", chips=1, config=tiny(), mix=mix,
                rate_qps=rate_qps, end_to_end=e2e, per_layer=[])


def test_family_matches_reference():
    """float32 program against the float32 reference: the chunked SSD
    form against the token-by-token recurrence, the sorted grouped
    matmul against every expert masked. Both compute in float32 on the
    CPU, so 1e-5 leaves room for summation order only."""
    cfg = tiny()
    w = fam.make_weights(cfg, jax.random.PRNGKey(3))
    feats = fam.features(cfg, url_of_rank(np.arange(1, 41), 26))
    prog = np.asarray(fam.make_evaluator(cfg, w)(
        jax.tree.map(jnp.asarray, feats)))
    want = ref.trust(cfg, w, feats, block=16)
    # The trust spreads across documents far beyond the tolerance.
    assert np.std(want) > 100 * 1e-5
    np.testing.assert_allclose(prog, want, rtol=0, atol=1e-5)


def test_control_in_the_programs_place_is_not_correct(monkeypatch):
    """A run of the serving path with the reference's control (every
    matrix product's operands rounded to float8) as the evaluator reads
    a ``trust_gap`` over the limit, and the same run with the program's
    evaluator reads one under it. The tiny configuration serves in
    float32, so its limit (1e-3) sits between the program's summation
    order and float8."""
    from repro.serving.evaluators import Evaluator
    kinds, dims = ref.static_args(tiny())

    def control(cfg, params):
        return Evaluator(lambda p, chunk: ref._block_trust(
            p, chunk["tokens"], kinds=kinds, dims=dims, mode="control"),
            params)

    sound = bench.run_cell(tiny_cell(), 2**31 + 5, 2.0, False,
                           time.monotonic())
    assert sound.correct, sound.checks
    monkeypatch.setattr(fam, "make_evaluator", control)
    res = bench.run_cell(tiny_cell(), 2**31 + 5, 2.0, False,
                         time.monotonic())
    assert not res.correct
    c = res.checks["trust_gap"]
    assert c["value"] > c["limit"], res.checks
    assert res.failed > 0


def test_sound_run_is_correct_and_counts_expert_rows(monkeypatch):
    """The serving path end to end on the CPU, traced: the comparison
    passes, the expert counters reach the scheduler's stats, and the
    reader of their share reads them. The CPU is given made-up peaks so
    that the readers run; none of these numbers is a device measurement.
    The grouped matmul is no custom call on the CPU, so the roofline
    reader finds nothing to read."""
    from benchmarks.chip import peaks
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    c = tiny_cell()
    c.per_layer = [{"name": n, "unit": "%"} for n in
                   ("mfu.granite", "expert_row_use.granite",
                    "expert_ffn_roofline.granite")]
    res = bench.run_cell(c, 2**31 + 11, 2.0, True, time.monotonic())
    assert res.correct, res.checks
    assert res.failed == 0 and res.attempted > 0
    use = res.metrics["expert_row_use.granite"]["value"]
    # 3 of 8 experts per token, 4 held, rows for min(3, 4) per token:
    # about 3 * 4 / 8 / 3 = 50% of the rows hold a routed pair.
    assert 20.0 < use < 80.0
    assert "expert_ffn_roofline.granite" not in res.metrics


def test_flops_per_item_matches_hand_count():
    """tiny-granite: doc_len 12, so 11 scored positions and 66 causal
    pairs; layers 1-4 are mamba, attention, mamba, mamba."""
    s, pairs = 11, 66
    mamba = s * (64 * 296 + 128 * 64 + 4 * 160) + pairs * (16 + 8 * 16)
    attn = s * (64 * 64 * 2 + 64 * 32 * 2) + 4 * pairs * 16 * 2
    router = 4 * s * 64 * 8
    shared = 4 * s * 3 * 64 * 48
    experts = 4 * s * (3 * 4 / 8) * 3 * 64 * 32
    head = s * 64 * 512
    want = 2 * (3 * mamba + attn + router + shared + experts + head)
    assert fam.flops_per_item(tiny()) == want


def test_expert_ffn_cost_matches_hand_count():
    """Gate-and-up projection of 100 routed rows over 18 held experts at
    granite's widths, in bfloat16."""
    ops, nbytes = moe_kernels.expert_ffn_cost(100, 18, 4096, 1536, 2)
    assert ops == 2 * 100 * 4096 * 1536
    assert nbytes == 2 * (18 * 4096 * 1536 + 100 * (4096 + 1536))
