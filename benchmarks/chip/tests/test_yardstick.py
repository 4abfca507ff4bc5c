"""The yardstick's arithmetic: traffic from the seed, operations and bytes
from shapes, the table of peaks."""
import json

import numpy as np
import pytest

from benchmarks.chip import kernels, peaks
from benchmarks.chip.families import dlrm, llama_scorer
from benchmarks.chip.spec import BENCH_DIR
from benchmarks.chip.traffic import Schedule, url_of_rank, zipf_ranks


def config(name: str) -> dict:
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def mix(name: str) -> dict:
    return json.loads((BENCH_DIR / "mixes" / f"{name}.json").read_text())


def test_same_seed_same_requests_and_every_seed_the_same_work():
    m = mix("cand-tail")
    big = 2**33 + 5
    a, b = Schedule(m, 6.0, 30.0, big, 1), Schedule(m, 6.0, 30.0, big, 1)
    c = Schedule(m, 6.0, 30.0, 17, 1)
    assert [r.due_s for r in a.requests] == [r.due_s for r in b.requests]
    assert all((a.urls(x) == b.urls(y)).all()
               for x, y in zip(a.requests, b.requests))
    # another seed offers the same sizes, priorities and arrival gaps in
    # another order, over the same length of time
    assert sorted(r.size for r in a.requests) == \
        sorted(r.size for r in c.requests)
    assert sorted(r.priority for r in a.requests) == \
        sorted(r.priority for r in c.requests)
    assert [r.size for r in a.requests] != [r.size for r in c.requests]
    assert len(a) == len(c) == 180
    assert all(0 < r.due_s < 30.0 for r in a.requests + c.requests)


def test_set_sizes_follow_the_launcher_law():
    s = Schedule(mix("cand-tail"), 100.0, 100.0, 1, 1)
    sizes = np.array([r.size for r in s.requests])
    assert sizes.min() >= 64 and sizes.max() == 4096
    assert 0.12 < np.mean(sizes == 4096) < 0.18
    assert 850 < sizes.mean() < 1200


def test_urls_are_nonzero_and_inside_the_universe():
    ranks = zipf_ranks(np.linspace(0, 0.999999, 10_000), 0.9, 1 << 26)
    urls = url_of_rank(ranks, 26)
    assert urls.min() >= 1 and urls.max() <= 1 << 26
    # the bijection keeps distinct ranks distinct
    assert len(np.unique(urls)) == len(np.unique(ranks))


def test_smollm_operations_per_candidate():
    c = config("smollm-135m")
    s, d, f, v = 31, 576, 1536, 49152
    per_layer = d * 576 * 2 + d * 192 * 2 + 3 * d * f
    macs = s * 30 * per_layer + 30 * 9 * (31 * 32 // 2) * 64 * 2 + s * d * v
    assert llama_scorer.flops_per_item(c) == 2.0 * macs
    assert 8.0e9 < llama_scorer.flops_per_item(c) < 8.7e9


def test_dlrm_operations_per_candidate():
    c = config("dlrm-mlperf")
    bottom = 13 * 512 + 512 * 256 + 256 * 128
    pairs = 27 * 26 // 2
    top = (pairs + 128) * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256
    assert dlrm.flops_per_item(c) == 2.0 * (bottom + pairs * 128 + top)


def test_dlrm_rows_are_one_chips_share_of_sixteen():
    c = config("dlrm-mlperf")
    pub, held = (c["num_embeddings_per_feature_published"],
                 c["num_embeddings_per_feature"])
    for p, h in zip(pub, held):
        assert h == (-(-p // 16) if p > 1_000_000 else p)


@pytest.mark.parametrize("n", [704, 4224])
def test_kernel_costs_grow_with_the_items(n):
    ops, nbytes = kernels.shed_partition_cost(n, 4)
    assert (ops, nbytes) == (n * 14.0, n * 49.0)
    ops, nbytes = kernels.topk_select_cost(n, 64)
    assert (ops, nbytes) == (2.0 * n, 4.0 * n + 512)


def test_peaks_are_published_and_unknown_devices_are_refused():
    assert peaks.peaks_of("TPU v5 lite")["flops"] == 197e12
    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")
