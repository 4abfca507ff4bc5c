"""The reader of the serving thread's transfer time per batch."""
from types import SimpleNamespace

import pytest

from benchmarks.chip.metrics import xfer_ms

STATS = ({"n_batches": 10, "xfer_s": 0.02},
         {"n_batches": 30, "xfer_s": 0.07})


def test_reads_the_window_difference_per_batch():
    ctx = SimpleNamespace(stats=STATS)
    assert xfer_ms.read(ctx) == pytest.approx(1000 * 0.05 / 20)


@pytest.mark.parametrize("stats", [
    ({"n_batches": 10}, {"n_batches": 30}),      # a program without it
    (STATS[0], STATS[0]),                        # no batch in the window
])
def test_silent_without_the_counter_or_a_batch(stats):
    assert xfer_ms.read(SimpleNamespace(stats=stats)) is None
