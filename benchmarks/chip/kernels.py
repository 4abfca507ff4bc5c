"""Operations and bytes each kernel's algorithm must do, from its shapes.

Counted from what the algorithm has to read and write, not from how
today's kernel does it, so the count stays true whatever implements it.
"""
from __future__ import annotations


def shed_partition_cost(n_items: int, n_ways: int) -> tuple:
    """One probe-and-tier call over ``n_items`` keys: read each key and
    its valid flag and the ``n_ways`` candidate (key, value) pairs of
    its Trust DB set, write tier, cached value and eval rank. Per item:
    one compare per way, a select of the value, and the two running
    counts of the arrival-ordered scan."""
    bytes_ = n_items * (4 + 1 + n_ways * 8 + 3 * 4)
    ops = n_items * (2 * n_ways + 6)
    return float(ops), float(bytes_)


def topk_select_cost(n_scores: int, k: int) -> tuple:
    """Top-k of a dense score vector: read every score once, compare it
    against the running k-th value, write k (value, index) pairs."""
    return float(2 * n_scores), float(4 * n_scores + 8 * k)
