"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``mixes/<traffic>.json``) fixes the shape of the traffic: set
sizes, URL popularity, priority and tenant mix, SLO. A cell file
(``cells/<workload>.json``) fixes its offered rate. The schedule of a
run is built so that every seed offers the same work:

* the arrival gaps, set sizes, priorities and tenants of a run are one
  fixed draw (``schedule_seed`` of the mix), and ``--seed`` only
  permutes them, so two seeds differ in order and not in load;
* the URLs of a request are drawn from ``--seed`` and the request's
  index, when the request is due, so a pool is never held whole;
* a URL's evaluator features and bucket are a hash of its id
  (``families/*.features``), so a URL always scores the same;
* the Trust DB starts as a long-running deployment's would hold it
  (``trust_db_fill``), not empty.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

# Priority classes in the program's order (CRITICAL, HIGH, NORMAL, LOW).
N_PRIORITIES = 4


def seed_words(seed: int, *extra: int) -> np.random.SeedSequence:
    """A SeedSequence for any non-negative seed (more than 32 bits)."""
    if seed < 0:
        raise ValueError(f"--seed must be non-negative, got {seed}")
    return np.random.SeedSequence([seed & 0xFFFFFFFF, seed >> 32, *extra])


def zipf_ranks(u: np.ndarray, a: float, n: int) -> np.ndarray:
    """Ranks 1..n of a Zipf(a) law over a finite universe, by the
    continuous inverse CDF of ``u`` in [0, 1)."""
    if a == 1.0:
        r = np.exp(u * np.log(n + 1.0))
    else:
        e = 1.0 - a
        r = (((n + 1.0) ** e - 1.0) * u + 1.0) ** (1.0 / e)
    return np.clip(np.floor(r), 1, n).astype(np.int64)


def url_of_rank(rank: np.ndarray, log2_universe: int) -> np.ndarray:
    """A bijection of popularity ranks 1..2**k onto URL ids 1..2**k, so
    hot URLs are not small integers. Id 0 is the Trust DB's empty key."""
    mask = (1 << log2_universe) - 1
    return (((rank - 1) * 0x9E3779B1) & mask).astype(np.uint32) + 1


@dataclass
class Request:
    index: int
    due_s: float                 # offset from the start of its phase
    size: int
    priority: int
    tenant: str


class Schedule:
    """The arrivals of one phase (warm-up or window) of a run."""

    def __init__(self, mix: Dict, rate_qps: float, seconds: float,
                 seed: int, phase: int):
        n = max(int(round(rate_qps * seconds)), 1)
        fixed = np.random.default_rng(
            np.random.SeedSequence([mix["schedule_seed"], phase]))
        gaps = fixed.exponential(size=n + 1)
        sz = mix["size"]
        sizes = np.clip(fixed.zipf(sz["zipf_a"], size=n) * sz["scale"],
                        sz["min"], sz["max"]).astype(np.int64)
        mix_p = np.asarray(mix["priority_mix"], np.float64)
        counts = np.floor(mix_p * n).astype(int)
        counts[np.argmax(mix_p)] += n - counts.sum()
        prios = np.repeat(np.arange(N_PRIORITIES), counts)
        tn = mix["tenants"]
        tenants = zipf_ranks(fixed.random(n), tn["zipf_a"], tn["n"])

        perm = np.random.default_rng(seed_words(seed, phase, 1))
        gaps = perm.permutation(gaps)
        gaps *= seconds / gaps.sum()
        due = np.cumsum(gaps)[:n]
        self.requests: List[Request] = [
            Request(i, float(due[i]), int(s), int(p), f"tenant{int(t)}")
            for i, (s, p, t) in enumerate(zip(perm.permutation(sizes),
                                              perm.permutation(prios),
                                              perm.permutation(tenants)))]
        self.seed = seed
        self.phase = phase
        self.mix = mix

    def __len__(self) -> int:
        return len(self.requests)

    def urls(self, req: Request) -> np.ndarray:
        """The candidate URL ids of ``req``, drawn when it is due."""
        u = self.mix["urls"]
        rng = np.random.default_rng(
            seed_words(self.seed, self.phase, 2, req.index))
        ranks = zipf_ranks(rng.random(req.size), u["zipf_a"],
                           1 << u["universe_log2"])
        return url_of_rank(ranks, u["universe_log2"])


@dataclass
class TrustDBFill:
    """The Trust DB of a deployment that has served the mix for a long
    time, laid out as the program holds it: ``(ways, sets)`` arrays."""
    keys: np.ndarray             # uint32, 0 where a way is empty
    values: np.ndarray           # float32 trust
    age: np.ndarray              # int32, every entry older than the run


def trust_db_fill(mix: Dict, n_sets: int, n_ways: int, trust_scale: float,
                  seed: int) -> TrustDBFill:
    """Each set holds the most popular of the URLs that hash to it, as
    many as it has ways, from the ``top_ranks_per_entry`` x capacity most
    popular URLs of the mix; within a set the more popular is the
    younger, and all are older than any write of the run. A URL's trust
    is a hash of the seed and its id, in [0, trust_scale)."""
    from benchmarks.chip import hashing
    from benchmarks.chip.refs.trust_tier import slot_of

    u = mix["urls"]
    n = min(int(mix["trust_db_fill"]["top_ranks_per_entry"] * n_sets
                * n_ways), 1 << u["universe_log2"])
    rank = np.arange(1, n + 1, dtype=np.int64)
    url = url_of_rank(rank, u["universe_log2"])
    # One sort of (set, rank) packed in a word: by set, then by rank.
    shift = np.uint64(int(n).bit_length())
    packed = np.sort((slot_of(url, n_sets).astype(np.uint64) << shift)
                     | (rank - 1).astype(np.uint64))
    s_all = (packed >> shift).astype(np.int64)
    first = np.r_[True, s_all[1:] != s_all[:-1]]
    way = np.arange(n) - np.maximum.accumulate(
        np.where(first, np.arange(n), 0))
    kept = way < n_ways
    w, s = way[kept], s_all[kept]
    keep = (packed[kept] & ((np.uint64(1) << shift) - np.uint64(1))) \
        .astype(np.int64)                           # rank - 1 of each entry
    keys = np.zeros((n_ways, n_sets), np.uint32)
    values = np.zeros((n_ways, n_sets), np.float32)
    age = np.zeros((n_ways, n_sets), np.int32)
    salt = np.uint64(int(seed_words(seed, 11).generate_state(1)[0]))
    h = hashing.mix64(url[keep].astype(np.uint64) ^ salt)
    keys[w, s] = url[keep]
    values[w, s] = ((h >> np.uint64(40)).astype(np.float32)
                    * np.float32(trust_scale / (1 << 24)))
    age[w, s] = -w
    return TrustDBFill(keys, values, age)
