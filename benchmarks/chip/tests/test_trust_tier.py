"""The trust-tier reference's replay (``refs/trust_tier.py``) counts what a
replay item by item, written straight from its definitions, counts."""
import numpy as np
import pytest

from benchmarks.chip.refs.trust_tier import (TIER_CACHED, TIER_EVAL,
                                             TIER_PRIOR, TrustDBReplay,
                                             planned_tiers, slot_of)


def per_item(batches, n_slots, fill):
    """Counts and flagged keys, one item at a time: a URL must hit when
    it was evaluated before and every write to its set since that batch
    was its own, or when it was filled and its set was never written.
    ``fill`` maps each filled URL to its filled trust."""
    last, writes = {}, {}
    counts = {"tier_mismatch": 0, "readback_mismatch": 0, "missed_hits": 0}
    flagged = set()
    for b, (keys, n_valid, tier, trust, ucap, budget, max_evals) in \
            enumerate(batches):
        bad = planned_tiers(len(keys), n_valid, tier == TIER_CACHED, ucap,
                            budget, max_evals) != tier
        counts["tier_mismatch"] += int(bad.sum())
        slots = slot_of(keys, n_slots)
        for i in range(n_valid):
            key, slot = int(keys[i]), int(slots[i])
            if tier[i] == TIER_CACHED:
                if key in last:
                    ok = float(trust[i]) in last[key][1]
                else:
                    ok = key in fill and float(trust[i]) == fill[key]
                if not ok:
                    counts["readback_mismatch"] += 1
                    bad[i] = True
            elif tier[i] in (TIER_EVAL, TIER_PRIOR) and (
                    (key in last and all(
                        k == key for wb, k in writes.get(slot, [])
                        if wb >= last[key][0]))
                    or (key not in last and key in fill
                        and slot not in writes)):
                counts["missed_hits"] += 1
                bad[i] = True
        flagged.update(int(k) for k in keys[bad])
        for i in range(n_valid):
            if tier[i] == TIER_EVAL:
                key = int(keys[i])
                if key in last and last[key][0] == b:
                    last[key][1].add(float(trust[i]))
                else:
                    last[key] = (b, {float(trust[i])})
                writes.setdefault(int(slots[i]), []).append((b, key))
    return counts, flagged


def random_batches(seed, n_batches=60, n_slots=16, universe=40):
    """Batches over a few sets and URLs, so sets are shared and URLs
    repeat; tiers and trust are drawn at random, so every kind of
    mismatch occurs."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        n = int(rng.integers(1, 24))
        n_valid = int(rng.integers(0, n + 1))
        keys = rng.integers(1, universe, n).astype(np.uint32)
        tier = rng.choice([TIER_EVAL, TIER_CACHED, TIER_PRIOR], n)
        tier[n_valid:] = 3
        trust = rng.choice([0.5, 1.0, 1.5], n).astype(np.float32)
        out.append((keys, n_valid, tier, trust, int(rng.integers(0, n + 1)),
                    int(rng.integers(0, n + 1)), int(rng.integers(0, n + 1))))
    return out


@pytest.mark.parametrize("n_filled", [0, 12])
@pytest.mark.parametrize("seed", range(6))
def test_replay_counts_what_a_per_item_replay_counts(seed, n_filled):
    n_slots = 16
    batches = random_batches(seed, n_slots=n_slots)
    rng = np.random.default_rng(100 + seed)
    fill_keys = rng.choice(np.arange(1, 40, dtype=np.uint32), n_filled,
                           replace=False)
    fill_values = rng.choice([0.5, 1.0, 1.5], n_filled).astype(np.float32)
    rep = TrustDBReplay(n_slots, np.append(fill_keys, np.uint32(0)),
                        np.append(fill_values, np.float32(0)))
    for args in batches:
        rep.batch(*args)
    counts, flagged = per_item(batches, n_slots, dict(zip(
        fill_keys.tolist(), fill_values.tolist())))
    assert rep.counts == counts
    assert rep.flagged_keys == flagged
    assert counts["missed_hits"] > 0 and counts["readback_mismatch"] > 0
