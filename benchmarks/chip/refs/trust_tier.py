"""Plain reference of the trust tier: tiers, Trust DB read-back, hits.

Written from the paper's load shedder (Uload items in arrival order;
the first Ucapacity are the normal queue and are evaluated on a miss;
the drop queue's misses are evaluated in order while the total budget
lasts, the rest get the average-trust prior) and from the Trust DB's
stated semantics (a set-associative cache: a URL lives in the set its
32-bit hash selects, ways are replaced oldest first, key 0 is empty).
It imports nothing of the program and sees only what each batch was
given and what it answered.

Three readings per run, each a count that must be 0:

* ``tier_mismatch``: an item whose tier differs from the plan, given
  which items the Trust DB answered;
* ``readback_mismatch``: an item answered from the Trust DB whose trust
  is not the value the program served when it last evaluated that URL,
  or, for a URL never evaluated in the run, the trust the Trust DB was
  filled with before it (or that URL was never there);
* ``missed_hits``: an item whose URL was evaluated in an earlier batch,
  whose set nobody else has written since, and which the Trust DB did
  not answer; or a URL the Trust DB was filled with, whose set nobody
  has written in the run, and which it did not answer.
"""
from __future__ import annotations

from typing import Dict, Set

import numpy as np

TIER_EVAL, TIER_CACHED, TIER_PRIOR, TIER_INVALID = 0, 1, 2, 3


def slot_of(keys: np.ndarray, n_slots: int) -> np.ndarray:
    """The Trust DB set of each URL id (splitmix32 avalanche, modulo)."""
    x = np.asarray(keys, np.uint32)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
        x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
        x = x ^ (x >> np.uint32(16))
    return (x % np.uint32(n_slots)).astype(np.int64)


def planned_tiers(n_total: int, n_valid: int, hit: np.ndarray,
                  u_capacity: int, budget_total: int,
                  max_evals: int) -> np.ndarray:
    pos = np.arange(n_total)
    valid = pos < n_valid
    hit = hit & valid
    normal = valid & (pos < u_capacity)
    tier = np.where(hit, TIER_CACHED, TIER_PRIOR)
    tier[normal & ~hit] = TIER_EVAL
    drop = valid & ~normal & ~hit
    rank = np.cumsum(drop) - drop
    budget_drop = max(budget_total - int(np.sum(normal & ~hit)), 0)
    tier[drop & (rank < budget_drop)] = TIER_EVAL
    is_eval = tier == TIER_EVAL
    tier[is_eval & (np.cumsum(is_eval) - 1 >= max_evals)] = TIER_PRIOR
    tier[~valid] = TIER_INVALID
    return tier


def _served(rec, value: float) -> bool:
    """Whether ``value`` is the trust recorded for a URL: one float, or
    the set of them where one batch evaluated the URL more than once."""
    if rec is None:
        return False
    return value in rec if isinstance(rec, frozenset) else value == rec


class TrustDBReplay:
    """Replays every batch in dispatch order; see the module docstring.

    Per URL it keeps the batch of its last evaluation and the trust
    served then. Per set it keeps the URL of its latest write and the
    first batch from which every write to the set was by that URL, so a
    URL must hit when it owns its set and was evaluated since then.
    """

    NOBODY = -1

    def __init__(self, n_slots: int, fill_keys: np.ndarray = None,
                 fill_values: np.ndarray = None):
        """``fill_keys``/``fill_values``: what the Trust DB held before the
        first batch (key 0 is empty). A filled URL reads back its filled
        trust until it is evaluated again, and must hit while nobody has
        written its set."""
        self.n_slots = n_slots
        self.last_batch: Dict[int, int] = {}
        self.last_trust: Dict[int, object] = {}
        self.owner = np.full(n_slots, self.NOBODY, np.int64)
        self.owned_since = np.zeros(n_slots, np.int64)
        self.written = np.zeros(n_slots, bool)
        k = np.zeros(0, np.uint32) if fill_keys is None \
            else np.ravel(fill_keys)
        v = np.zeros(0, np.float32) if fill_values is None \
            else np.ravel(fill_values)
        order = np.argsort(k[k != 0])
        self.fill_keys = k[k != 0][order]
        self.fill_values = v[k != 0][order]
        self.counts = {"tier_mismatch": 0, "readback_mismatch": 0,
                       "missed_hits": 0}
        self.flagged_keys: Set[int] = set()
        self.n_batches = 0

    def _filled(self, keys: np.ndarray):
        """(whether each key was filled, its filled trust)."""
        i = np.minimum(np.searchsorted(self.fill_keys, keys),
                       max(len(self.fill_keys) - 1, 0))
        if not len(self.fill_keys):
            return np.zeros(len(keys), bool), np.zeros(len(keys), np.float32)
        return self.fill_keys[i] == keys, self.fill_values[i]

    def batch(self, keys: np.ndarray, n_valid: int, tier: np.ndarray,
              trust: np.ndarray, u_capacity: int, budget_total: int,
              max_evals: int) -> None:
        b = self.n_batches
        self.n_batches += 1
        keys = np.asarray(keys, np.uint32)
        tier = np.asarray(tier)
        want = planned_tiers(len(keys), n_valid, tier == TIER_CACHED,
                             u_capacity, budget_total, max_evals)
        bad = want != tier
        self.counts["tier_mismatch"] += int(np.sum(bad))
        k = keys[:n_valid].astype(np.int64)
        t = tier[:n_valid]
        klist = k.tolist()
        slots = slot_of(k, self.n_slots)

        ci = np.flatnonzero(t == TIER_CACHED)
        if len(ci):
            filled, fill_v = self._filled(keys[ci])
            ok = np.fromiter(
                (_served(rec, v) if rec is not None else f and v == fv
                 for rec, v, f, fv in zip(
                     (self.last_trust.get(klist[i]) for i in ci.tolist()),
                     trust[ci].tolist(), filled.tolist(), fill_v.tolist())),
                bool, len(ci))
            self.counts["readback_mismatch"] += int(np.sum(~ok))
            bad[ci[~ok]] = True

        mi = np.flatnonzero((t == TIER_EVAL) | (t == TIER_PRIOR))
        if len(mi):
            last = np.fromiter((self.last_batch.get(klist[i], -1)
                                for i in mi.tolist()), np.int64, len(mi))
            s = slots[mi]
            filled, _ = self._filled(keys[mi])
            must = ((last >= 0) & (self.owner[s] == k[mi])
                    & (last >= self.owned_since[s])) \
                | ((last < 0) & filled & ~self.written[s])
            self.counts["missed_hits"] += int(np.sum(must))
            bad[mi[must]] = True
        self.flagged_keys.update(keys[bad].tolist())

        ei = np.flatnonzero(t == TIER_EVAL)
        if not len(ei):
            return
        self.written[slots[ei]] = True
        ek, ev, es = k[ei], np.asarray(trust)[ei], slots[ei]
        order = np.argsort(ek, kind="stable")
        uk, first, n = np.unique(ek[order], return_index=True,
                                 return_counts=True)
        self.last_batch.update(dict.fromkeys(uk.tolist(), b))
        once = n == 1
        self.last_trust.update(zip(uk[once].tolist(),
                                   ev[order][first[once]].tolist()))
        for key, f, m in zip(uk[~once].tolist(), first[~once].tolist(),
                             n[~once].tolist()):
            vals = frozenset(ev[order][f:f + m].tolist())
            self.last_trust[key] = (next(iter(vals)) if len(vals) == 1
                                    else vals)
        # One distinct writer of a set in this batch keeps (or takes) it;
        # two or more leave it to nobody until the next lone writer.
        pairs = np.unique((es << 32) | ek)
        ps, pk = pairs >> 32, pairs & 0xFFFFFFFF
        us, fi, nk = np.unique(ps, return_index=True, return_counts=True)
        lone = nk == 1
        s1, k1 = us[lone], pk[fi[lone]]
        moved = self.owner[s1] != k1
        self.owner[s1[moved]] = k1[moved]
        self.owned_since[s1[moved]] = b
        self.owner[us[~lone]] = self.NOBODY
