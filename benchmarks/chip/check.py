"""End-to-end numbers of a run, and the comparison that decides ``correct``.

Every number compared has a limit, read from the configuration file's
``limits`` (the counts' limit is 0: they are exact). The comparison
covers what the timed path produced, at the timed sizes:

* ``one_response``: requests due in the window without exactly one
  answer;
* ``executor_errors``: answers the executor rescued from the prior;
* ``tier_mismatch``, ``readback_mismatch``, ``missed_hits``: every fused
  step of the run, warm-up included, replayed against the plain trust
  tier reference (``refs/trust_tier.py``) from the Trust DB the run was
  filled with;
* ``served_mismatch``: every answer the client got, warm-up included,
  against the rows of the fused steps (see ``served_mismatch``), so the
  replay's verdict on the steps holds for what was served;
* ``trust_gap``: the widest gap between the trust a response carried
  for an evaluated candidate and the plain float32 reference's, over a
  sample of the window's evaluated candidates drawn from the seed.
"""
from __future__ import annotations

from typing import Dict, List, Set, Tuple

import numpy as np

from benchmarks.chip.refs.trust_tier import (TIER_CACHED, TIER_EVAL,
                                             TIER_PRIOR, TrustDBReplay)
from benchmarks.chip.traffic import seed_words


class Responses:
    """The answers to the window's requests, as the client saw them."""

    def __init__(self, answers: Dict[int, list], sent: Dict, t0: float,
                 t_close: float, seconds: float):
        self.answers = answers
        self.sent = sent
        self.t0, self.t_close, self.seconds = t0, t_close, seconds

    def first(self, rid):
        a = self.answers[rid]
        return a[0] if a else (None, None)

    def failed_rids(self) -> Set[int]:
        bad = set()
        for rid, a in self.answers.items():
            if len(a) != 1 or a[0][1].reason.startswith("executor_error"):
                bad.add(rid)
        return bad

    def latencies_s(self) -> np.ndarray:
        out = []
        for rid, s in self.sent.items():
            t, _ = self.first(rid)
            out.append((t if t is not None else self.t_close + 60.0) - s.due)
        return np.asarray(out)

    def end_to_end(self, setup_s: float) -> Dict[str, float]:
        lat_ms = self.latencies_s() * 1e3
        served = sum(1 for rid in self.sent
                     if self.first(rid)[1] is not None
                     and self.first(rid)[1].admitted
                     and self.first(rid)[0] < self.t_close)
        trusted = total = 0
        for rid, s in self.sent.items():
            total += len(s.urls)
            r = self.first(rid)[1]
            if r is not None:
                trusted += int(np.sum((r.tier == TIER_EVAL)
                                      | (r.tier == TIER_CACHED)))
        return {"qps": served / self.seconds,
                "p95_ms": float(np.percentile(lat_ms, 95)),
                "p50_ms": float(np.percentile(lat_ms, 50)),
                "trusted_share": trusted / max(total, 1),
                "setup_s": setup_s}

    def lag_max(self) -> float:
        return max((s.t_enqueue - s.due for s in self.sent.values()),
                   default=0.0)

    def tier_shares(self) -> Dict[str, float]:
        counts = np.zeros(4)
        rejected = 0
        for rid in self.sent:
            r = self.first(rid)[1]
            if r is not None:
                counts += np.bincount(r.tier, minlength=4)[:4]
                rejected += int(not r.admitted)
        tot = max(counts[:3].sum(), 1)
        return {"share_eval": counts[0] / tot, "share_cached": counts[1] / tot,
                "share_prior": counts[2] / tot,
                "rejected_requests": rejected}


def _rids_with(sent: Dict, keys: Set[int]) -> Set[int]:
    if not keys:
        return set()
    arr = np.fromiter(keys, np.uint32)
    return {rid for rid, s in sent.items() if np.isin(s.urls, arr).any()}


def _packed(urls: np.ndarray, trust: np.ndarray) -> np.ndarray:
    """One uint64 per item: its URL id and the bits of its trust."""
    return (np.asarray(urls, np.uint64) << np.uint64(32)) \
        | np.asarray(trust, np.float32).view(np.uint32).astype(np.uint64)


def served_mismatch(log, all_answers: Dict[int, list], all_sent: Dict
                    ) -> Tuple[int, Set[int]]:
    """Items served to the client that no fused step answered so, and
    step rows that reached no client, counted as a multiset of (URL,
    tier, trust): a step's rows handed to the wrong request, at the wrong
    offset or twice, or answers altered after the step, all count. A
    request rejected at admission must be answered wholly from the
    prior. Returns the count and the requests holding such items."""
    step = {t: [] for t in (TIER_EVAL, TIER_CACHED, TIER_PRIOR)}
    for r in log:
        n = int(np.asarray(r.valid).sum())
        keys, tier = np.asarray(r.keys)[:n], np.asarray(r.tier)[:n]
        trust = np.asarray(r.trust)[:n]
        for t in step:
            step[t].append(_packed(keys[tier == t], trust[tier == t]))
    served = {t: [] for t in step}
    owner = {t: [] for t in step}
    bad: Set[int] = set()
    count = 0
    for rid, a in all_answers.items():
        if len(a) != 1 or a[0][1].reason.startswith("executor_error"):
            continue                     # counted by their own checks
        r, urls = a[0][1], all_sent[rid].urls
        tier, trust = np.asarray(r.tier), np.asarray(r.trust)
        if len(tier) != len(urls) or len(trust) != len(urls):
            count += len(urls)
            bad.add(rid)
            continue
        if not r.admitted:
            wrong = int(np.sum(tier != TIER_PRIOR))
            count += wrong
            if wrong:
                bad.add(rid)
            continue
        for t in served:
            sel = tier == t
            served[t].append(_packed(urls[sel], trust[sel]))
            owner[t].append(np.full(int(sel.sum()), rid, np.int64))
    for t in step:
        a = np.concatenate(step[t] or [np.zeros(0, np.uint64)])
        b = np.concatenate(served[t] or [np.zeros(0, np.uint64)])
        uniq, inv = np.unique(np.concatenate([a, b]), return_inverse=True)
        net = np.bincount(inv, np.r_[np.ones(len(a)), -np.ones(len(b))],
                          minlength=len(uniq))
        count += int(np.abs(net).sum())
        off = uniq[net != 0]
        if len(off) and len(b):
            rids = np.concatenate(owner[t])
            bad.update(np.unique(rids[np.isin(b, off)]).tolist())
    return count, bad


def trust_sample(responses: Responses, n: int, seed: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(URL ids, served trust) of up to ``n`` candidates evaluated for the
    window's requests, as their responses carried them, drawn from the
    seed."""
    keys, trust = [], []
    for rid, s in sorted(responses.sent.items()):
        r = responses.first(rid)[1]
        if r is None or len(r.tier) != len(s.urls):
            continue
        ev = np.flatnonzero(np.asarray(r.tier) == TIER_EVAL)
        keys.append(s.urls[ev])
        trust.append(np.asarray(r.trust)[ev])
    if not keys:
        return np.zeros(0, np.uint32), np.zeros(0, np.float32)
    keys, trust = np.concatenate(keys), np.concatenate(trust)
    rng = np.random.default_rng(seed_words(seed, 9))
    pick = np.sort(rng.permutation(len(keys))[:n])
    return keys[pick], trust[pick]


def run_checks(cell, weights, log, fill, responses: Responses,
               all_answers: Dict[int, list], all_sent: Dict, seed: int
               ) -> Tuple[Dict[str, Dict], Set[int]]:
    cfg = cell.config
    limits = cfg["limits"]
    replay = TrustDBReplay(cfg["serving"]["trust_db_slots"], fill.keys,
                           fill.values)
    for r in log:
        valid = np.asarray(r.valid)
        replay.batch(np.asarray(r.keys), int(valid.sum()),
                     np.asarray(r.tier), np.asarray(r.trust),
                     r.u_capacity, r.budget_total, r.max_evals)
    mismatch, mismatch_rids = served_mismatch(log, all_answers, all_sent)

    keys, served = trust_sample(responses, cfg["serving"]["check_items"],
                                seed)
    gap = 0.0
    far: Set[int] = set()
    if len(keys):
        ref = cell.reference.trust(cfg, weights,
                                   cell.family.features(cfg, keys))
        diff = np.abs(served.astype(np.float64) - ref)
        gap = float(diff.max())
        far = {int(k) for k in keys[diff > limits["trust_gap"]]}

    one = sum(1 for a in responses.answers.values() if len(a) != 1)
    errors = sum(1 for a in responses.answers.values()
                 for _, r in a if r.reason.startswith("executor_error"))
    checks = {
        "one_response": {"value": one, "limit": 0},
        "executor_errors": {"value": errors, "limit": 0},
        **{k: {"value": v, "limit": 0} for k, v in replay.counts.items()},
        "served_mismatch": {"value": mismatch, "limit": 0},
        "trust_gap": {"value": gap, "limit": limits["trust_gap"]},
        # A run that evaluated nothing in its window compared nothing.
        "empty_trust_sample": {"value": int(len(keys) == 0), "limit": 0},
    }
    failed = _rids_with(responses.sent, replay.flagged_keys | far) \
        | (mismatch_rids & set(responses.sent))
    return checks, failed
