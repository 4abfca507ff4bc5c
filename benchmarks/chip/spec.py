"""Everything a cell is, found by name from ``BENCHMARK.json``.

* ``configs/<config>.json``: the configuration as it is run, with its
  ``family``; ``families/<family>.py`` builds its evaluator from the
  program and ``refs/<family>.py`` is its plain reference;
* ``mixes/<traffic>.json``: the traffic's parameters, read by
  ``traffic.py``;
* ``cells/<workload>.json``: the cell's offered rate;
* ``metrics/<name>.py``: the reader of a per-layer metric; a name with a
  suffix (``mfu.over``) is read by the reader of its stem (``mfu``).

A later cell, configuration, mix or metric is added as files and
entries; no file here changes for it.
"""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    rate_qps: float
    end_to_end: List[Dict]        # this cell's end-to-end metrics
    per_layer: List[Dict]         # this cell's per-layer metrics

    @property
    def family(self):
        return importlib.import_module(
            f"benchmarks.chip.families.{self.config['family']}")

    @property
    def reference(self):
        return importlib.import_module(
            f"benchmarks.chip.refs.{self.config['family']}")


def reader(metric_name: str):
    """The module whose ``read(ctx)`` gives the per-layer metric."""
    stem = metric_name.split(".")[0]
    return importlib.import_module(f"benchmarks.chip.metrics.{stem}")


def _applies(metric: Dict, workload: str, reported: List[str]) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric["moves"] in reported


def load(workload: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[w["config"]]["file"])
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, names)]
    return Cell(name=workload, chips=w["chips"], config=config,
                mix=_json(BENCH_DIR / "mixes" / f"{w['traffic']}.json"),
                rate_qps=_json(BENCH_DIR / "cells"
                               / f"{workload}.json")["rate_qps"],
                end_to_end=e2e, per_layer=per_layer)
