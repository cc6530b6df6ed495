"""Readers, one module per kind, found by name.

A metric is a data file `benchmark/metrics/<metric>.json` with `"reader"`
(a module here) and `"args"`.  A reader is `read(obs, args) -> float | None`
over what the run observed (`harness.Obs`); one that finds nothing to read
returns None and the metric is left out of the line — never 0.
"""

from __future__ import annotations

import importlib
import json
import os

from ..harness import BENCH_DIR


def metric_spec(name: str) -> dict:
    with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as f:
        return json.load(f)


def read_metric(name: str, obs) -> "float | None":
    spec = metric_spec(name)
    mod = importlib.import_module(f"{__name__}.{spec['reader']}")
    value = mod.read(obs, spec.get("args", {}))
    return None if value is None else float(value)
