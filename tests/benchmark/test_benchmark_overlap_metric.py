"""`ec_degraded_overlap_pct.degraded`: the share of lost intervals a
needle read decoded with another of its decodes in flight, read by
`prom_ratio` from its data file against the program's own registry (CPU
only; no jax)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness as hz  # noqa: E402
from benchmark import readers  # noqa: E402

NAME = "ec_degraded_overlap_pct.degraded"
OVERLAPPED = "seaweedfs_ec_degraded_overlapped_total"


def _registry_obs(work) -> hz.Obs:
    from seaweedfs_tpu.stats.metrics import REGISTRY

    obs = hz.Obs()
    before = hz.parse_metrics(REGISTRY.render())
    work()
    obs.prom["read"] = [before, hz.parse_metrics(REGISTRY.render())]
    return obs


def test_the_data_file_names_the_counter_over_the_decoded_intervals():
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           NAME + ".json")) as f:
        spec = json.load(f)
    assert spec == readers.metric_spec(NAME)
    assert spec["reader"] == "prom_ratio"
    assert spec["args"] == {
        "phase": "read",
        "num": {"name": OVERLAPPED},
        "den": {"name": "seaweedfs_ec_degraded_intervals_total",
                "labels": ['outcome="decoded"']},
        "scale": 100.0,
    }


@pytest.mark.parametrize("overlapped,decoded,want", [
    (3, 4, 75.0),    # a read of three lost intervals beside one of one
    (0, 5, 0.0),     # no read had two decodes in flight: 0, not silence
    (0, 0, None),    # no decode in the phase: nothing to read
], ids=["some", "none", "idle"])
def test_prom_ratio_reads_the_share_from_the_registry(overlapped, decoded,
                                                      want):
    from seaweedfs_tpu.stats.metrics import (
        EC_DEGRADED_INTERVALS,
        EC_DEGRADED_OVERLAPPED,
    )

    def work():
        EC_DEGRADED_OVERLAPPED.labels().inc(overlapped)
        EC_DEGRADED_INTERVALS.labels("decoded").inc(decoded)
        EC_DEGRADED_INTERVALS.labels("cached").inc(2)  # not a decode

    got = readers.read_metric(NAME, _registry_obs(work))
    assert got == (None if want is None else pytest.approx(want))


def test_a_program_without_the_counter_reads_nothing():
    """The parent exports the decoded intervals but no overlap family: the
    metric is left out of its line."""
    from seaweedfs_tpu.stats.metrics import EC_DEGRADED_INTERVALS

    def work():
        EC_DEGRADED_INTERVALS.labels("decoded").inc(4)

    obs = _registry_obs(work)
    for pair in obs.prom.values():
        for scrape in pair:
            for key in [k for k in scrape if k.startswith(OVERLAPPED)]:
                del scrape[key]
    assert obs.delta("read", "seaweedfs_ec_degraded_intervals_total",
                     'outcome="decoded"') == 4
    assert readers.read_metric(NAME, obs) is None
    assert readers.read_metric(NAME, hz.Obs()) is None
