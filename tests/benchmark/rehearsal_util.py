"""Toy-size rehearsal of a cell: the real `run_cell` against a real
`server` child on the CPU backend (`-ec.codec tpu_xor`: the XLA XOR
network, the one device codec a CPU backend can run).  The harness's look
for a chip is skipped (`require_tpu=False`); everything else is the run.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

BENCH = run.load_benchmark()


def toy(cell_name: str):
    """-> (cell entry, config, traffic) cut to a size a test can hold."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == cell_name)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config.update(codec="tpu_xor", volume_bytes=25 << 20,
                  needle_max_bytes=1 << 20, preload_files=2048,
                  preload_volumes=2, clients=4)
    traffic = run.load_json("traffic", cell["traffic"] + ".json")

    def cut(spec):
        if "volumes" in spec:
            spec.update(volumes=min(spec["volumes"], 3),
                        warmup_bytes=12 << 20, rows_checked=3,
                        needles_checked=6)
        spec.update(next_loss_after_s=0.05)
        for key in ("foreground", "background"):
            if key in spec:
                cut(spec[key])
    cut(traffic)
    if traffic["trace"]["mode"] == "time":
        traffic["trace"].update(start_s=0.2, length_s=0.5)
    return cell, config, traffic


def rehearse(cell_name: str, seed: int, traced: bool = False,
             control=None, seconds: float = 1.5):
    cell, config, traffic = toy(cell_name)
    line, compared = run.run_cell(
        cell["name"], cell["chips"], config, traffic,
        run.cell_metrics(BENCH, cell["name"], traced), seed, seconds,
        traced, require_tpu=False, control=control,
        tag=f"-test{os.getpid()}")
    out = json.loads(line)
    assert out["compared"] == compared
    return out


OFF_CHIP = {"not_on_tpu", "window_without_device_batches"}


def over(out: dict) -> set:
    """Names of the numbers compared that are over their limit."""
    return {k for k, c in out["compared"].items()
            if c["value"] is None or c["value"] > c["limit"]}
