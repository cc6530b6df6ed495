"""`gf_hbm_roofline` where the traced slice holds jobs of more than one
class: the codec service's unpadded input bytes of each class (`class=` on
`seaweedfs_ec_service_batch_bytes_sum`) between trace start and stop, each
reckoned with the output rows its jobs really have (`rows_out`: {class:
rows}; `benchmark/gf_work.py`), summed, over the peak HBM bytes/s and the
device time in which an op ran (`busy_s`).  A program that does not tell
its jobs' classes apart reads nothing, never 0."""

from .. import gf_work

NAME = "seaweedfs_ec_service_batch_bytes_sum"


def read(obs, args):
    tr = obs.trace
    if not tr or not tr.get("busy_s"):
        return None
    peak = obs.peaks.get("hbm_bytes_per_s")
    phase = args.get("phase", "trace")
    pair = obs.prom.get(phase)
    if not peak or not pair or pair[1] is None:
        return None
    need = 0.0
    for cls, rows_out in args["rows_out"].items():
        label = f'class="{cls}"'
        if not any(key.split("{", 1)[0] == NAME and label in key
                   for key in pair[1]):
            return None
        need += gf_work.needed_bytes(
            obs.delta(phase, NAME, label), args["rows_in"], rows_out)
    if not need:
        return None
    # busy_s is per chip (mean over the chips used); so is the peak
    return gf_work.hbm_roofline_pct(
        need / max(tr.get("chips", 1), 1), tr["busy_s"], peak)
