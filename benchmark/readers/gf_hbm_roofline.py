"""Share of the HBM roofline the GF work reached in the traced slice:
(bytes the algorithm needs) / peak HBM bytes/s / (device time in which an
op ran: the union of the op intervals on the device plane, `busy_s`).  The
bytes come from the codec service's own unpadded input counter between
trace start and stop and from benchmark/gf_work.py — never from the
program's shapes.  Not the XLA modules' spans: on one chip they equal the
ops' time, but on a mesh a module's span holds its wait for the slowest
chip's input, which is the host's doing and `device_idle_pct`'s to show."""

from .. import gf_work


def read(obs, args):
    tr = obs.trace
    if not tr or not tr.get("busy_s"):
        return None
    peak = obs.peaks.get("hbm_bytes_per_s")
    input_bytes = obs.delta(args.get("phase", "trace"),
                            "seaweedfs_ec_service_batch_bytes_sum")
    if not input_bytes or not peak:
        return None
    need = gf_work.needed_bytes(input_bytes, args["rows_in"], args["rows_out"])
    # busy_s is per chip (mean over the chips used); so is the peak
    return gf_work.hbm_roofline_pct(
        need / max(tr.get("chips", 1), 1), tr["busy_s"], peak)
