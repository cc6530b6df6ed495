"""Share of the HBM roofline the GF work reached in the traced slice:
(bytes the algorithm needs) / peak HBM bytes/s / (device time of the XLA
modules on the device plane).  The bytes come from the codec service's own
unpadded input counter between trace start and stop and from
benchmark/gf_work.py — never from the program's shapes."""

from .. import gf_work


def read(obs, args):
    tr = obs.trace
    if not tr or not tr.get("module_s"):
        return None
    peak = obs.peaks.get("hbm_bytes_per_s")
    input_bytes = obs.delta(args.get("phase", "trace"),
                            "seaweedfs_ec_service_batch_bytes_sum")
    if not input_bytes or not peak:
        return None
    need = gf_work.needed_bytes(input_bytes, args["rows_in"], args["rows_out"])
    # module_s is per chip (mean over the chips used); so is the peak
    return gf_work.hbm_roofline_pct(
        need / max(tr.get("chips", 1), 1), tr["module_s"], peak)
