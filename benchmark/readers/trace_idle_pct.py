"""1 - (union of the intervals in which any op ran on the device) over
the traced slice, averaged over the chips used, in percent."""


def read(obs, args):
    tr = obs.trace
    if not tr or not tr.get("window_s") or tr.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
