"""A quantile of a server-side histogram over a phase, in ms.

The window's delta of each `name_bucket{labels, le}` series between the
phase's two scrapes (series of one `le` summed over the other labels),
interpolated as PromQL's `histogram_quantile` does: linearly inside the
bucket the rank falls in (from 0 below the lowest bound), and the highest
finite bound where it falls in `+Inf`.  None where the family is absent
or its count did not move.  args: `phase`, `name`, `labels`, `q`."""

import math
import re

_LE = re.compile(r'le="([^"]*)"')


def read(obs, args):
    pair = obs.prom.get(args["phase"])
    if not pair or pair[1] is None:
        return None
    before, after = pair
    series = args["name"] + "_bucket"
    bits = args.get("labels", [])
    cum: dict = {}
    for key, val in after.items():
        if key.split("{", 1)[0] != series or not all(b in key for b in bits):
            continue
        le = _LE.search(key)
        if le is None:
            continue
        bound = math.inf if le.group(1) == "+Inf" else float(le.group(1))
        cum[bound] = cum.get(bound, 0.0) + val - before.get(key, 0.0)
    total = cum.get(math.inf, 0.0)
    if total <= 0:
        return None
    rank = args["q"] * total
    lower, below = 0.0, 0.0
    for bound in sorted(cum):
        if cum[bound] >= rank:
            if math.isinf(bound):
                return 1e3 * lower
            inside = cum[bound] - below
            if inside <= 0:
                return 1e3 * bound
            return 1e3 * (lower + (bound - lower) * (rank - below) / inside)
        lower, below = bound, cum[bound]
    return None
