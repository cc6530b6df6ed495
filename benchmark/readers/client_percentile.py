"""A percentile over EVERY sample of a client-clock series (ms)."""

import math


def read(obs, args):
    series = obs.clock.get(args["series"])
    if not series:
        return None
    ordered = sorted(series)
    rank = math.ceil(args["percentile"] / 100.0 * len(ordered))
    return ordered[max(rank, 1) - 1]
