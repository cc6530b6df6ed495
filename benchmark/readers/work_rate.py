"""The harness's own count over the harness's own clock: work[bytes] /
work[span] / scale — over all the work and all the time of the window."""


def read(obs, args):
    done = obs.work.get(args["bytes"])
    span = obs.work.get(args["span"])
    if not done or not span:
        return None
    return done / span / args.get("scale", 1e6)
