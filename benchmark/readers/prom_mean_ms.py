"""Mean of a server-side histogram over a phase: delta sum / delta count
of `name{labels}` between the phase's two scrapes, in ms."""


def read(obs, args):
    bits = args.get("labels", [])
    total = obs.delta(args["phase"], args["name"] + "_sum", *bits)
    count = obs.delta(args["phase"], args["name"] + "_count", *bits)
    if not count or total is None:
        return None
    return 1e3 * total / count
