"""One number the harness took itself (e.g. `setup_s`)."""


def read(obs, args):
    return obs.work.get(args["key"])
