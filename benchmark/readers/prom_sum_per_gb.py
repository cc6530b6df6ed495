"""Seconds a layer was busy per GB of work: the delta sums of the listed
histogram series over a phase, per 1e9 bytes of `per` — either a count of
the harness's own (`{"work": key}`) or another delta (`{"name", "labels"}`).
"""


def read(obs, args):
    phase = args.get("phase", "window")
    busy = 0.0
    for s in args["series"]:
        d = obs.delta(phase, s["name"] + "_sum", *s.get("labels", []))
        if d is None:
            return None
        busy += d
    per = args["per"]
    if "work" in per:
        amount = obs.work.get(per["work"])
    else:
        amount = obs.delta(phase, per["name"], *per.get("labels", []))
    if not amount or not busy:
        return None
    return busy / (amount / 1e9)
