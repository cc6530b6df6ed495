"""One delta over another over a phase: `scale * (num / den - minus)` of two
series `{"name", "labels"}` between the phase's two scrapes — a mean of a
histogram the program keeps (`_sum` over `_count`), or what one counter
exceeds another by (`minus` 1, `scale` 100: percent).  A program that has
no such series reads nothing."""


def read(obs, args):
    phase = args.get("phase", "window")
    num, den = (obs.delta(phase, s["name"], *s.get("labels", []))
                for s in (args["num"], args["den"]))
    if not num or not den:
        return None
    return args.get("scale", 1.0) * (num / den - args.get("minus", 0.0))
