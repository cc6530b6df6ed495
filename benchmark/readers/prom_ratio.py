"""One delta over another over a phase: `scale * (num / den - minus)` of two
series `{"name", "labels"}` between the phase's two scrapes — a mean of a
histogram the program keeps (`_sum` over `_count`), or what one counter
exceeds another by (`minus` 1, `scale` 100: percent).  Where the denominator
moved and the numerator stood still the reading is `scale * (0 - minus)`: a
share of nothing is 0, not silence.  A program that has no such series, and
a phase in which the denominator did not move, read nothing."""


def read(obs, args):
    phase = args.get("phase", "window")
    num, den = (obs.delta(phase, s["name"], *s.get("labels", []))
                for s in (args["num"], args["den"]))
    if not den or (not num and not obs.has_series(phase, args["num"]["name"])):
        return None
    return args.get("scale", 1.0) * ((num or 0.0) / den
                                     - args.get("minus", 0.0))
