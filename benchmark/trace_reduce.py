#!/usr/bin/env python3
"""Reduce a jax profiler trace (`*.xplane.pb`) to the numbers the metrics
read.  Runs as a child, after the server has exited:

    python benchmark/trace_reduce.py TRACE_DIR  ->  one JSON object

Reduction is by MODULE ON THE DEVICE PLANE, not by fusion names, so the
numbers survive a change of kernel:

  chips      device planes found ("/device:TPU:<n>")
  window_s   the traced slice: first to last event start/end over every
             plane (host planes included: the slice is what was traced,
             not what the device happened to do)
  busy_s     union of the intervals in which an op ran on a device plane
             (its "XLA Ops" line; the "XLA Modules" line where that is
             missing), averaged over the chips
  module_s   summed durations on the "XLA Modules" lines, averaged likewise
  modules    [[name, seconds], ...] top 10 by time, over all chips
  device_ops [[name, seconds], ...] top 10 ops by time, over all chips
  idle_gaps  [[label, seconds], ...] the 10 longest gaps on the busiest
             chip, labelled by what the host was doing: the host-plane event
             that covers most of the gap with its share, and the share no
             host event covers (the program has no spans of its own on the
             profiler's clock yet, so that share is its Python), then the
             op the device ran last before the gap
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> "str | None":
    found = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def union_seconds(intervals: list) -> float:
    """Total length of the union of [start, end) intervals (ns) in s."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def host_label(g_s: int, g_e: int, host: list) -> str:
    """What the host planes show inside the gap [g_s, g_e): the event name
    that covers most of it, and how much of it no event covers."""
    by_name, clipped = {}, []
    for s, e, name in host:
        s, e = max(s, g_s), min(e, g_e)
        if e > s:
            by_name.setdefault(name, []).append((s, e))
            clipped.append((s, e))
    if not clipped:
        return "no host span"
    length = (g_e - g_s) / 1e9
    top = max(by_name, key=lambda n: union_seconds(by_name[n]))
    return (f"{top} {100 * union_seconds(by_name[top]) / length:.0f}%, "
            f"no host span {100 * (1 - union_seconds(clipped) / length):.0f}%")


def gaps(intervals: list, host: "list | None" = None,
         limit: int = 10) -> list:
    """The longest gaps (of a microsecond or more) between busy stretches:
    [[label, seconds], ...]; intervals and host are (start, end, name)."""
    found, cur_e, cur_name = [], None, ""
    for s, e, name in sorted(intervals):
        if cur_e is not None and s - cur_e >= 1000:   # a microsecond or more
            found.append((s - cur_e, cur_e, s, cur_name))
        if cur_e is None or e > cur_e:
            cur_e, cur_name = e, name
    found.sort(key=lambda g: -g[0])
    return [[f"{host_label(g_s, g_e, host or [])}; after {name}",
             length / 1e9] for length, g_s, g_e, name in found[:limit]]


def short(name: str) -> str:
    """An op is named by its whole HLO line: keep the instruction's name
    ("%xor_xor_fusion.1 = u8[...] fusion(...)" -> "xor_xor_fusion.1")."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def _top(table: dict, limit: int = 10) -> list:
    return [[k, v] for k, v in sorted(
        table.items(), key=lambda kv: -kv[1])[:limit]]


def reduce_planes(planes: list) -> dict:
    """`planes`: [(plane name, [(line name, [(event name, start_ns,
    duration_ns), ...]), ...]), ...] -> the dict described above."""
    t_min, t_max = None, None
    chips, host = [], []
    for pname, lines in planes:
        for lname, events in lines:
            for name, start, dur in events:
                t_min = start if t_min is None else min(t_min, start)
                t_max = start + dur if t_max is None \
                    else max(t_max, start + dur)
                if pname.startswith("/host:"):
                    host.append((start, start + dur, short(name)))
        if DEVICE_PLANE.match(pname):
            chips.append((pname, dict(lines)))
    out = {"chips": len(chips),
           "window_s": (t_max - t_min) / 1e9 if t_min is not None else 0.0}
    if not chips:
        return out
    busy, module, mod_table, op_table = [], [], {}, {}
    busiest, busiest_s = None, -1.0
    for _pname, lines in chips:
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        iv = [(s, s + d, short(n)) for n, s, d in ops]
        b = union_seconds([(s, e) for s, e, _ in iv])
        busy.append(b)
        if b > busiest_s:
            busiest, busiest_s = iv, b
        for n, _s, d in lines.get(OPS_LINE, []):
            op_table[short(n)] = op_table.get(short(n), 0.0) + d / 1e9
        msum = 0.0
        for n, _s, d in lines.get(MODULES_LINE, []):
            n = re.sub(r"\(\d+\)$", "", n)   # drop the program fingerprint
            mod_table[n] = mod_table.get(n, 0.0) + d / 1e9
            msum += d / 1e9
        module.append(msum)
    out.update({
        "busy_s": sum(busy) / len(busy),
        "module_s": sum(module) / len(module),
        "modules": _top(mod_table),
        "device_ops": _top(op_table) or _top(mod_table),
        "idle_gaps": gaps(busiest or [], host),
    })
    return out


def load_planes(path: str) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events]))
        planes.append((plane.name, lines))
    return planes


def describe(planes: list) -> str:
    """Plane / line / event-count summary, for reading a trace by hand."""
    rows = []
    for pname, lines in planes:
        for lname, events in lines:
            names = {}
            for n, _s, d in events:
                names[n] = names.get(n, 0) + d
            top = ", ".join(f"{n}={d / 1e6:.2f}ms" for n, d in sorted(
                names.items(), key=lambda kv: -kv[1])[:6])
            rows.append(f"{pname} | {lname} | {len(events)} events | {top}")
    return "\n".join(rows)


def main(argv: list) -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    path = argv[0] if argv[0].endswith(".pb") else find_xplane(argv[0])
    if path is None:
        print(json.dumps({"error": f"no .xplane.pb under {argv[0]}"}))
        return 1
    planes = load_planes(path)
    if len(argv) > 1:
        with open(argv[1], "w") as f:
            f.write(describe(planes) + "\n")
    out = reduce_planes(planes)
    out["xplane_bytes"] = os.path.getsize(path)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
