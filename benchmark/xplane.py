"""From the profiler's ``.xplane.pb`` to device busy time and top ops.

``jax.profiler.ProfileData`` reads the file: planes (one per device,
``/device:TPU:<n>``, and the host's), their lines, and events with a
start and a duration.  Busy is the union of the intervals in which an
op runs on a device plane's op line; idle is the rest of the traced
slice.  The slice is what the trace itself spans, first event to last,
over every plane: the host's threads write into it all the time the
profiler is on.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
TOP = 10


def find(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def planes_of(path):
    """``{plane name: {line name: [(name, start_ns, end_ns), ...]}}``."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events)
    return out


def short(name):
    """``jit_f(1234)`` -> ``jit_f``; an HLO line -> its result's name."""
    return name.split(" = ")[0].lstrip("%").split("(")[0]


def by_program(modules, ops):
    """Seconds per program and per op, an op named by the program whose
    run covers its start."""
    import bisect

    modules = sorted(modules, key=lambda e: e[1])
    starts = [s for _n, s, _e in modules]
    programs, per_op = {}, {}
    for name, s, e in modules:
        programs[short(name)] = programs.get(short(name), 0.0) + (e - s) / 1e9
    for name, s, e in ops:
        k = bisect.bisect_right(starts, s) - 1
        inside = k >= 0 and s < modules[k][2]
        key = (short(modules[k][0]) if inside else "?") + "/" + short(name)
        per_op[key] = per_op.get(key, 0.0) + (e - s) / 1e9
    return programs, per_op


def reduce_planes(planes):
    """The numbers of one trace.  A device plane with no op line (a
    backend that names it otherwise) counts every line it has."""
    every = [(s, e) for lines in planes.values() for evs in lines.values()
             for _n, s, e in evs]
    if not every:
        raise ValueError("the trace holds no event")
    t0, t1 = min(s for s, _ in every), max(e for _, e in every)
    devices = {n: l for n, l in planes.items() if n.startswith(DEVICE_PREFIX)}
    busy, programs, per_op, gaps = [], {}, {}, []
    for name, lines in sorted(devices.items()):
        evs = lines.get(OP_LINE) or [e for l in lines.values() for e in l]
        merged = union((s, e) for _n, s, e in evs)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for into, some in zip((programs, per_op),
                              by_program(lines.get(MODULE_LINE, []), evs)):
            for k, v in some.items():
                into[k] = into.get(k, 0.0) + v
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        gaps += [(edges[i + 1] - edges[i]) / 1e9
                 for i in range(0, len(edges), 2)]
    n = max(len(devices), 1)

    def top(d, k, prefix=""):
        return [[prefix + name, v / n] for name, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:k]]

    progs = top(programs, 3, "program ")
    return {
        "devices": len(devices),
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / n,
        "programs": top(programs, len(programs)),
        # whole programs first, then the ops inside them
        "top_ops": progs + top(per_op, TOP - len(progs)),
        # which host span covers a gap is not known: the program's
        # tracer and the profiler have not been shown to share a clock
        "idle_gaps": [["unattributed", g]
                      for g in sorted(gaps, reverse=True)[:TOP] if g > 0],
        "lines": {p: {l: len(e) for l, e in lines.items()}
                  for p, lines in planes.items()},
    }


def reduce(path):
    return reduce_planes(planes_of(path))
