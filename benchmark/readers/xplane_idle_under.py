"""What the host was doing while the device idled, in percent of the
device's idle time in the profiler's slice.

While tracing is on the program holds a profiler annotation named
``flowgger.<stage>`` open over every stage and sub-span of a batch
(``obs/trace.py``), so they lie in the profiler's own file, on the
host's plane, on the clock of the device's op line.  Idle is the slice
(first event to last, over every plane, as ``xplane.reduce_planes``
takes it) less the union of the op intervals of a device; the host
events are united over every thread.

An annotation is written when it closes, and only if it opened while
the profiler listened: a stage longer than the slice (a probe's compile
of 10-30 s under ``fetch``, the ingest thread's ``window_wait`` behind
it) leaves no event.  So the tracer's own records of the same stages
and sub-spans (``ctx["spans"]``, perf_counter readings with the
process's offset to the wall clock) are laid beside the annotations,
placed by the trace's ``profile_start_time``; the two agree within
0.04 ms where both exist (PERF.md, PR 26), and the union counts a
stage once.

``args``: ``{"under": [names]}``: the share of the idle time that
events of these names cover; or ``{"outside": prefix}``: the share that
no event whose name starts with ``prefix`` covers.  Nothing to read
where the trace has no host plane, no device plane or no ``flowgger.*``
event at all (a program from before the annotations), or where the
device never idled.
"""

from benchmark import xplane

PREFIX = "flowgger."


def overlap(a, b):
    """Total length of the intersection of two sorted, merged lists of
    ``[start, end]``."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def profile_start_ns(path):
    """The epoch nanoseconds at which the trace's own clock reads 0
    (``profile_start_time`` of its ``Task Environment`` plane); None
    where the file does not say."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                return value
    return None


def placed_spans(spans, start_ns):
    """The tracer's stage spans and sub-spans as host events on the
    trace's clock: ``[(flowgger.<stage>, start_ns, end_ns), ...]``."""
    if not spans or start_ns is None:
        return []
    return [(PREFIX + sp["stage"],
             (sp["t0"] + rec["wall"]) * 1e9 - start_ns,
             (sp["t1"] + rec["wall"]) * 1e9 - start_ns)
            for rec in spans for sp in rec["spans"] + rec.get("sub", [])]


def idle_share(planes, args, placed=()):
    every = [(s, e) for lines in planes.values() for evs in lines.values()
             for _n, s, e in evs]
    devices = [l for n, l in planes.items()
               if n.startswith(xplane.DEVICE_PREFIX)]
    # an annotation's name may carry its arguments after a '#'
    host = [(n.split("#")[0], s, e) for p, lines in planes.items()
            if not p.startswith(xplane.DEVICE_PREFIX)
            for evs in lines.values() for n, s, e in evs]
    if not devices or not any(n.startswith(PREFIX) for n, _s, _e in host):
        return None
    host += placed
    if "under" in args:
        cover = [(s, e) for n, s, e in host if n in args["under"]]
    else:
        cover = [(s, e) for n, s, e in host
                 if n.startswith(args["outside"])]
    cover = xplane.union(cover)
    t0, t1 = min(s for s, _ in every), max(e for _, e in every)
    idle_ns, covered_ns = 0, 0
    for lines in devices:
        evs = lines.get(xplane.OP_LINE) or [e for l in lines.values()
                                            for e in l]
        edges = [t0] + [x for ab in xplane.union(
            (s, e) for _n, s, e in evs) for x in ab] + [t1]
        idle = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle_ns += sum(b - a for a, b in idle)
        covered_ns += overlap(idle, cover)
    if not idle_ns:
        return None
    share = 100.0 * covered_ns / idle_ns
    return share if "under" in args else 100.0 - share


def read(ctx, args):
    if not ctx.get("trace_dir"):
        return None
    if "planes" not in ctx:
        # one parse for the metrics of a run that share this reader
        path = xplane.find(ctx["trace_dir"])
        ctx["planes"] = xplane.planes_of(path)
        ctx["placed"] = placed_spans(ctx.get("spans"),
                                     profile_start_ns(path))
    return idle_share(ctx["planes"], args, ctx["placed"])
