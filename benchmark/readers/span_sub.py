"""Time inside the program's sub-spans, in microseconds a row.

``args``: the sub-span stages (``obs/trace.py``: window_wait, h2d,
device_wait, d2h).  Over every batch that began in the window: the
durations of the batch's ``sub`` entries of those stages, summed, over
the batches' rows.  A sub-span is measured around the work itself, on
the thread that does it, so nothing is subtracted.  Where no batch
carries a ``sub`` list (a program from before the sub-spans) there is
nothing to read.
"""


def read(ctx, args):
    if not ctx.get("spans"):
        return None
    t0, t1 = (x / 1e6 for x in ctx["window"])
    rows, total = 0, 0.0
    for rec in ctx["spans"]:
        if "sub" not in rec or not t0 <= rec["t0"] + rec["wall"] < t1:
            continue
        rows += rec["rows"]
        total += sum(s["t1"] - s["t0"] for s in rec["sub"]
                     if s["stage"] in args)
    return total / rows * 1e6 if rows else None
