"""Self time of the program's stage spans, in microseconds a row.

``args``: the stages (``obs/trace.py``: frame, pack, submit, decode,
fetch, encode, sequence, emit).  Over every batch that began in the
window: a span's duration less what the same batch's other spans, on
the same thread and inside it, cover; summed, over the batches' rows.
"""


def self_seconds(span, others):
    inner = sorted((max(o["t0"], span["t0"]), min(o["t1"], span["t1"]))
                   for o in others
                   if o is not span and o["thread"] == span["thread"]
                   and o["t0"] >= span["t0"] and o["t1"] <= span["t1"]
                   and (o["t1"] - o["t0"]) < (span["t1"] - span["t0"]))
    covered, upto = 0.0, span["t0"]
    for a, b in inner:
        if b > upto:
            covered += b - max(a, upto)
            upto = b
    return (span["t1"] - span["t0"]) - covered


def read(ctx, args):
    if not ctx.get("spans"):
        return None
    t0, t1 = (x / 1e6 for x in ctx["window"])
    rows, total = 0, 0.0
    for rec in ctx["spans"]:
        if not t0 <= rec["t0"] + rec["wall"] < t1:
            continue
        rows += rec["rows"]
        total += sum(self_seconds(s, rec["spans"]) for s in rec["spans"]
                     if s["stage"] in args)
    return total / rows * 1e6 if rows else None
