"""A ratio of the registry's counters over the window.

``args``: ``{"num": [...], "minus": [...], "den": [...], "scale": x}``:
``scale`` x (sum of ``num`` less sum of ``minus``) / sum of ``den``;
``"window_s"`` among ``den`` is the window's length in seconds.
"""


def read(ctx, args):
    c = dict(ctx["counters"], window_s=ctx["window_s"])
    den = sum(c.get(k, 0) for k in args["den"])
    if not den:
        return None
    num = (sum(c.get(k, 0) for k in args["num"])
           - sum(c.get(k, 0) for k in args.get("minus", ())))
    return args.get("scale", 1) * num / den
