"""The device's idle share of the profiler's slice, in percent: 1 less
(union of the intervals in which an op runs on the TPU plane) over the
slice."""


def read(ctx, args):
    p = ctx.get("profile")
    if not p or not p["devices"] or not p["window_s"]:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
