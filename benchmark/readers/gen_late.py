"""How late the generator ran: 99th percentile, over its writes that
were due in the window, of (last byte handed to the kernel) - (due),
in milliseconds.  A starved generator must not read as a fast
collector."""

from benchmark import stats


def read(ctx, args):
    rows = ctx["gen_rows"]
    if not len(rows):
        return None
    return stats.percentile((rows[:, 4] - rows[:, 3]) / 1000.0, 99)
