"""The bytes the device-served stages need moved, over the device's busy
seconds, over the chip's peak memory bandwidth, in percent: the share of
the memory roofline at which the device did its part of the cell's work
while it ran.

The work is defined by the traffic and not by the implementation.  For
the rows the program counted as decoded on the device while the profiler
listened (``slice_counters``: ``input_lines`` less ``fallback_rows``):
the bytes of the lines themselves in (the window's mean, not the padded
batch) and ``channel_bytes_per_row`` out, 4 B for each decode channel
the encoder reads.  For the rows encoded on the device as well
(``device_encode_rows``), the bytes of their records besides.  Padding,
spans nobody reads and whatever else a kernel moves do not count, so a
later PR that fuses, pads less or replaces a kernel reads against the
same numerator, and one that moves the encoder onto the chip reads more
work, not less.

``args``: ``channel_bytes_per_row``.
"""


def read(ctx, args):
    p, c = ctx.get("profile"), ctx.get("slice_counters")
    if (not p or not p["busy_s"] or not c or not ctx.get("peaks")
            or not ctx.get("line_bytes")):
        return None
    decoded = c.get("input_lines", 0) - c.get("fallback_rows", 0)
    if decoded <= 0:
        return None
    needed = (decoded * (ctx["line_bytes"] + args["channel_bytes_per_row"])
              + c.get("device_encode_rows", 0) * ctx["record_bytes"])
    return 100.0 * needed / p["busy_s"] / ctx["peaks"]["hbm_bytes_per_s"]
