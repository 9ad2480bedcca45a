#!/usr/bin/env python3
"""The sink reader: a process of its own, never JAX, never the program.

    sinktail.py <sink file> <out.npz>

Tails the file the collector writes.  Every read takes one arrival
instant (``time.time()``, the generator's clock: one machine); every
NUL-framed GELF record that the read completes gets that instant, its
``"timestamp"`` (the line's due time, which is how latency needs no
side channel) and the offset at which it ends.  A record split across
two reads belongs to the read that brought its terminator.  A line
``stop`` on fd 0 ends it once the file has stopped growing; the three
columns go to ``<out.npz>`` for the parent to reduce.
"""

from __future__ import annotations

import os
import re
import select
import sys
import time

import numpy as np

READ = 4 << 20
POLL_S = 0.001
TS_RE = re.compile(rb'"timestamp":([0-9]+(?:\.[0-9]+)?)')


class Tail:
    """Feed it what each read returned; it keeps the three columns."""

    def __init__(self):
        self.rest = b""
        self.consumed = 0          # bytes of the file before ``rest``
        self.ts, self.seen, self.end = [], [], []

    def feed(self, data, seen_us):
        data = self.rest + data
        cut = data.rfind(b"\0") + 1
        if not cut:
            self.rest = data
            return
        whole = data[:cut]
        ends = np.flatnonzero(np.frombuffer(whole, np.uint8) == 0)
        stamps = TS_RE.findall(whole)
        if len(stamps) != len(ends):
            # a record with no timestamp, or with two: one at a time
            stamps = []
            for rec in whole[:-1].split(b"\0"):
                m = TS_RE.findall(rec)
                stamps.append(m[-1] if m else b"-1")
        us = np.rint(np.array(stamps, dtype="S32").astype(np.float64) * 1e6)
        self.ts.append(us.astype(np.int64))
        self.seen.append(np.full(len(ends), seen_us, np.int64))
        self.end.append(ends.astype(np.int64) + self.consumed)
        self.consumed += cut
        self.rest = data[cut:]

    def columns(self):
        cat = (lambda xs: np.concatenate(xs) if xs
               else np.zeros(0, np.int64))
        return cat(self.ts), cat(self.seen), cat(self.end)


def main():
    path, out = sys.argv[1], sys.argv[2]
    while not os.path.exists(path):
        time.sleep(0.01)
    tail, stopping, idle = Tail(), False, 0
    with open(path, "rb", buffering=0) as f:
        while not (stopping and idle >= 3):
            data = f.read(READ)
            if data:
                idle = 0
                tail.feed(data, int(time.time() * 1_000_000))
                continue
            idle += 1
            if not stopping and select.select([0], [], [], 0)[0]:
                stopping = True    # ``stop``, or the parent is gone
            time.sleep(0.05 if stopping else POLL_S)
    ts, seen, end = tail.columns()
    np.savez(out, ts=ts, seen=seen, end=end, rest=len(tail.rest))
    return 0


if __name__ == "__main__":
    sys.exit(main())
