#!/usr/bin/env python3
"""The comparison's sink children of two checkouts over one kept sink:
seconds, peak resident memory, and whether the fingerprints agree.

    python3 benchmark/chip/sink_children.py <work dir of a --keep-work run> <checkout>...

Cuts the sink as ``check.compare`` does (``check.sink_shares``), runs each checkout's ``benchmark/refchunk.py sink``
over every share, all at once as ``in_children`` does, and prints per
checkout the wall seconds, the children's peaks summed and the largest
(``peakrss.wait``: this script holds little, so the kernel's count is
the child's), and whether every share's fingerprints are the first
checkout's.  How PR 33 held the chunked read against the whole read on
the chip's machine.
"""

import os
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT

from benchmark import check, peakrss  # noqa: E402


def main():
    work, checkouts = sys.argv[1], sys.argv[2:]
    path = os.path.join(work, "sink.gelf")
    end = np.load(os.path.join(work, "sink.npz"))["end"]
    shares = check.sink_shares(end)
    print(f"sink: {os.path.getsize(path)} bytes, {len(end)} records, "
          f"{len(shares)} shares", flush=True)
    first = None
    for root in checkouts:
        t0, procs = time.time(), []
        for k, (a, b) in enumerate(shares):
            out = os.path.join(work, f"ab_{k}.npz")
            procs.append((out, subprocess.Popen(
                [sys.executable, os.path.join(root, "benchmark",
                                              "refchunk.py"),
                 "sink", path, str(a), str(b), out],
                stdin=subprocess.DEVNULL,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))))
        ended = [peakrss.wait(p, 600) for _out, p in procs]
        took = time.time() - t0
        fps = [np.load(out)["fp"] for out, _p in procs]
        first = first or fps
        peaks = [rss for _rc, rss in ended]
        print(f"{root}: exit {[rc for rc, _ in ended]}, {took:.1f}s, "
              f"children sum {peakrss.gb(sum(peaks))}, largest "
              f"{peakrss.gb(max(peaks))}; fingerprints as the first's: "
              f"{all((a == b).all() for a, b in zip(first, fps))} "
              f"({sum(map(len, fps))})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
