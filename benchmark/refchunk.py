#!/usr/bin/env python3
"""One share of the comparison's work, in a process that never sees the
chip (no JAX, nothing of the program).

    refchunk.py pool   <work> <first pool line> <last pool line> <out.npz>
    refchunk.py expect <work> <rows.npy> <unused> <out.npz>
    refchunk.py sink   <sink file> <first byte> <last byte> <out.npz>

``pool``: those lines of the pool, as they stand (placeholder
timestamps), through the plain reference: per line the length of its
record, 0 for a line the reference drops.  ``expect``: the lines that
the given rows of the generator's log say were written, rebuilt from
the pool with their due times, through the plain reference: per line a
fingerprint of its record (8 bytes of BLAKE2b), 0 for a dropped line.
``sink``: the same fingerprint of every NUL-terminated record in that
byte range of the sink file.
"""

from __future__ import annotations

import hashlib
import os
import sys

import numpy as np

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import corpus, reference, traffic  # noqa: E402


def fingerprint(record):
    return int.from_bytes(
        hashlib.blake2b(record, digest_size=8).digest(), "little")


def fingerprints(records):
    return np.fromiter((fingerprint(r) for r in records), np.uint64,
                       len(records))


def written_lines(pool, log):
    """The bytes of every line the log's rows wrote, in their order."""
    blob = b"".join(
        corpus.stamp_block(pool, int(a), int(a + n),
                           traffic.stamps(int(base), int(n)))
        for _src, a, n, base, _done in log)
    return blob.split(b"\n")[:-1]


def pool_records(work, first, last, out):
    pool = corpus.load_pool(os.path.join(work, "pool.npz"))
    records = [reference.gelf(pool.line(i)) for i in range(first, last)]
    np.savez(out, size=np.fromiter((len(r) if r else 0 for r in records),
                                   np.int64, len(records)))


def expect(work, rows, _unused, out):
    pool = corpus.load_pool(os.path.join(work, "pool.npz"))
    records = [reference.gelf(x) for x in written_lines(pool, np.load(rows))]
    np.savez(out,
             fp=fingerprints([r or b"" for r in records])
             * np.fromiter((r is not None for r in records), np.uint64,
                           len(records)))


def sink(path, first, last, out):
    with open(path, "rb") as f:
        f.seek(first)
        data = f.read(last - first)
    np.savez(out, fp=fingerprints(data.split(b"\0")[:-1]))


def main():
    mode, where, first, last, out = sys.argv[1:6]
    if mode == "expect":
        expect(where, first, last, out)
    else:
        {"pool": pool_records, "sink": sink}[mode](
            where, int(first), int(last), out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
