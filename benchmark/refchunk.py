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
byte range of the sink file, which is read ``SINK_CHUNK`` bytes at a
time: a child holds one chunk, its records and 8 bytes a record, never
its share of the file (a run's sink is 5-20 GB).

Every child also writes ``peak_rss``: the most it held resident at the
instants it holds most (``peakrss.Fullest``: after every chunk of the
sink, and before it saves while everything it made is still there).
"""

from __future__ import annotations

import hashlib
import os
import sys

import numpy as np

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import corpus, peakrss, reference, traffic  # noqa: E402

# one read of the sink file: 16 MiB of 430-820 B records are some 20-40
# thousand ``bytes`` objects, about 40 MB with the chunk itself
SINK_CHUNK = 16 << 20


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


def pool_records(work, first, last, look):
    pool = corpus.load_pool(os.path.join(work, "pool.npz"))
    records = [reference.gelf(pool.line(i)) for i in range(first, last)]
    size = np.fromiter((len(r) if r else 0 for r in records), np.int64,
                       len(records))
    look()
    return {"size": size}


def expect(work, rows, _unused, look):
    pool = corpus.load_pool(os.path.join(work, "pool.npz"))
    records = [reference.gelf(x) for x in written_lines(pool, np.load(rows))]
    fp = (fingerprints([r or b"" for r in records])
          * np.fromiter((r is not None for r in records), np.uint64,
                        len(records)))
    look()
    return {"fp": fp}


def sink_fingerprints(path, first, last, chunk=SINK_CHUNK, look=None):
    """The fingerprint of every record whose terminating NUL lies in
    bytes ``[first, last)`` of the file, in the file's order; an empty
    record between two NULs is one too, and what follows the range's
    last NUL is no record.  Read ``chunk`` bytes at a time: the bytes
    after a chunk's last NUL are carried into the next.  ``look`` is
    called after every chunk, while the chunk is still held."""
    found, carry, left = [], [], last - first
    with open(path, "rb") as f:
        f.seek(first)
        while left > 0:
            data = f.read(min(chunk, left))
            if not data:
                break           # the file ends inside the range
            left -= len(data)
            records = data.split(b"\0")
            carry.append(records.pop())     # b"" after a closing NUL
            if records:
                # the first record began in the chunks before
                records[0] = b"".join(carry[:-1]) + records[0]
                carry = carry[-1:]
                found.append(fingerprints(records))
            if look:
                look()
    return np.concatenate(found) if found else np.zeros(0, np.uint64)


def sink(path, first, last, look):
    return {"fp": sink_fingerprints(path, first, last, look=look)}


def main():
    mode, where, first, last, out = sys.argv[1:6]
    fullest = peakrss.Fullest()
    if mode == "expect":
        made = expect(where, first, last, fullest.look)
    else:
        made = {"pool": pool_records, "sink": sink}[mode](
            where, int(first), int(last), fullest.look)
    np.savez(out, peak_rss=fullest.look(), **made)
    return 0


if __name__ == "__main__":
    sys.exit(main())
