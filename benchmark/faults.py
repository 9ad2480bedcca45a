"""Ways to break the timed path underneath a run, to show that the
comparison sees it (``run.py --break <name>``; never part of a measured
run, and not in ``BENCHMARK.json``'s command).

Each acts where a record is produced: on the block the handler hands
to the sink's queue, one block in ``EVERY``.

``coarse_ts``  the control: every record's ``"timestamp"`` printed with
               three decimals, the cheaper text a later PR might be
               tempted by.  Breaks "bytes as the scalar pipeline writes
               them".
``drop``       one record left out (breaks "exactly once")
``dup``        one record written twice (breaks "exactly once")
``alter``      one byte of one record's message changed
``half``       the second half of the block left out
``swap``       two neighbouring records exchanged (breaks the order a
               one-stream deployment promises; where a block holds the
               records of several connections the two may be two
               senders', and the exchange legal)
``reverse``    the block's records back to front: whatever the batching,
               some connection's own order is broken (the order control
               of a deployment with many connections)
"""

from __future__ import annotations

import re

import numpy as np

EVERY = 4
TS_RE = re.compile(rb'("timestamp":[0-9]+\.[0-9]{3})[0-9]*')


def _records(item):
    from flowgger_tpu.block import EncodedBlock

    if isinstance(item, EncodedBlock):
        return list(item.iter_framed())
    return None


def _block(like, records):
    from flowgger_tpu.block import EncodedBlock

    bounds = np.zeros(len(records) + 1, np.int64)
    np.cumsum([len(r) for r in records], out=bounds[1:])
    return EncodedBlock(b"".join(records), bounds, None, like.suffix_len,
                        like.ack_cb)


def coarse_ts(recs):
    return [TS_RE.sub(rb"\1", r) for r in recs]


def drop(recs):
    return recs[:len(recs) // 2] + recs[len(recs) // 2 + 1:]


def dup(recs):
    k = len(recs) // 2
    return recs[:k + 1] + recs[k:]


def alter(recs):
    k = len(recs) // 2
    at = recs[k].index(b'"short_message":"') + 17
    flipped = bytes([recs[k][at] ^ 1])
    return recs[:k] + [recs[k][:at] + flipped + recs[k][at + 1:]] \
        + recs[k + 1:]


def half(recs):
    return recs[:(len(recs) + 1) // 2]


def swap(recs):
    k = len(recs) // 2
    if k < 1:
        return recs
    return recs[:k - 1] + [recs[k], recs[k - 1]] + recs[k + 1:]


def reverse(recs):
    return recs[::-1]


FAULTS = {"coarse_ts": coarse_ts, "drop": drop, "dup": dup, "alter": alter,
          "half": half, "swap": swap, "reverse": reverse}


def install(pipe, name):
    """Wrap the queue between handler and sink of ``pipe``."""
    fault, put, seen = FAULTS[name], pipe.tx.put, [0]
    every = 1 if name == "coarse_ts" else EVERY

    def broken_put(item, *a, **kw):
        recs = _records(item)
        if recs:
            # flowcheck: disable=FC02 -- a counter of blocks for a deliberate fault; which block in EVERY it hits does not matter
            seen[0] += 1
            if seen[0] % every == 0:
                item = _block(item, fault(recs))
        return put(item, *a, **kw)

    pipe.tx.put = broken_put
