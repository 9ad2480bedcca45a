"""The generator's bookkeeping: what it queues is what its log says, and
a closed loop's due times rise strictly, also where a write wraps round
the pool (which once stamped the second piece over the first)."""

import os

import numpy as np

from benchmark import check, corpus, gen, refchunk, traffic


def make(mix_name, pool_lines):
    mix = traffic.load(mix_name)
    pool = corpus.build_pool(9, pool_lines, mix["corpus"])
    return gen.Generator(mix, pool, status=os.open(os.devnull, os.O_WRONLY))


def test_closed_loop_stamps_rise_strictly_across_a_wrap():
    g = make("drain", 1000)
    base = 1_790_000_000_000_000
    for k in range(7):                  # 7 x 300 lines round a 1000-line pool
        g.queue_lines(300, base + 301 * k)
    log = np.asarray(g.log, np.int64)
    assert len(log) == 9                # two writes wrapped: two rows each
    line, due = check.written(log)
    assert (np.diff(due) > 0).all()
    assert line.tolist() == [k % 1000 for k in range(2100)]
    assert g.scheduled == 2100


def test_the_log_rebuilds_the_bytes_that_were_queued():
    g = make("drain", 4096)
    base = 1_790_000_000_000_000
    g.queue_lines(900, base)
    g.queue_lines(3500, base + 10_000)  # wraps
    queued = b"".join(bytes(view) for view, _row in g.queue)
    log = np.asarray(g.log, np.int64)
    rebuilt = b"\n".join(refchunk.written_lines(g.pool, log)) + b"\n"
    assert rebuilt == queued
    # each line carries its own due time
    _line, due = check.written(log)
    lines = queued.split(b"\n")[:-1]
    assert len(lines) == len(due) == 4400
    junk = corpus.load("loghub_syslog")["junk"]["text"].encode()
    assert all(corpus.stamp_text(int(d)) in l for d, l in zip(due, lines)
               if l != junk)
    assert corpus.TS_PLACEHOLDER not in queued
