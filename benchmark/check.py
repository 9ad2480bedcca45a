"""What decides ``correct``: the sink against what was sent.

Every line the generator wrote is known from its log (pool line, due
time), so the plain reference can say what record each must have become,
or that it must be dropped.  After the window and the drain, in CPU-only
children (``refchunk.py``): the pool goes through the reference once
(which lines a collector must keep), every record of the sink is
fingerprinted, and a sample of the generator's writes, drawn from the
seed and about ``SAMPLE_LINES`` lines in all, is rebuilt with its due
times and goes through the reference line by line.  Compared, each with
the limit 0:

``missing``        well-formed lines written whose due time is on no
                   record of the sink (a line lost); every line
``unexpected``     records of the sink whose due time no written line
                   explains: a duplicate, a junk line kept, an altered
                   timestamp, a record cut short at the file's end;
                   every record
``out_of_order``   places where a connection's own records leave the
                   order they were sent in; every record.  A record's
                   connection is that of the line written with its due
                   time (for a record no line explains: of the last
                   line due before it).  Between connections the order
                   is not defined (upstream's shared queue), so with
                   one stream this is every place where the sink's
                   order departs from the order sent
``bytes_differ``   well-formed lines of the sample for which the sink
                   holds no record that is byte for byte the reference's

The sample keeps the comparison shorter than the window (the reference
does some 60,000 lines a second a core, a drain run writes 5 million):
a fault in one record of every fourth block still meets the sample a
dozen times in a run.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

from . import stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
# a sixth of the lines go through the reference, every record of the
# sink is fingerprinted: about the same work
CORES = max(3, (os.cpu_count() or 4) - 1)
SINK_CHILDREN = CORES // 2
REF_CHILDREN = CORES - SINK_CHILDREN
SAMPLE_LINES = 1_000_000
LIMITS = {"missing": 0, "unexpected": 0, "out_of_order": 0,
          "bytes_differ": 0}


def in_children(work, jobs):
    """Run ``refchunk.py`` once per job, all at once, each in a process
    that cannot see the chip; returns what each wrote, its own
    ``peak_rss`` with it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = []
    for k, job in enumerate(jobs):
        out = os.path.join(work, f"chunk_{k}.npz")
        procs.append((out, subprocess.Popen(
            [sys.executable, os.path.join(HERE, "refchunk.py"),
             *map(str, job), out], stdin=subprocess.DEVNULL, env=env)))
    failed = [p.args for _out, p in procs if p.wait(timeout=300)]
    if failed:
        raise RuntimeError(f"the comparison's children failed: {failed}")
    return [np.load(out) for out, _p in procs]


def even_cuts(weights, parts):
    """Indices that cut ``weights`` into ``parts`` runs of about equal
    sum."""
    total = np.cumsum(weights)
    marks = np.searchsorted(total, total[-1] * np.arange(1, parts) / parts)
    return [0, *(int(m) + 1 for m in marks), len(weights)]


def sink_shares(end, parts=SINK_CHILDREN):
    """The sink file cut at records' starts into ``parts`` byte ranges
    of about as many records each; ``end``: each record's terminator's
    offset."""
    cuts = even_cuts(np.ones(len(end)), parts)
    starts = np.concatenate(([0], end + 1))
    return [(int(starts[a]), int(starts[b]))
            for a, b in zip(cuts[:-1], cuts[1:])]


def written(log):
    """One entry per line written: (pool line, due time, stream), in
    the order of the log."""
    n = log[:, 2]
    row = np.repeat(np.arange(len(log)), n)
    j = np.arange(int(n.sum())) - np.repeat(np.cumsum(n) - n, n)
    return log[row, 1] + j, log[row, 3] + j % traffic.SPREAD_US, log[row, 0]


def out_of_order(ts, due, stream):
    """Places where one stream's records, in the sink's order ``ts``,
    do not rise.  ``due`` and ``stream`` say which stream each line
    written went on; ``due`` arrives sorted."""
    if not len(ts) or not len(due):
        return int((np.diff(ts) <= 0).sum())
    of = stream[np.maximum(np.searchsorted(due, ts, "right") - 1, 0)]
    by_stream = np.argsort(of, kind="stable")
    ts, of = ts[by_stream], of[by_stream]
    return int(((np.diff(ts) <= 0) & (of[1:] == of[:-1])).sum())


class Sink:
    """The sink reader's three columns: each record's ``"timestamp"``
    (the line's due time), the instant its bytes were seen, the offset
    of its terminator."""

    def __init__(self, path, cols):
        self.path = path
        self.ts, self.seen, self.end = cols["ts"], cols["seen"], cols["end"]
        self.rest = int(cols["rest"])
        self.sorted_ts = np.sort(self.ts)


def sample_rows(log, seed):
    """Which writes of the log go through the reference line by line:
    each with the same chance, from the seed, about ``SAMPLE_LINES``
    lines in all."""
    total = int(log[:, 2].sum()) if len(log) else 0
    if total <= SAMPLE_LINES:
        return np.ones(len(log), bool)
    return np.random.default_rng(seed).random(len(log)) < SAMPLE_LINES / total


def compare(work, sink, log, window, seed):
    """Returns the numbers compared, and what the metrics are made of:
    ``attempted`` well-formed lines written in the whole run,
    ``sampled`` of them compared byte for byte, of the well-formed
    lines due in the window the mean ``line_bytes`` and
    ``record_bytes``, and ``children_rss``: the peak resident bytes
    each child says it reached, by what it did."""
    off = np.load(os.path.join(work, "pool.npz"))["line_off"]
    n_pool = len(off) - 1
    cuts = np.linspace(0, n_pool, REF_CHILDREN + 1).astype(int)
    jobs = [("pool", work, a, b) for a, b in zip(cuts[:-1], cuts[1:])]
    n_pool_jobs = len(jobs)
    picked = log[sample_rows(log, seed)]
    if len(picked):
        cuts = even_cuts(picked[:, 2], REF_CHILDREN)
        for k, (a, b) in enumerate(zip(cuts[:-1], cuts[1:])):
            rows = os.path.join(work, f"rows_{k}.npy")
            np.save(rows, picked[a:b])
            jobs.append(("expect", work, rows, "-"))
    n_expect = len(jobs)
    if len(sink.end):
        jobs += [("sink", sink.path, a, b)
                 for a, b in sink_shares(sink.end)]
    parts = in_children(work, jobs)

    def cat(key, some, dtype):
        return (np.concatenate([p[key] for p in some]) if some
                else np.zeros(0, dtype))

    pool_size = cat("size", parts[:n_pool_jobs], np.int64)
    want_fp = cat("fp", parts[n_pool_jobs:n_expect], np.uint64)
    have_fp = cat("fp", parts[n_expect:], np.uint64)
    line, due, stream = written(log)
    by_due = np.argsort(due, kind="stable")
    keep = pool_size[line] > 0
    exp_line, exp_due = line[keep], due[keep]
    at, unexpected = stats.match(np.sort(exp_due, kind="stable"),
                                 sink.sorted_ts)
    want_fp = want_fp[want_fp > 0]
    at_fp, _ = stats.match(np.sort(want_fp), np.sort(have_fp))
    got = {"missing": int((at < 0).sum()),
           "unexpected": unexpected + (1 if sink.rest else 0),
           "out_of_order": out_of_order(sink.ts, due[by_due],
                                        stream[by_due]),
           "bytes_differ": int((at_fp < 0).sum())}
    t0, t1 = window
    cand = exp_line[(exp_due >= t0) & (exp_due < t1)]
    return got, {"attempted": int(keep.sum()), "sampled": len(want_fp),
                 "children_rss": {
                     kind: [int(p["peak_rss"]) for p in some]
                     for kind, some in (("pool", parts[:n_pool_jobs]),
                                        ("expect", parts[n_pool_jobs:n_expect]),
                                        ("sink", parts[n_expect:]))},
                 "line_bytes": float((off[1:] - off[:-1] - 1)[cand].mean())
                 if len(cand) else None,
                 # the pool's record, placeholder timestamp and all: the
                 # stamped one is a byte or two longer or shorter
                 "record_bytes": float(pool_size[cand].mean())
                 if len(cand) else None}
