"""The generator's bookkeeping: what it queues is what its log says, and
a closed loop's due times rise strictly, also where a write wraps round
the pool (which once stamped the second piece over the first), and over
all the connections of a fleet together."""

import os
import socket

import numpy as np

from benchmark import check, corpus, gen, refchunk, traffic


def make(mix_name, pool_lines, fds=(gen.OUT,)):
    mix = traffic.load(mix_name)
    pool = corpus.build_pool(9, pool_lines, mix["corpus"])
    return gen.Generator(mix, pool, fds=fds,
                         status=os.open(os.devnull, os.O_WRONLY))


def queued(g, stream=0):
    return b"".join(bytes(view) for view, _row in g.queues[stream])


def test_closed_loop_stamps_rise_strictly_across_a_wrap():
    g = make("drain", 1000)
    base = 1_790_000_000_000_000
    for k in range(7):                  # 7 x 300 lines round a 1000-line pool
        g.queue_lines(300, base + 301 * k)
    log = np.asarray(g.log, np.int64)
    assert len(log) == 9                # two writes wrapped: two rows each
    line, due, stream = check.written(log)
    assert (np.diff(due) > 0).all() and not stream.any()
    assert line.tolist() == [k % 1000 for k in range(2100)]
    assert g.scheduled == 2100


def test_the_log_rebuilds_the_bytes_that_were_queued():
    g = make("drain", 4096)
    base = 1_790_000_000_000_000
    g.queue_lines(900, base)
    g.queue_lines(3500, base + 10_000)  # wraps
    sent = queued(g)
    log = np.asarray(g.log, np.int64)
    rebuilt = b"\n".join(refchunk.written_lines(g.pool, log)) + b"\n"
    assert rebuilt == sent
    # each line carries its own due time
    _line, due, _stream = check.written(log)
    lines = sent.split(b"\n")[:-1]
    assert len(lines) == len(due) == 4400
    junk = corpus.load("loghub_syslog")["junk"]["text"].encode()
    assert all(corpus.stamp_text(int(d)) in l for d, l in zip(due, lines)
               if l != junk)
    assert corpus.TS_PLACEHOLDER not in sent


def fleet(pool_lines=4096):
    """A generator of the fleet's 64 streams, on fds nothing is written
    to: what is queued is looked at."""
    g = make("fleet_catchup", pool_lines, fds=range(100, 164))
    g.poller = type("NoPoll", (), {"register": lambda *a: None,
                                   "unregister": lambda *a: None})()
    return g


def test_a_fleets_stamps_are_unique_and_rise_over_all_connections():
    g = fleet()
    assert len(g.queues) == g.mix["sources"] == 64
    for _round in range(3):             # a closed loop's turns, in order
        for stream in range(64):
            g.queue_now(512, stream)
    g.queue_burst(200)
    log = np.asarray(g.log, np.int64)
    _line, due, stream = check.written(log)
    assert len(due) == 3 * 64 * 512 + 200 == g.scheduled
    assert (np.diff(due) > 0).all()     # unique, and rising as queued
    assert sorted(set(stream.tolist())) == list(range(64))


def test_the_log_rebuilds_each_connections_bytes():
    g = fleet()
    for stream in (5, 0, 63, 5, 17, 5):
        g.queue_now(512, stream)
    g.queue_burst(6400)
    log = np.asarray(g.log, np.int64)
    for stream in range(64):
        mine = log[log[:, 0] == stream]
        rebuilt = b"".join(l + b"\n"
                           for l in refchunk.written_lines(g.pool, mine))
        assert rebuilt == queued(g, stream)
    assert queued(g, 5).count(b"\n") == 3 * 512 + 100


def test_a_burst_splits_evenly():
    g = fleet()
    for n, first, last in ((200, 4, 3),         # 8 connections get 4
                           (25600, 400, 400),
                           (70000, 1094, 1093)):  # more than one write each
        before = len(g.log)
        g.queue_burst(n)
        log = np.asarray(g.log[before:], np.int64)
        per = np.bincount(log[:, 0], weights=log[:, 2], minlength=64)
        assert per.sum() == n and per.max() - per.min() <= 1
        assert per[0] == first and per[63] == last
        assert log[:, 2].max() <= traffic.SPREAD_US


def test_one_stream_writes_todays_bytes_and_log(monkeypatch):
    """With one source and fd 1's stand-in, the bytes written and the
    log's rows are those the one-stream generator always made: stamped
    from the clock, or on from the last write where the clock stands."""
    clock = iter([1_790_000_000_000_000, 1_790_000_000_000_100,   # queue,
                  1_790_000_000_200_000] + [1_790_000_000_300_000] * 9)
    monkeypatch.setattr(gen, "now_us", lambda: next(clock))
    r, w = os.pipe()
    os.set_blocking(w, False)
    g = make("drain", 2048, fds=(w,))
    g.poller.unregister(0)
    g.ctl_watched = False
    for _ in range(3):
        g.queue_now(100)
    while any(g.queues):
        g.pump(0.0, watch_ctl=False)
    os.close(w)
    with os.fdopen(r, "rb") as f:
        got = f.read()
    bases = [1_790_000_000_000_000, 1_790_000_000_000_101,
             1_790_000_000_200_000]
    assert got == b"".join(
        corpus.stamp_block(g.pool, 100 * k, 100 * k + 100,
                           traffic.stamps(b, 100))
        for k, b in enumerate(bases))
    assert [row[:4] for row in g.log] == [[0, 100 * k, 100, b]
                                          for k, b in enumerate(bases)]
    assert all(row[4] == 1_790_000_000_300_000 for row in g.log)
    assert g.lines == g.scheduled == 300


def test_connections_carry_what_the_log_says(monkeypatch):
    """Through real sockets: four connections to a listener, a burst
    and some turns of the closed loop, every socket closed at the end."""
    srv = socket.create_server(("127.0.0.1", 0))
    mix = dict(traffic.load("fleet_catchup"), sources=4)
    pool = corpus.build_pool(9, 2048, mix["corpus"])
    g = gen.Generator(mix, pool, os.open(os.devnull, os.O_WRONLY), fds=())
    g.poller.unregister(0)
    g.ctl_watched = False
    g.connect(f"127.0.0.1:{srv.getsockname()[1]}")
    peers = [srv.accept()[0] for _ in range(4)]
    by_port = {p.getpeername()[1]: p for p in peers}
    peers = [by_port[s.getsockname()[1]] for s in g.socks]
    for p in peers:
        p.setblocking(False)
    got = [b""] * 4

    def drain_peers():
        for k, p in enumerate(peers):
            try:
                while True:
                    data = p.recv(1 << 20)
                    if not data:
                        break
                    got[k] += data
            except BlockingIOError:
                pass

    g.queue_burst(1001)
    for _turn in range(5):
        for stream, queue in enumerate(g.queues):
            if not queue:
                g.queue_now(512, stream)
        g.pump(0.01, watch_ctl=False)
        drain_peers()
    while any(g.queues):
        g.pump(0.01, watch_ctl=False)
        drain_peers()
    g.close()
    for p in peers:
        p.setblocking(True)
        p.settimeout(10.0)
    drain_peers()
    log = np.asarray(g.log, np.int64)
    assert (log[:, 4] > 0).all() and g.lines == g.scheduled
    for k in range(4):
        mine = log[log[:, 0] == k]
        assert got[k] == b"".join(
            l + b"\n" for l in refchunk.written_lines(pool, mine))
    assert (np.diff(check.written(log)[1]) > 0).all()
