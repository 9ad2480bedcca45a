#!/usr/bin/env python3
"""The load generator: a process of its own, never JAX, never the program.

    gen.py --traffic <mix> --seed <n> --work <dir> --status-fd <fd> [--sockets]

Builds the pool from the seed and writes the mix as fast as it is taken
(a closed loop): to fd 1, the one stream of a stdin deployment, or, with
``--sockets``, to the mix's ``sources`` TCP connections to the
collector's listener, with equal shares: the next chunk goes to
whichever connection has nothing queued.  Before a line is written its
TIMESTAMP is overwritten with its due time (microseconds, UTC,
``time.time()``); due times are unique and rise strictly in the order
the lines are queued, over all the streams together, which is what lets
the comparison find a record's line, and so its connection, by its due
time alone.

Commands arrive as lines on fd 0: ``run``, ``pause``, ``stop``;
``connect <host:port>`` (with ``--sockets``, once, before any line)
opens the connections and answers ``connected``; ``burst <n>`` (while
paused) writes ``n`` lines at once, split evenly over the streams, and
answers ``burst``, which is how set-up walks the batch shapes.  Replies
leave as JSON lines on ``--status-fd``: ``ready``, ``connected``,
``paused``, ``burst``, ``done`` (each with the lines written and the
lines queued so far).  On ``stop`` every queued line is written out,
every stream is closed, and ``<work>/gen_log.npy`` gets one row per
write: the stream's index, first pool line, lines, due time of the
first, and the instant the last byte was handed to the kernel: enough to
say what every line's bytes were, on which connection it went, and how
late the generator ran.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import select
import socket
import sys
import time

import numpy as np

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import corpus, traffic  # noqa: E402

FLUSH_DEADLINE_S = 60.0
OUT = 1
# a sender's socket holds a chunk or so (the kernel doubles this), not
# the 4 MB that loopback's autotuning grows it to: what stands in the
# sockets has to drain at every pause of set-up and at the stop, and 64
# times 4 MB is a million and a half lines
SNDBUF = 1 << 16


def now_us():
    return int(time.time() * 1_000_000)


class Generator:
    def __init__(self, mix, pool, status, fds=(OUT,)):
        self.mix, self.pool, self.status = mix, pool, status
        self.socks = []
        self.poller = select.poll()
        self.poller.register(0, select.POLLIN)
        self.ctl_watched = True
        self.open(fds)
        self.at = 0                        # the next line of the pool
        self.log = []     # [stream, first line, lines, base_us, done_us]
        self.lines = self.scheduled = 0
        self.last_stamp = 0
        self.ctl = b""

    def open(self, fds):
        """The streams: one queue of [memoryview left, log row] each."""
        self.fds = list(fds)
        self.queues = [collections.deque() for _ in self.fds]
        self.stream_of = {fd: k for k, fd in enumerate(self.fds)}

    def connect(self, where):
        """The mix's ``sources`` connections to the listener, held to
        the end."""
        host, _, port = where.rpartition(":")
        for _ in range(self.mix["sources"]):
            sock = socket.socket()
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, SNDBUF)
            sock.settimeout(30.0)
            sock.connect((host, int(port)))
            sock.setblocking(False)
            self.socks.append(sock)
        self.open(s.fileno() for s in self.socks)

    def close(self):
        if self.socks:
            for sock in self.socks:
                sock.close()
        else:
            for fd in self.fds:
                os.close(fd)

    def say(self, ev, **kw):
        os.write(self.status, (json.dumps(
            dict(ev=ev, lines=self.lines, scheduled=self.scheduled,
                 t_us=now_us(), **kw)) + "\n"
        ).encode())

    def queue_lines(self, n, base_us, stream=0):
        """``n`` more lines for one stream, stamped from ``base_us``; a
        run that wraps round the pool is two writes, the second stamped
        on from where the first ended."""
        self.scheduled += n
        queue = self.queues[stream]
        if not queue:
            self.poller.register(self.fds[stream], select.POLLOUT)
        while n > 0:
            take = min(n, self.pool.n - self.at)
            due = traffic.stamps(base_us, take)
            block = corpus.stamp_block(self.pool, self.at, self.at + take, due)
            self.log.append([stream, self.at, take, base_us, 0])
            queue.append([memoryview(block), len(self.log) - 1])
            self.at = (self.at + take) % self.pool.n
            n -= take
            base_us += take

    def queue_now(self, n, stream=0):
        """``n`` lines due now: stamped on from the last such write on
        any stream, so that the due times of all of them rise
        strictly."""
        base = max(now_us(), self.last_stamp + 1)
        self.last_stamp = base + n
        self.queue_lines(n, base, stream)

    def queue_burst(self, n):
        """``n`` lines at once, split evenly over the streams."""
        each, more = divmod(n, len(self.fds))
        for stream in range(len(self.fds)):
            left = each + (stream < more)
            while left > 0:
                self.queue_now(min(left, traffic.SPREAD_US), stream)
                left -= traffic.SPREAD_US

    def write(self, stream):
        """What that stream takes of its queue, now."""
        queue, fd = self.queues[stream], self.fds[stream]
        while queue:
            view, row = queue[0]
            try:
                sent = os.write(fd, view[:1 << 20])
            except BlockingIOError:
                return
            if sent < len(view):
                queue[0][0] = view[sent:]
                return
            queue.popleft()
            self.log[row][4] = now_us()
            self.lines += self.log[row][2]
        self.poller.unregister(fd)

    def pump(self, timeout, watch_ctl=True):
        """Write what the streams take for up to ``timeout`` seconds;
        returns the commands that arrived meanwhile."""
        if watch_ctl != self.ctl_watched:
            if watch_ctl:
                self.poller.register(0, select.POLLIN)
            else:
                self.poller.unregister(0)
            self.ctl_watched = watch_ctl
        cmds = []
        for fd, _event in self.poller.poll(max(timeout, 0.0) * 1000.0):
            if fd in self.stream_of:
                self.write(self.stream_of[fd])
                continue
            data = os.read(0, 4096)
            if not data:
                return ["stop"]   # the parent is gone
            *done, self.ctl = (self.ctl + data).split(b"\n")
            cmds = [c.decode() for c in done]
        return cmds

    def run(self):
        running = stopping = False
        chunk = min(int(self.mix["chunk_lines"]), traffic.SPREAD_US)
        self.say("ready")
        while not stopping:
            if running:
                for stream, queue in enumerate(self.queues):
                    if not queue:
                        self.queue_now(chunk, stream)
            for cmd in self.pump(0.05):
                if cmd == "run":
                    running = True
                elif cmd == "pause" and running:
                    running = False
                    self.flush()
                    self.say("paused")
                elif cmd.startswith("burst ") and not running and self.fds:
                    self.queue_burst(int(cmd.split()[1]))
                    self.flush()
                    self.say("burst")
                elif cmd.startswith("connect ") and not self.fds:
                    self.connect(cmd.split()[1])
                    self.say("connected", sources=len(self.fds))
                elif cmd == "stop":
                    stopping = True
        self.flush()

    def flush(self):
        lines, deadline = self.lines, time.time() + FLUSH_DEADLINE_S
        while any(self.queues):
            if self.lines != lines:
                lines, deadline = self.lines, time.time() + FLUSH_DEADLINE_S
            elif time.time() > deadline:
                raise RuntimeError(
                    f"the collector took none of the queued lines for "
                    f"{FLUSH_DEADLINE_S:.0f}s")
            self.pump(0.05, watch_ctl=False)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--work", required=True)
    ap.add_argument("--status-fd", type=int, required=True)
    ap.add_argument("--sockets", action="store_true",
                    help="the streams are the mix's TCP connections, "
                         "opened on the command connect <host:port>")
    ap.add_argument("--pool-lines", type=int, default=None,
                    help="rehearsals only: a smaller pool")
    args = ap.parse_args()
    mix = traffic.load(args.traffic)
    if args.pool_lines:
        mix["pool_lines"] = args.pool_lines
    pool = corpus.build_pool(args.seed, mix["pool_lines"], mix["corpus"])
    corpus.save_pool(pool, os.path.join(args.work, "pool.npz"))
    if args.sockets:
        gen = Generator(mix, pool, args.status_fd, fds=())
    elif mix["sources"] != 1:
        raise SystemExit(f"gen.py: {mix['sources']} sources need --sockets; "
                         "fd 1 is one stream")
    else:
        os.set_blocking(OUT, False)
        gen = Generator(mix, pool, args.status_fd)
    try:
        gen.run()
    finally:
        gen.close()
        log = np.asarray(gen.log, np.int64).reshape(-1, 5)
        np.save(os.path.join(args.work, "gen_log.npy"), log)
    gen.say("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
