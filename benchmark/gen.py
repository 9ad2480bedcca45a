#!/usr/bin/env python3
"""The load generator: a process of its own, never JAX, never the program.

    gen.py --traffic <mix> --seed <n> --work <dir> --status-fd <fd>

Builds the pool from the seed and writes the mix to fd 1, the one stream
of a stdin deployment, as fast as the pipe takes it (a closed loop).
Before a line is written its TIMESTAMP is overwritten with its due time
(microseconds, UTC, ``time.time()``).

Commands arrive as lines on fd 0: ``run``, ``pause``, ``stop``; ``burst
<n>`` (while paused) writes ``n`` lines at once and answers ``burst``,
which is how set-up walks the batch shapes.  Replies leave as JSON lines
on ``--status-fd``: ``ready``, ``paused``, ``burst``, ``done`` (each
with the lines written and the lines queued so far).  On ``stop`` every
queued line is written out, fd 1 is closed, and ``<work>/gen_log.npy``
gets one row per write: 0, first pool line, lines, due time of the
first, and the instant the last byte was handed to the kernel: enough to
say what every line's bytes were, and how late the generator ran.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import select
import sys
import time

import numpy as np

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import corpus, traffic  # noqa: E402

FLUSH_DEADLINE_S = 60.0
OUT = 1


def now_us():
    return int(time.time() * 1_000_000)


class Generator:
    def __init__(self, mix, pool, status, fd=OUT):
        self.mix, self.pool, self.status, self.fd = mix, pool, status, fd
        self.at = 0                        # the next line of the pool
        self.queue = collections.deque()   # [memoryview left, log row]
        self.log = []          # [0, first line, lines, base_us, done_us]
        self.lines = self.scheduled = 0
        self.last_stamp = 0
        self.ctl = b""

    def say(self, ev, **kw):
        os.write(self.status, (json.dumps(
            dict(ev=ev, lines=self.lines, scheduled=self.scheduled,
                 t_us=now_us(), **kw)) + "\n"
        ).encode())

    def queue_lines(self, n, base_us):
        """``n`` more lines, stamped from ``base_us``; a run that wraps
        round the pool is two writes, the second stamped on from where
        the first ended."""
        self.scheduled += n
        while n > 0:
            take = min(n, self.pool.n - self.at)
            due = traffic.stamps(base_us, take)
            block = corpus.stamp_block(self.pool, self.at, self.at + take, due)
            self.log.append([0, self.at, take, base_us, 0])
            self.queue.append([memoryview(block), len(self.log) - 1])
            self.at = (self.at + take) % self.pool.n
            n -= take
            base_us += take

    def queue_now(self, n):
        """``n`` lines due now: stamped on from the last such write, so
        that the stream's due times rise strictly."""
        base = max(now_us(), self.last_stamp + 1)
        self.last_stamp = base + n
        self.queue_lines(n, base)

    def pump(self, timeout, watch_ctl=True):
        """Write what the pipe takes for up to ``timeout`` seconds;
        returns the commands that arrived meanwhile."""
        r, w, _ = select.select([0] if watch_ctl else [],
                                [self.fd] if self.queue else [], [],
                                max(timeout, 0.0))
        while w and self.queue:
            view, row = self.queue[0]
            try:
                sent = os.write(self.fd, view[:1 << 20])
            except BlockingIOError:
                break
            if sent < len(view):
                self.queue[0][0] = view[sent:]
                break
            self.queue.popleft()
            self.log[row][4] = now_us()
            self.lines += self.log[row][2]
        cmds = []
        if r:
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
            if running and not self.queue:
                self.queue_now(chunk)
            for cmd in self.pump(0.05):
                if cmd == "run":
                    running = True
                elif cmd == "pause" and running:
                    running = False
                    self.flush()
                    self.say("paused")
                elif cmd.startswith("burst ") and not running:
                    left = int(cmd.split()[1])
                    while left > 0:
                        self.queue_now(min(left, traffic.SPREAD_US))
                        left -= traffic.SPREAD_US
                    self.flush()
                    self.say("burst")
                elif cmd == "stop":
                    stopping = True
        self.flush()

    def flush(self):
        lines, deadline = self.lines, time.time() + FLUSH_DEADLINE_S
        while self.queue:
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
    ap.add_argument("--pool-lines", type=int, default=None,
                    help="rehearsals only: a smaller pool")
    args = ap.parse_args()
    mix = traffic.load(args.traffic)
    if args.pool_lines:
        mix["pool_lines"] = args.pool_lines
    pool = corpus.build_pool(args.seed, mix["pool_lines"], mix["corpus"])
    corpus.save_pool(pool, os.path.join(args.work, "pool.npz"))
    os.set_blocking(OUT, False)
    gen = Generator(mix, pool, args.status_fd)
    try:
        gen.run()
    finally:
        os.close(OUT)
        log = np.asarray(gen.log, np.int64).reshape(-1, 5)
        np.save(os.path.join(args.work, "gen_log.npy"), log)
    gen.say("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
