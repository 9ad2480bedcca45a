#!/usr/bin/env python3
"""The load generator alone: is it the ceiling of a cell?

    python3 benchmark/chip/gen_alone.py <mix> <seed> <seconds>

Not a cell and never part of a measured run: ``gen.py`` as ``run.py``
starts it, writing the mix's connections (or its one pipe) into a
reader that only counts newlines, no collector anywhere.  Prints the
lines a second it wrote over ``seconds`` (after a second to get going)
and how late it ran by its own log.  A cell measures the collector only
while this stands well above the cell's ``lines_per_s`` (PERF.md says
by how much it has to).  Needs no chip: run it on the chip's host
(``chiprun -- python3 benchmark/chip/gen_alone.py fleet_catchup 7 20``)
for a number that can stand beside the cell's.
"""

from __future__ import annotations

import json
import os
import selectors
import shutil
import socket
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[0] = os.path.dirname(BENCH)

from benchmark import stats, traffic  # noqa: E402

READ = 1 << 20


def main():
    name, seed, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    mix = traffic.load(name)
    work = os.path.join(BENCH, "work", f"gen-alone-{os.getpid()}")
    os.makedirs(work)
    status_r, status_w = os.pipe()
    listener = socket.create_server(("127.0.0.1", 0))
    over_tcp = mix["sources"] > 1
    gen = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "gen.py"), "--traffic", name,
         "--seed", str(seed), "--work", work, "--status-fd", str(status_w)]
        + (["--sockets"] if over_tcp else []),
        stdin=subprocess.PIPE, pass_fds=(status_w,),
        stdout=subprocess.DEVNULL if over_tcp else subprocess.PIPE)
    os.close(status_w)
    answers = os.fdopen(status_r, "rb")

    def tell(word):
        gen.stdin.write(word.encode() + b"\n")
        gen.stdin.flush()

    def answer(ev):
        msg = json.loads(answers.readline())
        assert msg["ev"] == ev, msg
        return msg

    try:
        answer("ready")
        sel = selectors.DefaultSelector()
        if over_tcp:
            tell(f"connect 127.0.0.1:{listener.getsockname()[1]}")
            for _ in range(mix["sources"]):
                conn, _peer = listener.accept()
                conn.setblocking(False)
                sel.register(conn, selectors.EVENT_READ)
            answer("connected")
        else:
            os.set_blocking(gen.stdout.fileno(), False)
            sel.register(gen.stdout, selectors.EVENT_READ)
        buf = bytearray(READ)
        lines = at_start = 0
        tell("run")
        t_begin = time.time()
        t0 = t1 = None
        told_stop = False
        while sel.get_map():
            for key, _ev in sel.select(0.05):
                f = key.fileobj
                n = (f.recv_into(buf) if over_tcp
                     else os.readv(f.fileno(), [buf]))
                if n:
                    lines += buf.count(b"\n", 0, n)
                else:
                    sel.unregister(f)
            now = time.time()
            if t0 is None and now - t_begin >= 1.0:
                t0, at_start = now, lines
            if t0 is not None and not told_stop and now - t0 >= seconds:
                t1, at_stop = now, lines
                tell("stop")
                told_stop = True
        done = answer("done")
        rc = gen.wait(60)
        log = np.load(os.path.join(work, "gen_log.npy"))
        rows = log[(log[:, 3] >= t0 * 1e6) & (log[:, 3] < t1 * 1e6)]
        late = (rows[:, 4] - rows[:, 3]) / 1000.0
        print(json.dumps({
            "mix": name, "sources": mix["sources"], "seconds": t1 - t0,
            "lines_per_s": (at_stop - at_start) / (t1 - t0),
            "lines_read": lines, "lines_written": done["lines"],
            "late_p50_ms": stats.percentile(late, 50),
            "late_p99_ms": stats.percentile(late, 99),
            "cores": os.cpu_count(), "gen_exit": rc}))
        return 0 if rc == 0 and lines == done["lines"] else 1
    finally:
        if gen.poll() is None:
            gen.kill()
            gen.wait()
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
