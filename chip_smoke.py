#!/usr/bin/env python3
"""chip_smoke.py: the served rfc5424 -> GELF path, once, on the chip.

The quickest proof that the program still starts and serves on the
hardware it is written for.  One process, which is the process that
holds the chip.  With no arguments (one TPU chip) it

1. fails at once unless ``jax.devices()[0].platform == "tpu"``;
2. builds ``native/libflowgger_host.so`` from its committed source (or
   says that the run takes the pure-Python pack tier);
3. makes an RFC 5424 corpus from ``--seed`` (hosts, apps, 0-3 SD
   elements of 1-6 params, escapes, a length tail past the 512 B
   ``tpu_max_line_len``);
4. **stdin run**: drives the corpus through ``Pipeline`` exactly as
   ``python -m flowgger_tpu`` builds it — ``rfc5424_tpu`` in, line
   framing, GELF out, file sink, **no tier, economics or watchdog key**
   — over a pipe on fd 0.  On the one handler: windows of
   ``--batches`` full default batches and a tail, each followed by the
   wait for the compile workers to land, until a window after the
   first compiles no program — that one is the measured window, every
   one before it was warm-up (cold compiles and their watchdog declines
   belong there; compile seconds per program are printed as set-up);
   then EOF and drain.  What a declined tier compiles when its cooldown
   is over makes that window warm-up too;
5. **tcp run**: the same route over ``type = "tcp"`` from 8 concurrent
   connections into the one shared ``BatchHandler``, warmed and
   measured in the same way on that handler;
6. runs the scalar ``format = "rfc5424"`` pipeline (no JAX) on the same
   bytes — every window, warm-up included — and compares the sinks:
   byte for byte for stdin, as a multiset of records for tcp;
7. prints what the registry counted in each measured window — rows
   decoded on the device, rows also encoded there, batches per encode
   route, declines, breaker, compiles — and exits non-zero if the
   device decoded under 95% of the rows, the breaker left ``closed``, a
   compile or framing decline or a device error was counted, a
   program was compiled inside the window (the fetch driver's
   per-length ``dynamic_slice`` programs excepted: they are printed),
   the bytes differ, or any phase raised.  Which *encoder* finishes a
   device-decoded batch (fused, split device, host block) is the route
   economics' measured choice: its share is printed beside the 95% the
   issue asked of the device tiers, and is not enforced (PERF.md,
   PR 22).  No rate is printed: this is not a benchmark.

``--chips 4`` runs only the four-chip phase: the stdin stream once with
``input.tpu_lanes = 1`` and once with lanes left to resolve (one per
local device), same bytes out, rows on every lane, each lane on its own
device.  It is held to placement, bytes and a closed breaker; one
warm-up window, and what the measured window declined is printed.

``--rehearse`` is for a sandbox with no chip: it accepts whatever
device JAX has (the last line then names that device, never a TPU),
shrinks the stream, and reports the tier shares without holding the run
to them (on the CPU backend the route economics rightly prefer the host
encoders).  ``--batch-size`` shrinks the batch for such a rehearsal.

Last line of stdout: ``{"ok": true, "device": {"platform": ..., "kind":
..., "count": ...}}`` with the device as JAX reports it.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import random
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TCP_CONNS = 8
DEVICE_SHARE_MIN = 0.95
WAIT_S = 600.0
WINDOWS_MAX = 10    # windows a handler gets to show one with no cold program

# what a measured window may not count (any of these > 0 fails the run)
MUST_BE_ZERO = ("device_encode_compile_declines", "framing_declines",
                "breaker_trips", "device_decode_errors",
                "drain_flush_errors", "output_errors")
# printed beside them; a fused fallback is tolerated only as the 5% rule
# applied to one small ragged batch (== a counted device_encode_declined)
COUNTERS = ("input_lines", "output_written", "batches", "fused_rows",
            "device_encode_rows", "device_encode_scalar_rows",
            "encode_route_fused", "encode_route_device",
            "encode_route_host", "fallback_rows", "framing_rows",
            "fused_fallbacks", "device_encode_declined",
            "compile_cache_hits", "compile_cache_misses") + MUST_BE_ZERO


def say(*a):
    print(*a, flush=True)


# ---------------------------------------------------------------------------
# corpus

HOSTS = [f"{n}{i:02d}.{dc}.example.net"
         for dc in ("ams", "iad", "sin") for n in ("web", "db", "edge", "mq")
         for i in (1, 2, 7)]
APPS = ["nginx", "postgres", "sshd", "kernel", "cron", "haproxy", "app-api",
        "auth-svc", "billing", "systemd", "dockerd", "kubelet"]
SD_IDS = ["origin@32473", "meta@32473", "exampleSDID@32473", "timeQuality",
          "trace@41058", "req@41058"]
# distinct within their first 8 bytes (the device encoder orders keys
# by an 8-byte prefix and sends ties to the host path)
SD_NAMES = ["iut", "eventSource", "eventID", "seq", "tzKnown", "isSynced",
            "user", "latency", "code", "span", "parent", "sampled", "ip",
            "method", "path", "bytes", "tenant", "zone"]
WORDS = ("connection accepted from upstream closed by peer request completed "
         "in ms status user session opened for root failed password invalid "
         "from port ssh2 GET POST /api/v1/items /healthz HTTP/1.1 200 404 502 "
         "worker started stopping cache miss hit evicted key queue depth "
         "retrying backoff exceeded timeout while reading response header "
         "checkpoint complete wrote buffers sync total slow query duration "
         "rows=42 plan=seqscan oom-killer invoked gfp_mask order=0 "
         "segfault at ip sp error 4 in libc.so.6").split()
N_SD = ((0, 0.52), (1, 0.40), (2, 0.07), (3, 0.01))
N_PARAMS = ((1, 0.50), (2, 0.30), (3, 0.13), (4, 0.045), (5, 0.015),
            (6, 0.01))
# message bytes: most lines ~100-350 B in all; about 1% long enough that
# their GELF (which carries the line twice) outgrows the device encoder's
# row, and under 1% past the 512 B tpu_max_line_len — both the scalar
# splice's by design
MSG_LEN = (((20, 120), 0.78), ((120, 250), 0.20), ((250, 400), 0.012),
           ((520, 900), 0.008))


def _pick(rng, table):
    x = rng.random()
    for v, p in table:
        x -= p
        if x < 0:
            return v
    return table[-1][0]


def _message(rng, want):
    out, n = [], 0
    while n < want:
        w = rng.choice(WORDS)
        out.append(w)
        n += len(w) + 1
    return " ".join(out)[:want].rstrip() or "-"


def gen_lines(rng, n):
    """``n`` RFC 5424 lines (bytes, no terminator) and how many of them
    a collector must drop (plain junk, 2 in 10,000)."""
    out, junk = [], 0
    for _ in range(n):
        r = rng.random()
        if r < 0.0002:
            junk += 1
            out.append(b"-- MARK -- not a syslog line")
            continue
        frac = rng.choice(("", ".%03d" % rng.randrange(1000),
                           ".%06d" % rng.randrange(1000000)))
        tz = rng.choice(("Z", "Z", "Z", "+02:00", "-07:00", "+05:30"))
        ts = "2026-%02d-%02dT%02d:%02d:%02d%s%s" % (
            rng.randrange(1, 13), rng.randrange(1, 29), rng.randrange(24),
            rng.randrange(60), rng.randrange(60), frac, tz)
        procid = rng.choice(("-", str(rng.randrange(1, 65536))))
        msgid = rng.choice(("-", "ID%d" % rng.randrange(100), "TCPIN", "AUDIT"))
        nsd = _pick(rng, N_SD)
        if nsd == 0:
            sd = "-"
        else:
            names = rng.sample(SD_NAMES, len(SD_NAMES))
            parts = []
            for sid in rng.sample(SD_IDS, nsd):
                kv = []
                for _k in range(_pick(rng, N_PARAMS)):
                    val = rng.choice(WORDS) + str(rng.randrange(1000))
                    if rng.random() < 0.003:
                        # RFC 5424 value escapes: the scalar path by design
                        val += rng.choice(('\\"q\\"', "\\\\srv", "a\\]b"))
                    kv.append(f'{names.pop()}="{val}"')
                parts.append(f"[{sid} {' '.join(kv)}]")
            sd = "".join(parts)
        msg = _message(rng, rng.randint(*_pick(rng, MSG_LEN)))
        r = rng.random()
        if r < 0.08:      # JSON escapes the device encoder handles
            msg = msg.replace(" ", ' "', 1).replace(" in ", '" in\\ ', 1)
        elif r < 0.0815:  # non-ASCII: the scalar path by design
            msg += " café ☕ ünïcode"
        line = (f"<{rng.randrange(192)}>1 {ts} {rng.choice(HOSTS)} "
                f"{rng.choice(APPS)} {procid} {msgid} {sd} {msg}")
        out.append(line.encode("utf-8"))
    return out, junk


def blob(lines):
    return b"\n".join(lines) + b"\n" if lines else b""


# ---------------------------------------------------------------------------
# observation: the metrics registry, JAX's compile events, the journal

class Compiles:
    """Backend compile events as JAX reports them (name, seconds)."""

    def __init__(self):
        from jax import monitoring

        self.events = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.events.append((kw.get("fun_name", "?"), duration))

    def mark(self):
        return len(self.events)

    def since(self, mark, end=None):
        by = collections.OrderedDict()
        for name, dt in self.events[mark:end]:
            n, s = by.get(name, (0, 0.0))
            by[name] = (n + 1, s + dt)
        return by


def snapshot():
    from flowgger_tpu.utils.metrics import registry

    snap = {k: registry.get(k) for k in COUNTERS}
    for i in range(8):
        snap[f"lane{i}_rows"] = registry.get(f"lane{i}_rows")
    return snap


def delta(after, before):
    return {k: after[k] - before[k] for k in after}


def event_reasons():
    from flowgger_tpu.obs import events

    return dict(collections.Counter(
        f"{e.get('site')}/{e.get('reason')}"
        for e in events.journal.snapshot()))


def wait_for(pred, what, timeout=WAIT_S):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout:
            raise RuntimeError(f"timed out after {timeout:.0f}s waiting for {what}")
        time.sleep(0.01)


def settle(written_target):
    """The sink holds every record sent so far and no compile is left
    running: the boundary between two windows."""
    from flowgger_tpu.tpu.device_common import join_compile_workers
    from flowgger_tpu.utils.metrics import registry

    wait_for(lambda: registry.get("output_written") >= written_target,
             f"the sink to hold {written_target} records "
             f"(has {registry.get('output_written')})")
    if join_compile_workers(WAIT_S):
        raise RuntimeError("a kernel compile was still running after "
                           f"{WAIT_S:.0f}s")


def programs(by):
    """The compiles that count as a cold program.  Not among them: the
    fetch driver's ``flat[:k]``, one tiny ``dynamic_slice`` program per
    distinct output length of a device-encoded batch — by the program's
    design a steady cost of that route, not a cold start (PERF.md,
    PR 22); they are printed with every window."""
    return collections.OrderedDict(
        (k, v) for k, v in by.items() if "dynamic_slice" not in k)


def fmt_compiles(by, floor=0.0):
    return (", ".join(f"{k} x{c} {t:.1f}s" for k, (c, t) in by.items()
                      if t >= floor) or "none")


def declines(d):
    """What a window counted that a warm handler must not."""
    bad = [f"{k}={d[k]}" for k in MUST_BE_ZERO if d[k]]
    if d["fused_fallbacks"] > d["device_encode_declined"]:
        bad.append(f"fused_fallbacks={d['fused_fallbacks']} exceed the "
                   f"batches the 5% rule declined "
                   f"({d['device_encode_declined']})")
    return bad


def report_window(name, d, compiles, breaker_state, enforce):
    """Print one measured window's counts; return the list of reasons
    it fails the run."""
    rows = d["input_lines"]
    dev_rows = d["device_encode_rows"]
    share = (rows - d["fallback_rows"]) / rows if rows else 0.0
    enc_share = dev_rows / rows if rows else 0.0
    say(f"[{name}] measured window: {rows} lines in, "
        f"{d['output_written']} records out, {d['batches']} flushes")
    say(f"[{name}] decoded on the device: {rows - d['fallback_rows']} rows "
        f"({share:.4f} of rows); by the scalar oracle: fallback_rows="
        f"{d['fallback_rows']} (by design: past 512 B, SD-value escapes, "
        "non-ASCII, junk)")
    say(f"[{name}] of those, encoded on the device too: device_encode_rows="
        f"{dev_rows} ({enc_share:.4f} of rows; "
        f"fused_rows={d['fused_rows']}, the rest the split device "
        f"encoder), with {d['device_encode_scalar_rows']} scalar rows "
        f"spliced into those batches; all other rows: the host block "
        f"encoder.  framing_rows={d['framing_rows']}")
    say(f"[{name}] batches by encode route, as the route economics "
        f"measured and chose: encode_route_fused={d['encode_route_fused']} "
        f"encode_route_device={d['encode_route_device']} "
        f"encode_route_host={d['encode_route_host']}")
    say(f"[{name}] the issue asked for >= {DEVICE_SHARE_MIN} of rows on the "
        "device tiers, encoders included: "
        + ("met" if enc_share >= DEVICE_SHARE_MIN else "NOT met")
        + f" ({enc_share:.4f}); the run is held to the decode share "
        "(PERF.md, PR 22)")
    say(f"[{name}] declines: fused_fallbacks={d['fused_fallbacks']} "
        f"(of which the 5% rule on a batch: device_encode_declined="
        f"{d['device_encode_declined']}) "
        + " ".join(f"{k}={d[k]}" for k in MUST_BE_ZERO)
        + f" device_breaker_state={breaker_state}")
    say(f"[{name}] persistent compile cache: hits={d['compile_cache_hits']} "
        f"misses={d['compile_cache_misses']}")
    say(f"[{name}] XLA compiles inside the window: "
        f"{sum(c for c, _ in compiles.values())} — "
        + fmt_compiles(compiles))
    bad = []
    if d["output_written"] + d.get("_junk", 0) != rows:
        bad.append(f"{rows} lines in but {d['output_written']} records out "
                   f"(+{d.get('_junk', 0)} junk lines dropped by design)")
    lost = len(bad)
    bad += declines(d)
    if programs(compiles):
        bad.append("a program was compiled inside the window: "
                   + fmt_compiles(programs(compiles)))
    if breaker_state != 0:
        bad.append(f"device_breaker_state={breaker_state} (not closed)")
    if share < DEVICE_SHARE_MIN:
        bad.append(f"the device decoded {share:.4f} of rows "
                   f"(< {DEVICE_SHARE_MIN})")
    if not enforce:
        # which tier serves, and what declines, is not what this run is
        # for (a rehearsal off the chip, the four-chip placement
        # phase): it is reported, and the run is held only to every
        # line in, every record out (and, later, the bytes)
        for b in bad[lost:]:
            say(f"[{name}] reported, not enforced: {b}")
        bad = bad[:lost]
    return [f"[{name}] {b}" for b in bad]


# ---------------------------------------------------------------------------
# driving the pipeline

# a rehearsal's only config deviation: input.tpu_batch_size (--batch-size)
REHEARSAL_KEYS = ""


def config_text(input_type, fmt, out_path, extra=""):
    """The deployment under test.  Nothing here names a tier, the
    economics or the watchdog: those stay at the program's defaults."""
    listen = 'listen = "127.0.0.1:0"\n' if input_type == "tcp" else ""
    if fmt.endswith("_tpu"):
        extra += REHEARSAL_KEYS
    return (f'[input]\ntype = "{input_type}"\n{listen}format = "{fmt}"\n'
            f'framing = "line"\n{extra}'
            f'[output]\ntype = "file"\nformat = "gelf"\n'
            f'file_path = "{out_path}"\n')


def build_pipeline(text):
    from flowgger_tpu.config import Config
    from flowgger_tpu.pipeline import Pipeline

    return Pipeline(Config.from_string(text))


class StdinFeed:
    """A pipe on fd 0 and a record of everything written to it."""

    def __init__(self, record_path):
        r, self.w = os.pipe()
        self._saved = os.dup(0)
        os.dup2(r, 0)
        os.close(r)
        self.log = open(record_path, "wb")
        self.sent = 0
        self.junk = 0

    def send(self, lines, junk):
        data = blob(lines)
        self.log.write(data)
        view = memoryview(data)
        while view:
            view = view[os.write(self.w, view[:1 << 20]):]
        self.sent += len(lines)  # flowcheck: disable=FC02 -- one writer: only the feeder thread sends
        self.junk += junk  # flowcheck: disable=FC02 -- one writer: only the feeder thread sends

    def close(self):
        os.close(self.w)
        self.log.close()

    def restore(self):
        os.dup2(self._saved, 0)
        os.close(self._saved)


def run_on_stdin(text, feeder):
    """``Pipeline(config).run()`` on the main thread — what
    ``flowgger_tpu.start()`` does — with ``feeder(feed)`` writing fd 0
    from a thread; a feeder error is the run's error."""
    fd, path = tempfile.mkstemp(prefix="smoke_in_", dir=WORK)
    os.close(fd)
    feed = StdinFeed(path)
    err = []

    def guarded():
        try:
            feeder(feed)
        except BaseException as e:  # noqa: BLE001 - re-raised on the main thread
            err.append(e)
        finally:
            feed.close()

    try:
        pipe = build_pipeline(text)
        # daemon: if run() raises, a feeder blocked on the pipe must not
        # keep the failed process alive
        t = threading.Thread(target=guarded, name="smoke-feeder",
                             daemon=True)
        t.start()
        pipe.run()
        t.join()
    finally:
        feed.restore()
    if err:
        raise err[0]
    return pipe, path


def scalar_reference(in_path, out_path):
    """The plain reference: the scalar rfc5424 pipeline (no JAX) over
    the recorded input bytes, through the same entry point."""
    fd = os.open(in_path, os.O_RDONLY)
    saved = os.dup(0)
    os.dup2(fd, 0)
    os.close(fd)
    try:
        build_pipeline(config_text("stdin", "rfc5424", out_path)).run()
    finally:
        os.dup2(saved, 0)
        os.close(saved)
    with open(out_path, "rb") as f:
        return f.read()


def breaker_state():
    from flowgger_tpu.utils.metrics import registry

    return int(registry.get_gauge("device_breaker_state", 0))


def run_windows(name, args, compiles, send, hold):
    """Windows of ``--batches`` full batches and a tail through the
    handler under test, until one after the first compiles no program:
    that one is the measured window, every one before it was warm-up.

    ``send(n)`` puts ``n`` fresh lines through that handler, waits until
    the sink holds them and no compile worker is left running, and
    returns how many of them were junk.  A tier that declined while its
    program compiled tries again when its cooldown is over (16 batches
    for the encoders, 32 flushes for device framing) and compiles what
    it still lacks in that window, which makes it warm-up too: every
    cold compile, its watchdog and busy declines and the cooldowns that
    follow fall before the measured window, on the shapes this
    handler's own traffic produces.  The measured window is then held
    to no decline at all.
    ``hold=False`` (the four-chip placement phase, where both runs must
    see the same bytes): two windows, the second is the measured one
    whatever it counted.  Returns the measured window's marks."""
    # the tail: a sixth of a batch.  A full flush takes up to one 64 KiB
    # read (~1/50 of a batch) more than the batch size, ~1/100 on
    # average, so allow for it and the tail keeps its row bucket from
    # window to window
    n = args.batches * args.batch + args.batches * args.batch // 100 \
        + args.batch // 6
    tries = WINDOWS_MAX if hold else 2
    setup0 = compiles.mark()
    for attempt in range(1, tries + 1):
        m0, c0 = snapshot(), compiles.mark()
        junk = send(n)
        d, comp = delta(snapshot(), m0), compiles.since(c0)
        if attempt > 1 and (not programs(comp) if hold
                            else attempt == tries):
            break
        say(f"[{name}] window {attempt} was warm-up: {d['input_lines']} "
            f"lines, {d['batches']} flushes, encode routes fused/device/"
            f"host {d['encode_route_fused']}/{d['encode_route_device']}/"
            f"{d['encode_route_host']}; compiled: {fmt_compiles(comp)}; "
            f"declines: {', '.join(declines(d)) or 'none'}")
    else:
        raise RuntimeError(
            f"[{name}] {tries} windows and each compiled a program new "
            "to the handler")
    setup = compiles.since(setup0, c0)
    say(f"[{name}] set-up on this handler: {attempt - 1} warm-up windows, "
        f"{sum(c for c, _ in setup.values())} XLA compiles, "
        f"{sum(t for _, t in setup.values()):.1f}s; compile seconds per "
        f"program: {fmt_compiles(setup)}")
    return {"m0": m0, "c0": c0, "junk": junk}


def stdin_phase(args, rng, compiles, name, extra="", hold=True):
    """One pipeline on fd 0: warm-up, the measured window, EOF, drain."""
    out_path = os.path.join(WORK, f"{name}.gelf")
    marks = {}

    def feeder(feed):
        from flowgger_tpu.utils.metrics import registry

        base_written = registry.get("output_written")

        def send(n):
            lines, junk = gen_lines(rng, n)
            feed.send(lines, junk)
            settle(base_written + feed.sent - feed.junk)
            return junk

        marks.update(run_windows(name, args, compiles, send, hold))
        # EOF follows: the pipeline drains and run() returns

    pipe, in_path = run_on_stdin(
        config_text("stdin", "rfc5424_tpu", out_path, extra), feeder)
    d = delta(snapshot(), marks["m0"])
    d["_junk"] = marks["junk"]
    handler = pipe._handlers[0]
    say(f"[{name}] handler: lanes={len(handler._lane_devices)} "
        f"framing_engaged={handler._framing_engaged} "
        "economics="
        + json.dumps([e.snapshot() for e in handler._econs])
        + " framing economics="
        + json.dumps(handler._framing_econ.snapshot()))
    with open(out_path, "rb") as f:
        got = f.read()
    return d, compiles.since(marks["c0"]), got, in_path, handler


def tcp_phase(args, rng, compiles):
    from flowgger_tpu.utils.metrics import registry

    name = "tcp"
    out_path = os.path.join(WORK, "tcp.gelf")
    pipe = build_pipeline(config_text("tcp", "rfc5424_tpu", out_path))
    threads = pipe.start_output()
    if not isinstance(threads, list):
        threads = [threads]
    # flowcheck: disable=FC10 -- TcpInput.accept never returns: the listener lives as long as the process, as in the CLI, and dies with it (daemon)
    threading.Thread(target=pipe.input.accept, args=(pipe.handler_factory,),
                     daemon=True, name="smoke-accept").start()
    wait_for(lambda: pipe.input.bound_port is not None, "the listener", 30)
    port = pipe.input.bound_port
    conns = [socket.create_connection(("127.0.0.1", port), timeout=WAIT_S)
             for _ in range(TCP_CONNS)]
    base_written = registry.get("output_written")
    sent = [0, 0]  # lines, junk
    all_lines = []

    def send(n):
        """Every connection sends its share at once, from its own thread."""
        shares, junk_here = [], 0
        for _c in conns:
            lines, junk = gen_lines(rng, n // TCP_CONNS)
            shares.append(blob(lines))
            all_lines.extend(lines)
            sent[0] += len(lines)
            junk_here += junk
        sent[1] += junk_here
        errs = []

        def one(c, data):
            try:
                c.sendall(data)
            except BaseException as e:  # noqa: BLE001 - re-raised below
                errs.append(e)

        ts = [threading.Thread(target=one, args=cd)
              for cd in zip(conns, shares)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise errs[0]
        settle(base_written + sent[0] - sent[1])
        return junk_here

    marks = run_windows(name, args, compiles, send, True)
    for c in conns:
        c.close()
    pipe._drain(threads)
    d = delta(snapshot(), marks["m0"])
    d["_junk"] = marks["junk"]
    assert len(pipe._handlers) == 1, "connections did not share one handler"
    handler = pipe._handlers[0]
    say(f"[{name}] handler: one BatchHandler for {TCP_CONNS} connections, "
        f"framing_engaged={handler._framing_engaged} economics="
        + json.dumps([e.snapshot() for e in handler._econs])
        + " framing economics="
        + json.dumps(handler._framing_econ.snapshot()))
    in_path = os.path.join(WORK, "tcp_in.bin")
    with open(in_path, "wb") as f:
        f.write(blob(all_lines))
    with open(out_path, "rb") as f:
        got = f.read()
    return d, compiles.since(marks["c0"]), got, in_path


def compare_bytes(name, got, want):
    if got == want:
        say(f"[{name}] sink bytes identical to the scalar pipeline, warm-up "
            f"windows included: {len(got)} bytes, {got.count(bytes(1))} "
            "records")
        return []
    n = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
             min(len(got), len(want)))
    return [f"[{name}] sink differs from the scalar pipeline at byte {n} "
            f"({len(got)} vs {len(want)} bytes): "
            f"{got[max(0, n - 60):n + 60]!r} vs {want[max(0, n - 60):n + 60]!r}"]


def compare_multiset(name, got, want):
    a = collections.Counter(got.split(bytes(1)))
    b = collections.Counter(want.split(bytes(1)))
    if a == b:
        say(f"[{name}] sink records equal the scalar pipeline's as a "
            f"multiset, warm-up windows included: {sum(a.values()) - 1} "
            "records, each exactly once")
        return []
    extra, missing = a - b, b - a
    return [f"[{name}] sink records differ from the scalar pipeline: "
            f"{sum(extra.values())} not expected (e.g. "
            f"{next(iter(extra), b'')[:160]!r}), {sum(missing.values())} "
            f"missing (e.g. {next(iter(missing), b'')[:160]!r})"]


def build_native():
    """The C++ pack tier is built from committed source, never taken
    from a binary that happens to lie in the tree."""
    r = subprocess.run(["make", "-C", os.path.join(HERE, "native"), "-s",
                        "-B", "libflowgger_host.so"],
                       capture_output=True, text=True, timeout=300)
    from flowgger_tpu import native

    if r.returncode == 0 and native.available():
        say("native pack tier: built from native/flowgger_host.cpp")
    else:
        say("native pack tier: NOT built (" + (r.stderr.strip()[-200:]
            or "make failed") + "); this run takes the pure-Python pack "
            "tier of flowgger_tpu/native.py")


def one_chip(args, compiles, enforce):
    failures = []
    rng = random.Random(args.seed)
    d, comp, got, in_path, _h = stdin_phase(args, rng, compiles, "stdin")
    failures += report_window("stdin", d, comp, breaker_state(), enforce)
    failures += compare_bytes(
        "stdin", got,
        scalar_reference(in_path, os.path.join(WORK, "stdin_ref.gelf")))
    d, comp, got, in_path = tcp_phase(args, random.Random(args.seed + 1),
                                      compiles)
    failures += report_window("tcp", d, comp, breaker_state(), enforce)
    failures += compare_multiset(
        "tcp", got,
        scalar_reference(in_path, os.path.join(WORK, "tcp_ref.gelf")))
    return failures


def four_chips(args, compiles, devices):
    """The stdin stream with one lane and with lanes left to resolve."""
    from flowgger_tpu.tpu import device_common

    failures = []
    # one warm-up window, then the measured window whatever it counts:
    # this phase is held to placement, bytes and a closed breaker; its
    # tier shares and declines are printed (every lane compiles each
    # program for its own device, so a warm-up that waits them all out
    # costs four chips for minutes, and both runs must see the same
    # bytes, so the number of windows cannot depend on what they count)
    lanes_extra = ""
    if args.rehearse and devices[0].platform == "cpu":
        # the CPU backend resolves to one lane by design; a rehearsal on
        # virtual CPU devices has to ask for the lanes a TPU host gets
        lanes_extra = f"tpu_lanes = {len(devices)}\n"
        say("[4chip] rehearsal on virtual CPU devices: input.tpu_lanes = "
            f"{len(devices)} set by hand (a TPU host resolves it itself)")
    outs = {}
    for name, extra in (("lanes1", "tpu_lanes = 1\n"),
                        ("lanesN", lanes_extra)):
        # where batches really are while the stream runs: the devices
        # that hold a [rows, 512] uint8 array, sampled from outside
        held, stop = set(), threading.Event()

        def sample():
            import jax

            while not stop.wait(0.02):
                for a in jax.live_arrays():
                    if a.ndim == 2 and a.shape[1] == 512 \
                            and a.dtype.itemsize == 1:
                        held.update(str(x) for x in a.devices())

        sampler = threading.Thread(target=sample, daemon=True,
                                   name="smoke-sampler")
        sampler.start()
        try:
            d, comp, got, _in, handler = stdin_phase(
                args, random.Random(args.seed), compiles, name, extra=extra,
                hold=False)
        finally:
            stop.set()
            sampler.join()
        outs[name] = got
        lanes = len(handler._lane_devices)
        placed = [str(x) for x in handler._lane_devices]
        say(f"[{name}] lanes={lanes} lane devices={placed}")
        failures += report_window(name, d, comp, breaker_state(),
                                  enforce=False)
        for k in ("device_decode_errors", "breaker_trips"):
            if d[k]:
                failures.append(f"[{name}] {k}={d[k]}")
        if breaker_state() != 0:
            failures.append(f"[{name}] breaker not closed")
        if name == "lanesN":
            rows = [d[f"lane{i}_rows"] for i in range(len(devices))]
            say(f"[{name}] rows per lane in the measured window: {rows}")
            if lanes != len(devices):
                failures.append(f"[{name}] resolved {lanes} lanes on "
                                f"{len(devices)} devices")
            if not all(r > 0 for r in rows):
                failures.append(f"[{name}] a lane served no rows: {rows}")
            if len(set(placed)) != len(devices):
                failures.append(f"[{name}] lanes share devices: {placed}")
            # where the batches really went: the devices seen holding
            # a batch, the device named in each program the watchdog saw
            # compiled, and the memory each device reports having held
            say(f"[{name}] devices seen holding a batch: {sorted(held)}")
            if held != set(placed):
                failures.append(f"[{name}] batches were held by "
                                f"{sorted(held)}, not by {placed}")
            ready = set(device_common._compile_ready)
            say(f"[{name}] devices named by compiled programs: " + str(sorted(
                {dev for dev in placed
                 if any(s.endswith(":" + dev) or (":" + dev + ":") in s
                        for s in ready)})))
            peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use")
                     for dev in devices]
            say(f"[{name}] peak_bytes_in_use per device: {peaks}")
            if all(p is not None for p in peaks) and not all(
                    p > args.batch * 512 for p in peaks):
                failures.append(f"[{name}] a device never held a batch: "
                                f"{peaks}")
    if outs["lanes1"] == outs["lanesN"]:
        say(f"[4chip] identical sink bytes with 1 lane and "
            f"{len(devices)} lanes: {len(outs['lanes1'])} bytes")
    else:
        failures.append("[4chip] sink bytes differ between 1 lane and "
                        f"{len(devices)} lanes")
    return failures


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the four-chip lane phase")
    ap.add_argument("--batches", type=int, default=16,
                    help="full default batches in each measured window")
    ap.add_argument("--rehearse", action="store_true",
                    help="no chip: run on whatever device JAX has, "
                         "tier shares reported but not enforced")
    ap.add_argument("--batch-size", type=int, default=None,
                    help="rehearsal only: input.tpu_batch_size")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu" and not args.rehearse:
        print("chip_smoke.py needs a TPU and JAX found none "
              "(--rehearse runs the control flow on this device, and "
              "says so)", file=sys.stderr)
        return 3
    if len(devices) != args.chips:
        print(f"chip_smoke.py --chips {args.chips} needs {args.chips} "
              f"device(s), JAX has {len(devices)}", file=sys.stderr)
        return 3
    if args.batch_size is not None and not args.rehearse:
        print("--batch-size is for --rehearse only: the chip run uses the "
              "default batch", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(HERE, "flowgger_tpu")):
        print("chip_smoke.py runs from the root of a flowgger-tpu "
              "checkout; none here", file=sys.stderr)
        return 3
    sys.path.insert(0, HERE)

    from flowgger_tpu.tpu.batch import DEFAULT_BATCH_SIZE

    args.batch = args.batch_size or DEFAULT_BATCH_SIZE
    if args.batch_size is not None:
        global REHEARSAL_KEYS
        REHEARSAL_KEYS = f"tpu_batch_size = {args.batch}\n"
        say(f"rehearsal: input.tpu_batch_size = {args.batch} (default "
            f"{DEFAULT_BATCH_SIZE}) — not the production shape")

    global WORK
    WORK = tempfile.mkdtemp(prefix="chip_smoke_")
    build_native()
    from flowgger_tpu.tpu.device_common import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()} (placed by "
        "JAX_COMPILATION_CACHE_DIR if set, else the in-checkout default; "
        "None = the CPU backend's default, no cache)")
    compiles = Compiles()
    t0 = time.monotonic()
    if args.chips == 4:
        failures = four_chips(args, compiles, devices)
    else:
        failures = one_chip(args, compiles, enforce=not args.rehearse)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    say(f"device memory: peak_bytes_in_use={peaks}")
    say(f"journal (site/reason counts): {json.dumps(event_reasons())}")
    say(f"wall: {time.monotonic() - t0:.0f}s including compiles "
        "(set-up, not a metric)")
    shutil.rmtree(WORK, ignore_errors=True)
    if failures:
        for f in failures:
            say("FAIL " + f)
        return 1
    say(json.dumps({"ok": True, "device": device}))
    return 0


WORK = None

if __name__ == "__main__":
    sys.exit(main())
