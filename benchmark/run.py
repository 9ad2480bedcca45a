#!/usr/bin/env python3
"""One run of one cell of the benchmark, on the machine it is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and per-layer metrics are data
files found by the names in ``BENCHMARK.json`` (``README.md`` here says
how to add one).  The deployment's ``[input] type`` says which way in the
lines take: ``stdin`` (the generator's pipe on fd 0) or ``tcp`` (the
generator's connections to the listener).  A run: set-up (native build,
compile cache, pool, children, pipeline, the shape walk, the mix itself
for ``warm_min_s`` seconds and on until a slice of it compiles and
declines nothing), the measured window, opened on the clock, stop and
drain, then the comparison with the plain reference, the report, and
one JSON object as the last line of standard output.  Without a TPU it
exits non-zero and prints no result; ``--rehearse`` runs the control
flow on whatever device JAX has and names that device.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import select  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import tomllib  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[0] = ROOT
# the compile cache lives in this checkout and nowhere else (the
# configuration's ``tpu_compile_cache_dir``): two checkouts share
# nothing, and every machine starts a checkout's first run cold alike
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

from benchmark import peakrss  # noqa: E402

SLICE_S = 4.0           # one warm-up slice
WARM_MAX_S = 150.0      # then the window opens as things stand
COLD_S = 1.0            # a compile this long holds the stream up
WALK_FROM, WALK_TOP = 200, 32768   # bursts of 200, 400, ... 25,600 lines
TRACE_SLICE_S = 5.0     # the profiler's slice, from the middle of the window
WAIT_S = 600.0
HOST = "127.0.0.1"      # a tcp deployment's @LISTEN@: any free port, here
# what a warm slice, and the window, may not count
MUST_BE_ZERO = ("device_encode_compile_declines", "framing_declines",
                "pallas_declines", "breaker_trips", "device_decode_errors",
                "drain_flush_errors", "output_errors")
SHOWN = ("input_lines", "output_written", "batches", "batch_lines",
         "fused_rows", "device_encode_rows", "device_encode_scalar_rows",
         "encode_route_fused", "encode_route_device", "encode_route_host",
         "fallback_rows", "framing_rows", "fused_fallbacks",
         "device_encode_declined", "compile_cache_hits",
         "compile_cache_misses", "overlap_stall_seconds") + MUST_BE_ZERO


def say(*a):
    print(f"[{time.time() - T_START:7.2f}s]", *a, flush=True)


class Failed(Exception):
    """The run cannot give a result; the message says why."""


# ---------------------------------------------------------------------------
# observation (copied from chip_smoke.py, PR 22)

class Compiles:
    """Backend compile events as JAX reports them: (name, seconds,
    whether the persistent cache served it, when it ended).  JAX times a
    load from the cache under the same event as a compile, and says
    ``cache_hits`` on the same thread just before."""

    def __init__(self):
        from jax import monitoring

        self.events = []
        self._hit = set()
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on)

    def _on_event(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self._hit.add(threading.get_ident())

    def _on(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            me = threading.get_ident()
            loaded = me in self._hit
            self._hit.discard(me)
            self.events.append((kw.get("fun_name", "?"), duration, loaded,
                                time.time()))

    def mark(self):
        return len(self.events)

    def since(self, mark, end=None):
        by = {}
        for name, dt, _loaded, _at in self.events[mark:end]:
            n, s = by.get(name, (0, 0.0))
            by[name] = (n + 1, s + dt)
        return by

    def last_cold(self):
        """When the last program that the cache did not hold finished
        compiling (0.0: none yet).  One that took under ``COLD_S`` held
        nothing up and does not count."""
        return max((at for _name, dt, loaded, at in self.events
                    if not loaded and dt >= COLD_S), default=0.0)


def programs(by):
    """The compiles and loads that are a program of the collector's.
    Not among them: the fetch driver's ``flat[:k]``, one tiny
    ``dynamic_slice`` program per distinct output length of a
    device-encoded batch, a steady cost of that route by the program's
    design (PERF.md, PR 22)."""
    return {k: v for k, v in by.items() if "dynamic_slice" not in k}


def fmt_compiles(by):
    return ", ".join(f"{k} x{c} {t:.1f}s" for k, (c, t) in by.items()) \
        or "none"


def snapshot():
    from flowgger_tpu.utils.metrics import registry

    return {k: v for k, v in registry.snapshot().items()
            if isinstance(v, (int, float))}


def delta(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()}


def cpu_seconds():
    t = os.times()
    return t.user + t.system


def declines(d):
    bad = [f"{k}={d[k]}" for k in MUST_BE_ZERO if d.get(k)]
    if d.get("fused_fallbacks", 0) > d.get("device_encode_declined", 0):
        bad.append(f"fused_fallbacks={d['fused_fallbacks']}")
    return bad


# ---------------------------------------------------------------------------
# the children

class Child:
    """A process that never sees the chip, a pipe to command it, and
    (for the generator) a pipe on which it answers."""

    def __init__(self, argv, stdout=None, answers=False):
        self.rfd = None
        pass_fds = ()
        if answers:
            self.rfd, wfd = os.pipe()
            argv = argv + ["--status-fd", str(wfd)]
            pass_fds = (wfd,)
        self.proc = subprocess.Popen(
            [sys.executable] + argv, stdin=subprocess.PIPE, stdout=stdout,
            pass_fds=pass_fds, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        if answers:
            os.close(wfd)
        self.buf = b""
        self.peak_rss = None    # the kernel's count, once it has ended

    def tell(self, word):
        self.proc.stdin.write(word.encode() + b"\n")
        self.proc.stdin.flush()

    def answer(self, ev, timeout=WAIT_S):
        """The next answer, which has to be ``ev``."""
        deadline = time.time() + timeout
        while b"\n" not in self.buf:
            left = deadline - time.time()
            if left <= 0 or not select.select([self.rfd], [], [], left)[0]:
                raise Failed(f"the generator did not say {ev!r}")
            data = os.read(self.rfd, 4096)
            if not data:
                raise Failed(f"the generator ended before saying {ev!r} "
                             f"(exit code {self.proc.wait()})")
            # flowcheck: disable=FC02 -- one thread at a time talks to a child: the conductor until it ends, then the main thread
            self.buf += data
        line, _, self.buf = self.buf.partition(b"\n")
        msg = json.loads(line)
        if msg["ev"] != ev:
            raise Failed(f"the generator said {msg!r}, not {ev!r}")
        return msg

    def reap(self, timeout=60.0):
        """Wait for the process to end; kill it if it will not.  Keeps
        what the kernel says it peaked at (``peakrss.wait``: sound for
        a child started before this process grew)."""
        if self.proc.stdin and not self.proc.stdin.closed:
            try:
                self.proc.stdin.close()
            except BrokenPipeError:
                say("child's command pipe was already broken")
        try:
            rc, self.peak_rss = peakrss.wait(self.proc, timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            rc, self.peak_rss = peakrss.wait(self.proc)
        if self.rfd is not None:
            os.close(self.rfd)
            self.rfd = None
        return rc


# ---------------------------------------------------------------------------
# the manifest and its data files

def load_cell(name):
    manifest = os.path.join(ROOT, "BENCHMARK.json")
    with open(manifest) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise Failed(f"{manifest} has no workload {name!r}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, os.path.splitext(conf["file"])[0]
                           + ".toml")) as f:
        toml = f.read()

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return {"cell": cell, "toml": toml,
            "input": tomllib.loads(toml).get("input", {}).get("type"),
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def layer_metrics(judged):
    """Every ``layer_metrics/<name>.json`` whose suffix is the traffic's
    ``judged``, with its reader module."""
    out = []
    folder = os.path.join(HERE, "layer_metrics")
    for fn in sorted(os.listdir(folder)):
        name, ext = os.path.splitext(fn)
        if ext != ".json" or not name.endswith("." + judged):
            continue
        with open(os.path.join(folder, fn)) as f:
            spec = json.load(f)
        spec["name"] = name
        spec["read"] = importlib.import_module(
            "benchmark.readers." + spec["reader"]).read
        out.append(spec)
    return out


def peak_of(kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if kind not in peaks:
        raise Failed(f"device kind {kind!r} is not in benchmark/peaks.json: "
                     "add it with its source, do not guess")
    return peaks[kind]


# ---------------------------------------------------------------------------
# one run

class Run:
    def __init__(self, args, spec, mix):
        self.args, self.spec, self.mix, self.device = args, spec, mix, None
        self.work = os.path.join(HERE, "work", f"run-{os.getpid()}")
        self.sink_path = os.path.join(self.work, "sink.gelf")
        self.gen = self.tail = None
        self.failure = None
        self.window = None
        self.lines0 = 0     # the collector's input_lines before any line
        self.facts = {}

    # -- set-up --------------------------------------------------------------
    def start_children(self):
        os.makedirs(self.work)
        argv = [os.path.join(HERE, "gen.py"), "--traffic",
                self.spec["cell"]["traffic"], "--seed", str(self.args.seed),
                "--work", self.work]
        if self.args.rehearse:
            argv += ["--pool-lines", "8192"]
        if self.spec["input"] == "tcp":
            self.gen = Child(argv + ["--sockets"], answers=True,
                             stdout=subprocess.DEVNULL)
        else:
            self.gen = Child(argv, answers=True, stdout=subprocess.PIPE)
        self.tail = Child([os.path.join(HERE, "sinktail.py"), self.sink_path,
                           os.path.join(self.work, "sink.npz")])

    def build_native(self):
        so = os.path.join(ROOT, "native", "libflowgger_host.so")
        if not os.path.exists(so):
            r = subprocess.run(["make", "-C", os.path.join(ROOT, "native"),
                                "-s", "libflowgger_host.so"],
                               capture_output=True, text=True, timeout=300,
                               stdin=subprocess.DEVNULL)
            if r.returncode:
                raise Failed("native/libflowgger_host.so did not build: "
                             + r.stderr.strip()[-300:])
        from flowgger_tpu import native

        if not native.available():
            raise Failed("the native pack tier does not load")
        say("native pack tier: native/libflowgger_host.so")

    def pipeline(self):
        from flowgger_tpu.config import Config
        from flowgger_tpu.pipeline import Pipeline

        text = self.spec["toml"].replace("@SINK@", self.sink_path).replace(
            "@CACHE@", os.path.join(ROOT, ".jax_cache")).replace(
            "@LISTEN@", HOST + ":0")
        if self.args.rehearse:
            # small batches; and no persistent cache, the program's
            # default on the CPU backend
            text = text.replace("[input]\n", "[input]\ntpu_batch_size = 512\n")
            if self.spec["input"] == "tcp":
                # the chip's framing tier, which "auto" engages there
                # and not on the CPU: each connection's bytes then go
                # through a raw session of its own, whose order the
                # flush keeps.  The host splitters' shared list does
                # not keep it between racing flushes (PERF.md, Open
                # questions), and the chip never takes that path
                text = text.replace("[input]\n",
                                    '[input]\ntpu_framing = "on"\n')
            text = text.replace('tpu_compile_cache_dir = "', '# "')
        if self.args.trace:
            text += '\n[metrics]\ntrace = "ring"\ntrace_ring = 65536\n'
        pipe = Pipeline(Config.from_string(text))
        if self.args.break_:
            from benchmark import faults

            faults.install(pipe, self.args.break_)
            say(f"BROKEN ON PURPOSE: {self.args.break_} (benchmark/faults.py)")
        return pipe

    # -- the conductor: warm-up, window, stop --------------------------------
    def settle(self, written):
        """Nothing moves any more and no compile is left running.
        ``written``: the generator's answer, with the lines it has
        written by now.  Sockets take a burst whole before the
        collector has read a byte, so on the tcp way in the collector
        first has to have counted as many."""
        from flowgger_tpu.tpu.device_common import join_compile_workers

        lines = written["lines"] if self.spec["input"] == "tcp" else 0
        deadline = time.time() + WAIT_S
        while snapshot().get("input_lines", 0) < self.lines0 + lines:
            if time.time() > deadline:
                raise Failed(f"the collector counted fewer than the {lines} "
                             f"lines written, {WAIT_S:.0f}s on")
            time.sleep(0.05)
        last, since = None, time.time()
        while time.time() - since < 0.3:
            now = snapshot()
            now = (now.get("input_lines"), now.get("output_written"))
            if now != last:
                last, since = now, time.time()
            time.sleep(0.05)
        if join_compile_workers(WAIT_S):
            raise Failed(f"a compile was still running after {WAIT_S:.0f}s")

    def walk_shapes(self, compiles):
        """One burst per row bucket under a full batch, smallest first,
        each flushed by the handler's timer and waited for: a ragged
        batch in the window (a reader that stalled, the lines beyond a
        full batch) then finds its decode program compiled.  The cell's
        own lines; the full batch's shape comes with the slices."""
        top = 512 if self.args.rehearse else WALK_TOP
        c0, m0, n = compiles.mark(), snapshot(), WALK_FROM
        while n < top:
            self.gen.tell(f"burst {n}")
            self.settle(self.gen.answer("burst"))
            n *= 2
        d = delta(snapshot(), m0)
        say(f"shape walk: bursts of {WALK_FROM}..{n // 2} lines, "
            f"{d.get('input_lines', 0)} lines in, "
            f"{d.get('output_written', 0)} records out, "
            f"{d.get('batches', 0)} batches; compiled or loaded: "
            f"{fmt_compiles(compiles.since(c0))}; declines: "
            f"{', '.join(declines(d)) or 'none'}")

    def warm_up(self, compiles):
        """The shape walk, then the mix itself in slices, until all of
        this holds: the mix has run for its ``warm_min_s``; as long has
        passed since the last program that the cache did not hold was
        compiled (a checkout's early runs still meet shapes for the
        first time, whenever one of the collector's periodic probes
        falls on a batch of another size: those runs warm up longer,
        and leave the programs in the cache); and the last slice loaded
        and compiled no program, counted no decline and flowed (some
        records, and half of the best slice's at least: a stream that
        stands still is waiting for a compile that has not reported
        yet)."""
        slice_s = 1.0 if self.args.rehearse else SLICE_S
        least = 0.0 if self.args.rehearse else float(self.mix["warm_min_s"])
        c_first = compiles.mark()
        self.walk_shapes(compiles)
        self.gen.tell("run")
        t_run, best, attempt = time.time(), 0, 0
        while True:
            attempt += 1
            m0, c0 = snapshot(), compiles.mark()
            time.sleep(slice_s)
            d, comp = delta(snapshot(), m0), compiles.since(c0)
            bad = declines(d)
            out = d.get("output_written", 0)
            best = max(best, out)
            if out < best / 2 or not out:
                bad.append("the stream stood still" if out else
                           "nothing reached the sink")
            cold = programs(comp)
            say(f"warm-up slice {attempt}: {d.get('input_lines', 0)} lines in, "
                f"{out} records out, {d.get('batches', 0)} batches; "
                f"compiled or loaded: {fmt_compiles(comp)}; declines: "
                f"{', '.join(bad) or 'none'}")
            now = time.time()
            if (not cold and not bad and now - t_run >= least
                    and now - compiles.last_cold() >= least):
                break
            if now - t_run > WARM_MAX_S:
                say(f"warm-up: {WARM_MAX_S:.0f}s and still compiling or "
                    "declining; the window opens as things stand")
                break
            if cold or bad:
                # let the compile workers land with the stream held
                # back, or a cold process would fill the disk meanwhile
                self.gen.tell("pause")
                self.settle(self.gen.answer("paused"))
                self.gen.tell("run")
        setup = compiles.since(c_first)
        loaded = sum(1 for e in compiles.events[c_first:] if e[2])
        say(f"set-up: {attempt} warm-up slices in "
            f"{time.time() - t_run:.1f}s; "
            f"{sum(c for c, _ in setup.values())} programs in "
            f"{sum(t for _, t in setup.values()):.1f}s, {loaded} of them "
            "loaded from the checkout's cache")

    def conduct(self, compiles, port=None):
        """Runs beside the pipeline: everything between "the pipeline is
        up" and "the generator has stopped".  ``port``: the listener's,
        where the lines arrive on connections."""
        self.gen.answer("ready")
        self.lines0 = snapshot().get("input_lines", 0)
        if port is not None:
            self.gen.tell(f"connect {HOST}:{port}")
            say(f"generator: {self.gen.answer('connected')['sources']} "
                f"connections to port {port}, held to the end")
        self.warm_up(compiles)
        seconds = self.args.seconds
        t0 = time.time()
        self.facts["setup_s"] = t0 - T_START
        m0, c0, h0 = snapshot(), compiles.mark(), cpu_seconds()
        trace_dir = None
        if self.args.trace:
            import jax

            lead = max(0.0, (seconds - TRACE_SLICE_S) / 2)
            time.sleep(lead)
            trace_dir = os.path.join(self.work, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            # a slice in which the program emitted nothing (the stream
            # stood still while a program compiled) has no device op to
            # read: listen again, while the window lasts
            while True:
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                in_slice = snapshot()
                time.sleep(min(TRACE_SLICE_S, seconds))
                # what the program counted while the profiler listened
                counted = delta(snapshot(), in_slice)
                jax.profiler.stop_trace()
                self.facts["slice_counters"] = counted
                if (counted.get("input_lines")
                        or time.time() + TRACE_SLICE_S + 2 > t0 + seconds):
                    break
                say("trace: nothing was emitted in that slice; another")
        time.sleep(max(0.0, t0 + seconds - time.time()))
        t1 = time.time()
        self.window = (int(t0 * 1e6), int(t1 * 1e6))
        self.facts.update(
            counters=delta(snapshot(), m0), compiles=compiles.since(c0),
            cores=(cpu_seconds() - h0) / (t1 - t0),
            trace_dir=trace_dir, window_s=t1 - t0)
        self.gen.tell("stop")
        self.facts["gen_done"] = self.gen.answer("done")

    def conduct_guarded(self, compiles, port=None):
        try:
            self.conduct(compiles, port)
        except BaseException as e:  # noqa: BLE001 - re-raised on the main thread
            self.failure = e
            # the stream has to end, or the pipeline never returns
            if self.gen is not None:
                self.gen.proc.kill()

    # -- the way in ----------------------------------------------------------
    def serve_stdin(self, compiles):
        """``Pipeline.run()`` on the main thread, as the CLI does, fd 0
        being the pipe the generator writes."""
        saved = os.dup(0)
        os.dup2(self.gen.proc.stdout.fileno(), 0)
        self.gen.proc.stdout.close()
        try:
            pipe = self.pipeline()
            t = threading.Thread(target=self.conduct_guarded,
                                 args=(compiles,), name="bench-conductor")
            t.start()
            try:
                pipe.run()
            finally:
                t.join()
        finally:
            os.dup2(saved, 0)
            os.close(saved)

    def serve_tcp(self, compiles):
        """The listener on a thread of its own, as ``chip_smoke.py``'s
        TCP phase serves it (``TcpInput.accept`` never returns), the
        conductor here.  When the generator has written every queued
        line and closed every socket, the connections' reader threads
        end of themselves; then the drain ``Pipeline.run()`` would make:
        flush, the queue through the sink, the compile workers."""
        from flowgger_tpu.tpu.device_common import join_compile_workers

        pipe = self.pipeline()
        threads = pipe.start_output()
        if not isinstance(threads, list):
            threads = [threads]
        # flowcheck: disable=FC10 -- TcpInput.accept never returns: the listener lives as long as the process, as in the CLI, and dies with it (daemon)
        threading.Thread(target=pipe.input.accept, daemon=True,
                         args=(pipe.handler_factory,),
                         name="bench-accept").start()
        try:
            deadline = time.time() + 30.0
            while pipe.input.bound_port is None:
                if time.time() > deadline:
                    raise Failed("the listener did not come up")
                time.sleep(0.01)
            self.conduct_guarded(compiles, pipe.input.bound_port)
            # the sockets are closed (or the generator is dead): every
            # reader thread sees the end of its stream
            left = pipe.input.join_handlers(timeout=WAIT_S)
            if left and self.failure is None:
                self.failure = Failed(
                    f"{left} connections' readers were still at work "
                    f"{WAIT_S:.0f}s after their sockets closed")
        finally:
            pipe._drain(threads)
        if join_compile_workers():
            say("drain: a kernel compile was still running at the end")
        stragglers = snapshot().get("drain_stragglers", 0)
        if stragglers and self.failure is None:
            self.failure = Failed(f"drain_stragglers={stragglers}: the drain "
                                  "left a thread behind; no sound run")

    # -- after the drain -----------------------------------------------------
    def reduce(self):
        import numpy as np

        from benchmark import check, stats

        if self.gen.reap():
            raise Failed("the generator failed")
        self.tail.tell("stop")
        if self.tail.reap(120.0):
            raise Failed("the sink reader failed")
        import jax

        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
        cols = np.load(os.path.join(self.work, "sink.npz"))
        sink = check.Sink(self.sink_path, cols)
        log = np.load(os.path.join(self.work, "gen_log.npy"))
        sink_bytes = os.path.getsize(self.sink_path)
        # said before the children start: a run that is stopped for its
        # memory inside the comparison leaves at least this
        say(f"comparison: the sink file holds {sink_bytes} bytes in "
            f"{len(sink.ts)} records")
        rss_before, t_cmp = peakrss.own(), time.time()
        got, made = check.compare(self.work, sink, log, self.window,
                                  self.args.seed)
        say(f"comparison: {made['attempted']} well-formed lines written, "
            f"{made['sampled']} of them through the plain reference line "
            f"by line, {len(sink.ts)} records of the sink, "
            f"{time.time() - t_cmp:.1f}s in "
            f"{check.REF_CHILDREN + check.SINK_CHILDREN} children")
        by_kind = made.pop("children_rss")
        children = [n for some in by_kind.values() for n in some]
        gb = peakrss.gb
        say(f"memory: collector {gb(rss_before)} before the comparison, "
            f"{gb(peakrss.own())} after; generator {gb(self.gen.peak_rss)}; "
            f"sink reader {gb(self.tail.peak_rss)}; comparison children: "
            f"sum {gb(sum(children))}, largest {gb(max(children))} "
            f"({len(children)} children, the sink's "
            f"{len(by_kind['sink'])}: sum {gb(sum(by_kind['sink']))}); "
            f"sink file {gb(sink_bytes)}, {len(sink.ts)} records")
        f, (t0, t1) = self.facts, self.window
        e2e = {"setup_s": f["setup_s"],
               "lines_per_s": stats.rate(sink.seen, t0, t1)}
        in_window = log[(log[:, 3] >= t0) & (log[:, 3] < t1)]
        f.update(made, peak=peak, compared=got, e2e=e2e, gen_rows=in_window)
        d = f["counters"]
        per_s = np.histogram(sink.seen, bins=np.arange(t0, t1 + 1, 1_000_000))[0]
        say("window: records at the sink in each second: "
            + " ".join(str(int(n)) for n in per_s))
        # a neighbour that takes the cores shows as fewer of them here
        say(f"window: the collector's process kept {f['cores']:.2f} of "
            f"{os.cpu_count()} cores busy")
        say(f"run: the generator wrote {f['gen_done']['lines']} lines, the "
            f"collector counted {snapshot().get('input_lines', 0) - self.lines0}")
        say("window: " + " ".join(f"{k}={d.get(k, 0)}" for k in SHOWN))
        say(f"window: XLA compiles inside it: {fmt_compiles(f['compiles'])}")
        say(f"window: declines: {', '.join(declines(d)) or 'none'}")
        late = (in_window[:, 4] - in_window[:, 3]) / 1000.0
        say(f"generator: {len(in_window)} writes in the window, late by "
            f"p50 {stats.percentile(late, 50):.3f} ms, p99 "
            f"{stats.percentile(late, 99):.3f} ms, max "
            f"{late.max() if len(late) else 0:.3f} ms")
        say(f"lines due in the window: mean {made['line_bytes']} B in, "
            f"{made['record_bytes']} B a record out")

    def layer_values(self):
        from benchmark import xplane

        f = self.facts
        ctx = dict(f, mix=self.mix, window=self.window, device=self.device,
                   peaks=(peak_of(self.device["kind"])
                          if self.device["platform"] == "tpu" else None),
                   profile=None,
                   spans=None)
        if f.get("trace_dir"):
            pb = xplane.find(f["trace_dir"])
            if self.args.keep_trace:
                os.makedirs(self.args.keep_trace, exist_ok=True)
                shutil.copy(pb, self.args.keep_trace)
            ctx["profile"] = xplane.reduce(pb)
            say(f"trace: {json.dumps(ctx['profile']['lines'])}")
            from flowgger_tpu.obs.trace import tracer

            # spans carry perf_counter readings: this process's own
            # offset to the wall clock places them in the window
            wall = time.time() - time.perf_counter()
            ctx["spans"] = [dict(rec, wall=wall) for rec in tracer.snapshot()]
        out = {}
        for spec in layer_metrics(self.mix["judged"]):
            v = spec["read"](ctx, spec.get("args"))
            if v is not None and math.isfinite(v):
                out[spec["name"]] = {"value": v, "unit": spec["unit"]}
            else:
                say(f"{spec['name']}: nothing to read")
        return out, ctx["profile"]

    def cleanup(self):
        for c in (self.gen, self.tail):
            if c is not None and c.proc.poll() is None:
                c.proc.kill()
                c.reap(10.0)
        if self.args.keep_work:
            say(f"work files left in {self.work}")
        else:
            shutil.rmtree(self.work, ignore_errors=True)


def begin(run, args, spec):
    """The first touch of JAX: name the device, refuse one that is no
    TPU or too few, build the native tier, switch on the compile cache;
    returns the compile listener."""
    import jax

    devs = jax.devices()
    run.device = device = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}
    say(f"device: {json.dumps(device)}")
    if device["platform"] != "tpu" and not args.rehearse:
        raise Failed("this benchmark needs a TPU and JAX found none "
                     "(--rehearse runs the control flow on this device)")
    if device["count"] < spec["cell"]["chips"]:
        raise Failed(f"the cell needs {spec['cell']['chips']} chip(s), "
                     f"JAX has {device['count']}")
    if device["platform"] == "tpu":
        peak_of(device["kind"])
    run.build_native()
    from flowgger_tpu.tpu.device_common import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    return Compiles()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="no chip: whatever device JAX has, a tiny pool "
                         "and batch; the last line names that device")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1: copy the profiler's .xplane.pb "
                         "there before the work files go (to look at one "
                         "by hand, or to record one for the tests)")
    ap.add_argument("--keep-work", action="store_true",
                    help="leave the run's work files (sink, logs, pool) "
                         "in benchmark/work/ to look at")
    ap.add_argument("--break", dest="break_", metavar="FAULT",
                    help="break the timed path on purpose "
                         "(benchmark/faults.py): the run has to end "
                         "with correct false")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "flowgger_tpu")):
        print("benchmark/run.py: no flowgger_tpu/ beside benchmark/: "
              "nothing to measure", file=sys.stderr)
        return 3
    from benchmark import traffic

    try:
        spec = load_cell(args.workload)
        mix = traffic.load(spec["cell"]["traffic"])
    except (Failed, OSError, ValueError) as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2
    run = Run(args, spec, mix)
    serve = {"stdin": run.serve_stdin, "tcp": run.serve_tcp}.get(spec["input"])
    if serve is None or (spec["input"] == "stdin" and mix["sources"] != 1):
        print(f"benchmark/run.py: the harness feeds a deployment through "
              f"stdin (one stream) or tcp, not {mix['sources']} sources "
              f"through [input] type = {spec['input']!r}", file=sys.stderr)
        return 2
    try:
        run.start_children()
        compiles = begin(run, args, spec)
        serve(compiles)
        if run.failure is not None:
            raise run.failure
        run.reduce()
        f = run.facts
        if args.trace:
            metrics, profile = run.layer_values()
        else:
            metrics = {m["name"]: {"value": f["e2e"][m["name"]],
                                   "unit": m["unit"]}
                       for m in spec["end_to_end"]}
            profile = None
    except Failed as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 1
    finally:
        run.cleanup()
    from benchmark import check

    got = f["compared"]
    compared = {k: [got[k], check.LIMITS[k]] for k in check.LIMITS
                if k in got}
    correct = f["attempted"] > 0 and all(v <= lim for v, lim in
                                        compared.values())
    device = dict(run.device, memory_peak_bytes=f["peak"])
    result = {"correct": correct, "attempted": f["attempted"],
              "failed": got["missing"], "metrics": metrics, "device": device}
    if profile is not None:
        device.update(busy_s=profile["busy_s"], window_s=profile["window_s"])
        result["breakdown"] = {"device_ops": profile["top_ops"],
                               "idle_gaps": profile["idle_gaps"]}
    result["compared"] = compared
    for name, m in metrics.items():
        say(f"{name} = {m['value']} {m['unit']}")
    sys.stdout.flush()
    print(f"compared ({f['attempted']} well-formed lines written, "
          f"{f['sampled']} of them byte for byte): "
          + " ".join(f"{k}={v} (limit {lim})"
                     for k, (v, lim) in compared.items()),
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
