"""Per-batch span tracing: the flight recorder's timeline half.

A monotonic batch ID is minted when a flush dispatches a packed batch;
every pipeline stage that touches the batch afterwards records a span
(a ``perf_counter`` pair plus row/byte annotations) against that ID —
frame → pack → submit → decode → fetch → encode → sequence → emit —
no matter which thread runs the stage (ingest thread, lane fetcher,
sequencer turnstile).  ``end()`` moves the completed trace into a
bounded ring of finished batches (and, in ``jsonl`` mode, appends it
to a sink), where ``tools/trace_dump.py`` and the health server's
``GET /trace`` leg render it as Chrome trace-event JSON
(Perfetto/chrome://tracing loadable).

Inside a stage, the boundaries the stage's wall hides are **sub-spans**
(:meth:`Tracer.sub`, a context manager around the work itself): the
ingest thread blocked on a full window (``window_wait`` under
``submit``), the batch's host-to-device copy (``h2d`` under
``decode``), the wait for the device program (``device_wait``) and
each wait on the link for its outputs (``d2h``: one per program, whose
copies were begun at dispatch) under ``fetch``, the block encoder's
rows that go through the scalar oracle one by one and the joining of
their output with the columnar tier's (``splice`` under ``encode``: one
a batch, none for a batch without such a row),
and a program's first call (``compile``, no parent: it runs on the
compile watchdog's worker).  They go to the batch record's ``sub``
list, each with its ``parent``, so ``spans`` holds the stages and
nothing else.

One clock with the profiler: while tracing is on, every stage and
sub-span also holds a ``jax.profiler.TraceAnnotation`` named
``flowgger.<stage>`` (``batch=<bid>``) open for its interval, so an
xprof capture (``[metrics] jax_profile_dir``, SIGUSR2, ``POST
/profile``) shows the host stages on the profiler's own timeline next
to the device's ops.  With ``trace = "off"`` no annotation is opened
and a capture shows the device and JAX's own host events only.

Config (``[metrics]``)::

    trace = "off"          # "off" | "ring" | "jsonl"
    trace_ring = 256       # completed batch traces kept (ring/jsonl)
    trace_path = "t.jsonl" # jsonl mode: one JSON object per batch
    trace_max_mb = 64      # rotate the jsonl sink past this size
    trace_keep = 3         # rotated files kept (t.jsonl.1 ...)

Cost model: ``tracer.active`` is a plain attribute — when tracing is
off every instrumentation site is one attribute read and a
predicted-false branch (the benchmark's ``--trace 0`` runs, which the
driver times, are the gate on that cost), and ``jax.profiler`` is not
imported.  When on, a span append is one lock + one list append, a
sub-span two clock reads and an annotation besides; the ring is a
``deque(maxlen=...)`` so memory is bounded regardless of uptime.

The stage timeline is wall-clock-anchored once per process
(``perf_counter`` ↔ ``time.time`` epoch pair) so Chrome trace ``ts``
microseconds are absolute and two hosts' dumps can be laid side by
side.  Fleet correlation: once ``fleet/federation.py`` calls
:meth:`Tracer.set_rank`, every completed batch trace carries a
``rank`` field, and ``tools/trace_dump.py --fleet`` merges every
routable host's ring into one document with per-host process lanes.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from .sink import JsonlSink

OFF, RING, JSONL = "off", "ring", "jsonl"
MODES = (OFF, RING, JSONL)

DEFAULT_RING = 256

# canonical stage order (used by trace_dump sorting and the tests; a
# span may carry any stage name — these are the ones the pipeline
# records)
STAGES = ("frame", "pack", "submit", "decode", "fetch", "encode",
          "sequence", "emit")


# what :meth:`Tracer.sub` hands out while tracing is off
_NO_SUB = contextlib.nullcontext()


class _Sub:
    """One sub-span being measured: entered where the work starts,
    left where it ends, on the thread that does it."""

    __slots__ = ("_tracer", "_ann", "bid", "fields", "t0")

    def __init__(self, tracer, bid, fields):
        self._tracer, self.bid, self.fields = tracer, bid, fields

    def __enter__(self):
        self._ann = self._tracer._annotate(
            self.fields["stage"], self.bid, self.fields.get("note"))
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._tracer._record_sub(self.bid, self.fields, self.t0, t1)
        return False


def _annotation_class():
    """``jax.profiler.TraceAnnotation``, imported when tracing is first
    switched on; None where JAX is not installed (the tracer then keeps
    its own records and the profiler sees nothing of them)."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


class Tracer:
    """Process-wide batch-span recorder (module singleton ``tracer``)."""

    def __init__(self, ring: int = DEFAULT_RING):
        # plain attribute, read unlocked on the hot path: instrumenting
        # sites check ``tracer.active`` before touching anything else
        self.active = False
        self.mode = OFF
        self._lock = threading.Lock()
        self._next = 0
        self._open: Dict[int, dict] = {}
        self._ring: "deque[dict]" = deque(maxlen=ring)
        self._completed = 0
        self._dropped_open = 0
        self._sink = JsonlSink("trace")
        self._rank: Optional[int] = None
        # per thread: the batch it works on (``bid``) and the stage
        # annotation it holds open (``ann``, ``stage``)
        self._tls = threading.local()
        self._annotation = None
        # perf_counter -> wall anchor, fixed at construction: chrome ts
        # microseconds are absolute wall time
        self._epoch_wall = time.time()
        self._epoch_perf = time.perf_counter()

    # -- configuration -----------------------------------------------------
    def configure(self, mode: str, ring: int = DEFAULT_RING,
                  path: Optional[str] = None,
                  max_mb: Optional[float] = None, keep: int = 3) -> None:
        if mode not in MODES:
            raise ValueError(f"trace mode must be one of {MODES}")
        with self._lock:
            self.mode = mode
            # a reconfigured tracer starts fresh: configure is a boot-
            # time (or test-fixture) action, and stale batches from a
            # previous configuration would skew the new ring's stats
            self._ring = deque(maxlen=max(1, int(ring)))
            self._open.clear()
            self._completed = 0
            self._dropped_open = 0
        self._sink.open(path if mode == JSONL else None,
                        max_mb=max_mb, keep=keep)
        if mode != OFF and self._annotation is None:
            self._annotation = _annotation_class()
        # flipped last: a site observing active=True sees a configured
        # tracer
        self.active = mode != OFF

    def set_rank(self, rank: Optional[int]) -> None:
        """Fleet correlation: stamp every subsequent batch trace with
        this host's fleet rank (federation.Fleet.start)."""
        self._rank = rank

    def close(self) -> None:
        self.active = False
        self._sink.close()

    # -- recording ---------------------------------------------------------
    def begin(self, route: Optional[str] = None) -> Optional[int]:
        """Mint one batch ID (monotonic) and open its trace; returns
        None when tracing is off so call sites can skip annotation
        work entirely."""
        if not self.active:
            return None
        t0 = time.perf_counter()
        with self._lock:
            self._next += 1
            bid = self._next
            if len(self._open) >= 4096:
                # a caller that began but never ended (a batch lost to
                # a crash path) must not leak the open table forever
                self._open.pop(next(iter(self._open)))
                self._dropped_open += 1
            rec = {"bid": bid, "route": route, "t0": t0,
                   "rows": 0, "spans": [], "sub": []}
            if self._rank is not None:
                rec["rank"] = self._rank
            self._open[bid] = rec
        self.bind(bid)
        return bid

    def bind(self, bid: Optional[int]) -> None:
        """This thread works on batch ``bid`` from here on (None: on
        none).  ``begin`` binds the thread that minted the batch; the
        lane fetcher binds itself when it pops one.  The sites below
        the handler (the window, the upload, the fetch) have no batch
        ID in their signatures and ask :meth:`bound`."""
        self._close_stage()
        self._tls.bid = bid

    def bound(self) -> Optional[int]:
        return getattr(self._tls, "bid", None)

    def enter(self, stage: str) -> None:
        """The bound batch's ``stage`` starts on this thread now: hold
        a profiler annotation open until :meth:`span` records the stage
        (or the thread enters its next one).  Called beside the clock
        read that ``span`` is later given as ``t0``."""
        if not self.active:
            return
        self._close_stage()
        bid = self.bound()
        if bid is not None:
            self._tls.ann = self._annotate(stage, bid)
            self._tls.stage = stage

    def _annotate(self, stage: str, bid: Optional[int],
                  note: Optional[str] = None):
        if self._annotation is None:
            return None
        kw = {"note": note} if note else {}
        ann = self._annotation(f"flowgger.{stage}", batch=bid, **kw)
        ann.__enter__()
        return ann

    def _close_stage(self, stage: Optional[str] = None) -> None:
        tls = self._tls
        ann = getattr(tls, "ann", None)
        if ann is not None and stage in (None, tls.stage):
            tls.ann = None
            ann.__exit__(None, None, None)

    def sub(self, bid: Optional[int], stage: str, parent: Optional[str],
            rows: Optional[int] = None, nbytes: Optional[int] = None,
            note: Optional[str] = None):
        """Context manager around one sub-span of ``parent``: the work
        inside is timed, annotated for the profiler, and appended to
        batch ``bid``'s ``sub`` list.  ``bid`` None (work that belongs
        to no batch: a compile on its worker thread) keeps the
        annotation and records nothing."""
        if not self.active:
            return _NO_SUB
        fields = {"stage": stage, "parent": parent}
        if rows is not None:
            fields["rows"] = int(rows)
        if nbytes is not None:
            fields["bytes"] = int(nbytes)
        if note:
            fields["note"] = note
        return _Sub(self, bid, fields)

    def _record_sub(self, bid, fields, t0: float, t1: float) -> None:
        if bid is None:
            return
        tname = threading.current_thread().name
        with self._lock:
            rec = self._open.get(bid)
            if rec is not None:
                rec["sub"].append(
                    dict(fields, t0=t0, t1=t1, thread=tname))

    def span(self, bid: Optional[int], stage: str, t0: float, t1: float,
             rows: Optional[int] = None, nbytes: Optional[int] = None,
             note: Optional[str] = None) -> None:
        """Record one completed stage span for batch ``bid``.  The
        caller passes the perf_counter pair it already measured for its
        stage metrics, so tracing never adds clock reads of its own."""
        if bid is None or not self.active:
            return
        self._close_stage(stage)
        tname = threading.current_thread().name
        with self._lock:
            rec = self._open.get(bid)
            if rec is None:
                return
            rec["spans"].append({
                "stage": stage, "t0": t0, "t1": t1, "thread": tname,
                **({"rows": int(rows)} if rows is not None else {}),
                **({"bytes": int(nbytes)} if nbytes is not None else {}),
                **({"note": note} if note else {}),
            })
            if rows:
                rec["rows"] = max(rec["rows"], int(rows))

    def end(self, bid: Optional[int],
            e2e_s: Optional[float] = None) -> None:
        """Finish one batch trace: move it to the completed ring (and
        the JSONL sink when configured)."""
        if bid is None:
            return
        if self.bound() == bid:
            self.bind(None)
        with self._lock:
            rec = self._open.pop(bid, None)
            if rec is None:
                return
            rec["t1"] = time.perf_counter()
            if e2e_s is not None:
                rec["e2e_s"] = round(e2e_s, 6)
            self._ring.append(rec)
            self._completed += 1
        if self.mode == JSONL:
            # best-effort: a failed write disables the sink (one
            # notice) — it must never propagate into the sequencer's
            # emit path that is closing this batch
            self._sink.write(rec)

    # -- export ------------------------------------------------------------
    def snapshot(self) -> List[dict]:
        """The completed ring, oldest first (JSON-safe dicts)."""
        with self._lock:
            return [dict(rec) for rec in self._ring]

    def stats(self) -> dict:
        with self._lock:
            return {"mode": self.mode, "completed": self._completed,
                    "ring": len(self._ring), "open": len(self._open),
                    "dropped_open": self._dropped_open}

    def chrome_events(self, traces: Optional[List[dict]] = None
                      ) -> List[dict]:
        """Render batch traces as Chrome trace-event ``"X"`` (complete)
        events: ``ts``/``dur`` in wall-anchored microseconds, ``pid``
        the process, ``tid`` a stable small integer per recorded
        thread name (thread names land in trace metadata events)."""
        if traces is None:
            traces = self.snapshot()
        return chrome_events(traces, self._epoch_wall, self._epoch_perf)


def chrome_events(traces: List[dict], epoch_wall: Optional[float] = None,
                  epoch_perf: Optional[float] = None) -> List[dict]:
    """Pure converter: batch-trace dicts → Chrome trace-event list.
    Used by the live tracer and by ``tools/trace_dump.py`` over a JSONL
    capture (where no live epoch exists — spans then anchor at 0)."""
    if epoch_wall is None or epoch_perf is None:
        epoch_wall, epoch_perf = 0.0, 0.0
    pid = os.getpid()
    tids: Dict[str, int] = {}
    events: List[dict] = []

    def tid_for(name: str) -> int:
        tid = tids.get(name)
        if tid is None:
            tid = len(tids) + 1
            tids[name] = tid
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": name}})
        return tid

    def us(t: float) -> float:
        return round((epoch_wall + (t - epoch_perf)) * 1e6, 3)

    for rec in traces:
        bid = rec.get("bid")
        # a stage first, then the sub-spans inside it: same thread,
        # contained in time, so a viewer nests them under their parent
        for cat, spans in (("batch", rec.get("spans", ())),
                           ("sub", rec.get("sub", ()))):
            for sp in spans:
                args = {"batch": bid}
                for key in ("parent", "rows", "bytes", "note"):
                    if sp.get(key) is not None:
                        args[key] = sp[key]
                if rec.get("route"):
                    args["route"] = rec["route"]
                events.append({
                    "name": sp["stage"], "ph": "X", "cat": cat,
                    "ts": us(sp["t0"]),
                    "dur": round(max(0.0, sp["t1"] - sp["t0"]) * 1e6, 3),
                    "pid": pid, "tid": tid_for(sp.get("thread", "?")),
                    "args": args,
                })
    return events


# the process-wide tracer every pipeline layer imports
tracer = Tracer()


def configure_from(config) -> None:
    """Wire ``[metrics] trace``/``trace_ring``/``trace_path`` (pipeline
    boot; no keys = tracing off, the production default)."""
    mode = config.lookup_str(
        "metrics.trace",
        'metrics.trace must be "off", "ring" or "jsonl"', OFF)
    if mode not in MODES:
        from ..config import ConfigError

        raise ConfigError('metrics.trace must be "off", "ring" or "jsonl"')
    ring = config.lookup_int(
        "metrics.trace_ring",
        "metrics.trace_ring must be an integer (batch traces kept)",
        DEFAULT_RING)
    path = config.lookup_str(
        "metrics.trace_path", "metrics.trace_path must be a string (file)")
    max_mb = config.lookup_float(
        "metrics.trace_max_mb",
        "metrics.trace_max_mb must be a number (MB before the JSONL "
        "sink rotates)")
    keep = config.lookup_int(
        "metrics.trace_keep",
        "metrics.trace_keep must be an integer (rotated files kept)", 3)
    if mode == JSONL and not path:
        from ..config import ConfigError

        raise ConfigError(
            'metrics.trace = "jsonl" requires metrics.trace_path')
    try:
        tracer.configure(mode, ring=ring, path=path, max_mb=max_mb,
                         keep=keep)
    except OSError as e:
        # an unwritable trace sink must never kill ingest: fall back to
        # the in-memory ring and say so
        print(f"trace: cannot open {path} ({e}); falling back to ring "
              "mode", file=sys.stderr)
        tracer.configure(RING, ring=ring)
