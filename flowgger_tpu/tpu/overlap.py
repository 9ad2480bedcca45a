"""Overlapped batch execution: the in-flight submit/fetch window and
the device-vs-host route economics.

The serial BatchHandler shape — pack, dispatch, fetch, encode, sink,
one batch at a time — sums every stage's latency, so the e2e rate is
bounded by the *slowest sequential path* instead of the slowest *stage*
(BENCH r5: device_fetch alone was 7.94s of an 8.08s batch wall).
ParPaRaw (arxiv 1905.13415) and simdjson (1902.08318) both get their
throughput from stage pipelining; this module is the flowgger-tpu shape
of that idea:

``InflightWindow``
    A bounded window of submitted device batches (``input.tpu_inflight``,
    default 2).  The ingest thread packs and *submits* batch N+1 while a
    dedicated fetcher thread *fetches/encodes/enqueues* batch N — device
    compute, D2H transfer, and host encode overlap instead of summing.
    Strict batch ordering is structural: one fetcher thread pops a FIFO,
    so blocks reach the merger in submit order no matter how long any
    fetch takes.  A full window blocks ``submit`` (``overlap_stall_
    seconds``) — backpressure flows to the splitter and from there to
    the socket, exactly like the bounded queue it feeds.

    Failure semantics: the pop function owns degradation (the device-
    decode circuit breaker re-decodes a failed batch through the scalar
    oracle *at its position in the window*, so byte-identity and
    ordering survive mid-window device failures).  An exception the pop
    function chooses to propagate (breaker disabled = legacy fail-fast)
    is stashed and re-raised on the ingest thread at the next
    ``fence()``/``submit()`` — batches behind the failed one still drain
    in order first.

``RouteEconomics``
    The device-encode tier is gated by *applicability* (route_ok) and
    *health* (decline hysteresis), but never by *profitability*: on a
    backend where the kernels execute slowly (the CPU backend), the
    device tier can cost more wall time than the host block
    encode it replaces while every probe still "succeeds".  This tracker
    keeps an EWMA of measured seconds/row for both paths and routes
    batches to the cheaper one, re-probing the loser periodically
    (``input.tpu_encode_probe_every``) so a recovered device wins back
    the traffic.  On the CPU backend the host path wins ~8x and the
    executor becomes host-stage-bound, which is the point; which path
    wins on a chip is recorded in PERF.md (on the first v5e run the
    host block encoder won at full batches).

``LaneSet``
    N per-device lanes, each an ``InflightWindow`` with its own fetcher
    thread and submit-ahead depth, fed round-robin by the ingest thread
    (ParPaRaw's parallel-lane shape: log decode has no cross-record
    state, so lanes never need to talk).  The pop function runs
    concurrently across lanes but returns an *emit closure* instead of
    enqueueing directly; a single FIFO sequencer (a ticket turnstile)
    runs those closures in global submit order, so blocks reach the
    merger in exactly the order batches were ingested no matter which
    lane finished first.  ``fence()`` fences **all** lanes — every
    synchronous-emit path (breaker degradation, Record path, shutdown
    drain) keeps its ordering barrier across the whole lane set.

Metrics: ``inflight_depth`` gauge (total in-flight across lanes),
``lane_depth`` (deepest lane) and per-lane ``lane{i}_depth`` gauges,
``overlap_stall_seconds``, ``dispatch_seconds`` (submit-side
pack+dispatch, recorded by the handler), ``fetch_seconds``
(fetch-behind stage wall), and ``encode_route_device`` /
``encode_route_host`` batch counters (per-lane seconds/row ride as
``lane{i}_route_*_spr`` gauges).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Optional

from ..obs.trace import tracer as _tracer
from ..utils.metrics import registry as _metrics

DEFAULT_INFLIGHT = 2
DEFAULT_PROBE_EVERY = 256
# the loser path must be this much slower (seconds/row) before traffic
# moves; hysteresis against flapping on noisy single-batch samples
ECON_MARGIN = 1.5
# EWMA weight of the newest sample (small history, fast adaptation)
ECON_ALPHA = 0.4
# a device tier at or under this measured seconds/row is performing at
# accelerator levels — no host path can beat it, so the comparison
# sample (one host-routed batch) is never paid.  Only a device tier
# slower than ~100K rows/s (the CPU backend) triggers the
# host probe at all.
DEVICE_OK_SPR = 1e-5


class InflightWindow:
    """Bounded FIFO of submitted batches with a fetch-behind worker.

    ``pop_fn(entry)`` runs on the fetcher thread and must do the fetch +
    encode + enqueue for one entry; entries complete in submit order.
    ``depth=0`` disables the worker: ``submit`` pops inline (strictly
    serial, the pre-overlap behavior) — the degenerate window tests and
    single-threaded debugging use this.
    """

    def __init__(self, depth: int, pop_fn: Callable, name: str = "tpu",
                 supervisor=None, gauge: str = "inflight_depth"):
        self.depth = max(0, int(depth))
        self._pop_fn = pop_fn
        self._name = name
        self._supervisor = supervisor
        self._gauge = gauge
        self._lock = threading.Lock()
        self._nonfull = threading.Condition(self._lock)
        self._nonempty = threading.Condition(self._lock)
        self._idle = threading.Condition(self._lock)
        self._queue: deque = deque()
        self._popping = False      # fetcher is inside pop_fn
        self._pending_exc: Optional[BaseException] = None
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        _metrics.init_gauge(gauge, 0)

    # -- ingest side -------------------------------------------------------
    def submit(self, entry) -> None:
        """Queue one submitted batch; blocks while the window is full
        (backpressure), re-raising any stashed fetcher exception."""
        if self.depth == 0:
            self._pop_fn(entry)
            return
        self._ensure_thread()
        t0 = time.perf_counter()
        with self._lock:
            self._raise_pending_locked()
            if self._full_locked():
                # the ingest thread blocked on the fetcher: a sub-span
                # of the caller's ``submit`` stage, the same seconds
                # ``overlap_stall_seconds`` adds below
                with _tracer.sub(_tracer.bound(), "window_wait", "submit"):
                    while self._full_locked():
                        self._nonfull.wait(timeout=0.5)
                        self._raise_pending_locked()
            self._queue.append(entry)
            _metrics.set_gauge(self._gauge,
                               len(self._queue) + (1 if self._popping else 0))
            self._nonempty.notify()
        stalled = time.perf_counter() - t0
        if stalled > 1e-4:
            _metrics.add_seconds("overlap_stall_seconds", stalled)

    def _full_locked(self) -> bool:
        return len(self._queue) + (1 if self._popping else 0) >= self.depth

    def fence(self) -> None:
        """Block until every submitted batch has been fetched and
        emitted (the in-flight window is empty and the fetcher idle),
        then re-raise any exception the fetcher stashed.  This is the
        ordering barrier every synchronous-emit path takes before
        bypassing the window (breaker-open scalar batches, Record-path
        encodes, shutdown drain)."""
        if self.depth == 0:
            return
        with self._lock:
            while self._queue or self._popping:
                self._idle.wait(timeout=0.5)
            self._raise_pending_locked()

    def pending(self) -> int:
        with self._lock:
            return len(self._queue) + (1 if self._popping else 0)

    def close(self) -> None:
        """Stop the fetcher after the queue drains (tests/shutdown)."""
        if self.depth == 0 or self._thread is None:
            return
        self.fence()
        with self._lock:
            self._closed = True
            self._nonempty.notify_all()
        self._thread.join(timeout=5)

    # -- fetcher side ------------------------------------------------------
    def _raise_pending_locked(self) -> None:
        if self._pending_exc is not None:
            exc, self._pending_exc = self._pending_exc, None
            raise exc

    def _ensure_thread(self) -> None:
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._closed = False
            name = f"{self._name}-fetch"
            if self._supervisor is not None:
                self._thread = self._supervisor.spawn(
                    self._run, name, exhausted="exit")
            else:
                self._thread = threading.Thread(
                    target=self._run, name=name, daemon=True)
                self._thread.start()

    def _run(self) -> None:
        while True:
            with self._lock:
                while not self._queue and not self._closed:
                    self._nonempty.wait(timeout=0.5)
                if self._closed and not self._queue:
                    self._idle.notify_all()
                    return
                entry = self._queue.popleft()
                self._popping = True
                _metrics.set_gauge(self._gauge, len(self._queue) + 1)
                self._nonfull.notify()
            t0 = time.perf_counter()
            try:
                self._pop_fn(entry)
            except BaseException as e:  # noqa: BLE001 - ferried to ingest
                # the pop fn already owns degradation (breaker + scalar
                # fallback); anything it lets out is the legacy fail-
                # fast contract and belongs on the ingest thread
                exc = e
            else:
                exc = None
            _metrics.add_seconds("fetch_seconds", time.perf_counter() - t0)
            with self._lock:
                if exc is not None and self._pending_exc is None:
                    self._pending_exc = exc
                self._popping = False
                _metrics.set_gauge(self._gauge, len(self._queue))
                self._nonfull.notify()
                if not self._queue:
                    self._idle.notify_all()


class _Sequencer:
    """FIFO ticket turnstile: emits happen in ticket order.

    ``ticket()`` hands out monotonically increasing tickets at submit
    time; a lane that finished its fetch+encode calls ``wait_turn(t)``
    before emitting and ``done(t)`` after (or instead, when it failed
    and has nothing to emit — ``done`` alone releases the turnstile so
    one failed batch can never wedge the lanes behind it).  ``done`` is
    idempotent and order-independent: completed tickets park in a set
    and the cursor advances over every contiguous finished ticket."""

    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._issued = 0
        self._next = 0
        self._finished = set()

    def ticket(self) -> int:
        with self._lock:
            t = self._issued
            self._issued += 1
            return t

    def wait_turn(self, ticket: int) -> None:
        with self._lock:
            while self._next != ticket:
                self._cond.wait(timeout=0.5)

    def done(self, ticket: int) -> None:
        with self._lock:
            if ticket < self._next:
                return  # already advanced past (idempotent)
            self._finished.add(ticket)
            while self._next in self._finished:
                self._finished.discard(self._next)
                self._next += 1
            self._cond.notify_all()


class LaneSet:
    """N per-device dispatch lanes behind one FIFO sequencer.

    Each lane is an ``InflightWindow`` (own fetcher thread, own
    submit-ahead ``depth``); ``submit`` assigns a global ticket and
    round-robins entries across lanes, so device decode / D2H / host
    encode for several batches run concurrently on several devices while
    the sequencer still emits blocks in strict submit order.

    Pop contract (different from ``InflightWindow``'s!): ``pop_fn(
    payload, lane)`` runs concurrently on the lane fetcher threads and
    must return either ``None`` or a zero-argument *emit closure*; the
    LaneSet runs that closure under the sequencer turnstile.  An
    exception out of ``pop_fn`` keeps the InflightWindow ferry contract
    (stashed, re-raised on the ingest thread at the lane's next
    ``submit``/``fence``) and releases the failed ticket so later
    batches still drain in order.

    ``lanes=1`` is byte-for-byte the PR 4 single-window executor (the
    turnstile is always open for the only in-order lane)."""

    def __init__(self, depth: int, pop_fn: Callable, lanes: int = 1,
                 name: str = "tpu", supervisor=None):
        self.lanes = max(1, int(lanes))
        self.depth = max(0, int(depth))
        self._pop_fn = pop_fn
        self._seq = _Sequencer()
        self._rr = 0
        self._submit_lock = threading.Lock()
        multi = self.lanes > 1
        self._windows = [
            InflightWindow(
                depth, self._lane_pop, name=f"{name}-lane{i}" if multi
                else name, supervisor=supervisor,
                gauge=f"lane{i}_depth" if multi else "inflight_depth")
            for i in range(self.lanes)
        ]
        if multi:
            _metrics.init_gauge("inflight_depth", 0)
            _metrics.init_gauge("lane_depth", 0)

    # -- ingest side -------------------------------------------------------
    def next_lane(self) -> int:
        """Reserve the next round-robin lane index (callers that need
        the lane's device *before* building the submit payload)."""
        with self._submit_lock:
            lane = self._rr
            self._rr = (self._rr + 1) % self.lanes
            return lane

    def submit(self, lane: int, payload) -> None:
        """Ticket + enqueue one batch on ``lane``; blocks while that
        lane's window is full (backpressure), re-raising any ferried
        fetcher exception.  Tickets are issued in call order under one
        lock, so emission order is exactly submission order."""
        with self._submit_lock:
            ticket = self._seq.ticket()
            try:
                self._windows[lane % self.lanes].submit(
                    (ticket, lane, payload))
            except BaseException:
                # the window refused the entry (ferried fetcher
                # exception re-raised, depth-0 inline pop failed):
                # release the ticket or the sequencer wedges every
                # later batch behind a turn that can never come
                self._seq.done(ticket)
                raise
        self._update_depth_gauges()

    def fence(self) -> None:
        """Fence every lane (and therefore the sequencer: an empty lane
        set has run every emit closure).  All lanes are fenced even when
        one re-raises a ferried exception — the first exception
        propagates after the others have drained, so a synchronous emit
        after a throwing fence still cannot overtake in-flight work."""
        pending_exc = None
        for w in self._windows:
            try:
                w.fence()
            except BaseException as e:  # noqa: BLE001 - ferried, re-raised below
                if pending_exc is None:
                    pending_exc = e
        self._update_depth_gauges()
        if pending_exc is not None:
            raise pending_exc

    def pending(self) -> int:
        return sum(w.pending() for w in self._windows)

    def close(self) -> None:
        for w in self._windows:
            w.close()

    def _update_depth_gauges(self) -> None:
        if self.lanes <= 1:
            return  # the single window owns inflight_depth itself
        depths = [w.pending() for w in self._windows]
        _metrics.set_gauge("inflight_depth", sum(depths))
        _metrics.set_gauge("lane_depth", max(depths))

    # -- lane fetcher side -------------------------------------------------
    def _lane_pop(self, entry) -> None:
        """Runs on a lane's fetcher thread: compute (concurrent), then
        emit under the sequencer turnstile (strict submit order)."""
        ticket, lane, payload = entry
        try:
            emit = self._pop_fn(payload, lane)
            self._seq.wait_turn(ticket)
            if emit is not None:
                emit()
        finally:
            # always release the turnstile — a failed batch (ferried
            # fail-fast exception) must not wedge the lanes behind it
            self._seq.done(ticket)


def resolve_lanes(config, mesh_mode: str = "auto"):
    """Resolve ``input.tpu_lanes`` to (lane_count, per-lane devices).

    Default ("auto", same precedent as ``input.tpu_mesh``): one lane per
    local device when more than one *real* accelerator is visible, else
    1 — so CPU test meshes and single-chip hosts keep the PR 4
    single-window executor.  An explicit integer engages anywhere
    (tests/benches set ``tpu_lanes = 2`` on the forced-host CPU mesh);
    more lanes than devices cycle over them (extra lanes still overlap
    host encode).  Lane dispatch and the sharded decode mesh are
    mutually exclusive — lanes give each chip its *own* batches (no
    cross-chip sync on the hot path), the mesh shards one batch across
    chips — so ``tpu_lanes > 1`` with ``tpu_mesh = "on"`` is a config
    error, and auto-resolved lanes > 1 disable the mesh.  Multi-host:
    lanes span only this host's chips (``jax.local_devices()``), like
    the mesh's dp axis — each host lane-dispatches its own stream.

    Lane 0 of a single-lane set stays on the default device (``None``)
    so the resolved setup is identical to the pre-lane executor."""
    from ..config import ConfigError

    req = config.lookup_int(
        "input.tpu_lanes",
        "input.tpu_lanes must be an integer (device lanes)", None)
    if req is not None and req < 1:
        raise ConfigError("input.tpu_lanes must be >= 1")
    if req is not None and req > 1 and mesh_mode == "on":
        raise ConfigError(
            'input.tpu_lanes > 1 and input.tpu_mesh = "on" are mutually '
            "exclusive (lanes give each chip its own batches; the mesh "
            "shards one batch across chips)")
    if req == 1:
        return 1, [None]
    import jax

    if req is None:
        if mesh_mode == "on" or jax.default_backend() == "cpu":
            return 1, [None]
        devs = list(jax.local_devices())
        if len(devs) <= 1:
            return 1, [None]
        return len(devs), devs
    devs = list(jax.local_devices())
    return req, [devs[i % len(devs)] for i in range(req)]


class RouteEconomics:
    """Measured seconds/row for the device-encode tier vs the host
    block-encode path; ``allow_device()`` routes each batch to the
    cheaper one with periodic re-probes of the loser.

    Probing order: the device tier goes first; while its measured
    seconds/row stays at accelerator levels (``DEVICE_OK_SPR``) the host
    path is never paid at all.  Only a device tier measuring slow buys
    one host batch for the comparison, after which the loser re-probes
    every ``probe_every`` batches.  ``enabled=False`` pins the legacy
    always-device behavior."""

    def __init__(self, enabled: bool = True,
                 probe_every: int = DEFAULT_PROBE_EVERY,
                 margin: float = ECON_MARGIN,
                 ok_spr: float = DEVICE_OK_SPR,
                 label: Optional[str] = None):
        self.enabled = enabled
        self.probe_every = max(2, int(probe_every))
        self.margin = margin
        self.ok_spr = ok_spr
        # label ("lane0", ...) exports this tracker's EWMAs as gauges —
        # per-lane economics so one sick chip degrades alone, visibly
        self.label = label
        self._lock = threading.Lock()
        # EWMA seconds/row per path: "fused" (single-program
        # decode→encode, tpu/fused_routes.py), "device" (split decode +
        # device encode), "host" (split decode + host block encode)
        self._spr = {"fused": None, "device": None, "host": None}
        self._batches = 0
        self._fused_batches = 0
        # steady-state winner per comparison arm, for the degradation
        # journal: the device/fused tiers are the probe-first defaults,
        # so the first measured re-route away from them (and every flip
        # back) is one economics_switch event
        self._winner = {"split": "device", "fused": "fused"}

    def allow_fused(self) -> bool:
        """Fused-vs-split arm of the economics, decided at submit time
        (the fused/split choice changes what gets dispatched).  Probing
        order mirrors allow_device: the fused tier goes first; while it
        measures at accelerator speed the split path is never paid.  A
        slow fused tier buys split batches for the comparison, after
        which the loser re-probes every ``probe_every`` batches.  The
        split path's own device-vs-host economics stay in
        ``allow_device`` — this arm only picks which pipeline runs."""
        if not self.enabled:
            return True
        with self._lock:
            self._fused_batches += 1
            fused = self._spr["fused"]
            split = [v for v in (self._spr["device"], self._spr["host"])
                     if v is not None]
            best_split = min(split) if split else None
            if fused is None:
                return True          # no fused sample yet: probe it
            if best_split is None:
                # healthy fused tier: never pay the split comparison; a
                # slow-measuring one buys split batches to compare
                return fused <= self.ok_spr
            probe = self._fused_batches % self.probe_every == 0
            if fused > best_split * self.margin:
                return probe         # fused losing: re-probe on schedule
            if best_split > fused * self.margin:
                return not probe     # split losing: re-sample on schedule
            return True              # within noise: prefer fused

    def allow_device(self) -> bool:
        if not self.enabled:
            return True
        with self._lock:
            self._batches += 1
            dev, host = self._spr["device"], self._spr["host"]
            if dev is None:
                return True          # no device sample yet: probe it
            if host is None:
                # healthy accelerator: never pay the host comparison;
                # a slow-measuring device buys one host batch to compare
                return dev <= self.ok_spr
            probe = self._batches % self.probe_every == 0
            if dev > host * self.margin:
                return probe         # device losing: re-probe on schedule
            if host > dev * self.margin:
                return not probe     # host losing: re-sample it on schedule
            return True              # within noise: prefer the device tier

    def observe(self, path: str, rows: int, seconds: float) -> None:
        if not self.enabled or rows <= 0 or path not in self._spr:
            return
        spr = seconds / rows
        switches = []
        with self._lock:
            prev = self._spr[path]
            ewma = spr if prev is None else prev + ECON_ALPHA * (spr - prev)
            self._spr[path] = ewma
            switches = self._winner_flips_locked()
        _metrics.inc(f"encode_route_{path}")
        if self.label is not None:
            _metrics.set_gauge(f"{self.label}_route_{path}_spr", ewma)
        for arm, old, new, new_spr, old_spr in switches:
            from ..obs import events as _events

            _events.emit(
                "economics", "economics_switch", route=arm,
                detail=f"{old} -> {new} "
                       f"({old}={old_spr:.3g} s/row, {new}={new_spr:.3g})",
                lane=(int(self.label[4:]) if self.label
                      and self.label.startswith("lane") else None),
                cost=new_spr, cost_unit="s_per_row",
                msg=f"route economics [{self.label or 'lane0'}/{arm}]: "
                    f"{old} -> {new} (measured {new_spr:.3g} s/row vs "
                    f"{old_spr:.3g})")

    def _winner_flips_locked(self):
        """Steady-state winner changes (margin-hysteretic, mirroring
        allow_device/allow_fused routing) for the journal; returns
        [(arm, old, new, new_spr, old_spr), ...]."""
        flips = []
        dev, host = self._spr["device"], self._spr["host"]
        if dev is not None and host is not None:
            old = self._winner["split"]
            new = old
            if dev > host * self.margin:
                new = "host"
            elif host > dev * self.margin:
                new = "device"
            if new != old:
                self._winner["split"] = new
                flips.append(("split", old, new,
                              dev if new == "device" else host,
                              host if new == "device" else dev))
        fused = self._spr["fused"]
        split = [v for v in (dev, host) if v is not None]
        best_split = min(split) if split else None
        if fused is not None and best_split is not None:
            old = self._winner["fused"]
            new = old
            if fused > best_split * self.margin:
                new = "split"
            elif best_split > fused * self.margin:
                new = "fused"
            if new != old:
                self._winner["fused"] = new
                flips.append(("fused", old, new,
                              fused if new == "fused" else best_split,
                              best_split if new == "fused" else fused))
        return flips

    def snapshot(self) -> dict:
        with self._lock:
            return {"fused_s_per_row": self._spr["fused"],
                    "device_s_per_row": self._spr["device"],
                    "host_s_per_row": self._spr["host"],
                    "batches": self._batches}

    @classmethod
    def from_config(cls, config, label: Optional[str] = None
                    ) -> "RouteEconomics":
        enabled = config.lookup_bool(
            "input.tpu_encode_economics",
            "input.tpu_encode_economics must be a boolean", True)
        probe_every = config.lookup_int(
            "input.tpu_encode_probe_every",
            "input.tpu_encode_probe_every must be an integer (batches)",
            DEFAULT_PROBE_EVERY)
        return cls(enabled=enabled, probe_every=probe_every, label=label)


def inflight_depth_from_config(config) -> int:
    from ..config import ConfigError

    depth = config.lookup_int(
        "input.tpu_inflight",
        "input.tpu_inflight must be an integer (batches)", DEFAULT_INFLIGHT)
    if depth < 0:
        raise ConfigError("input.tpu_inflight must be >= 0")
    return depth
