"""Structured degradation events: the flight recorder's journal half.

The pipeline has ~10 distinct degradation rungs; before this module
each surfaced as a scattered stderr print plus (sometimes) a bare
counter, so an operator watching throughput fall could not reconstruct
*which* rung fired, *when*, or *what it cost*.  Every decline site now
calls :func:`emit` with a **typed reason code** — the single emitter:

=========================  =================================================
reason                     fired by
=========================  =================================================
``watchdog_decline``       device_common.guarded_compile_call deadline
``busy_decline``           guarded call queued behind an in-flight compile
``breaker_trip``           tpu/breaker.py CLOSED→OPEN (errors or ratio)
``breaker_recover``        tpu/breaker.py →CLOSED after a cured probe
``economics_switch``       overlap.RouteEconomics / framing.FramingEconomics
                           steady-state winner flip (device↔host,
                           fused↔split, framing↔hostpack)
``aot_reject``             tpu/aot.py boot/entry artifact rejection
``framing_decline``        tpu/framing.py device-framing decline
``fused_fallback``         tpu/batch.py fused tier → split path
``device_error``           tpu/batch.py device/XLA exception (breaker feed)
``tenant_shed``            tenancy/admission.py token-bucket denial
``queue_drop``             utils/bounded_queue.py + tenancy/fairqueue.py
                           shed/drop (cause + tenant attributed)
``rendezvous_failover``    fleet/federation.py — the agreed rendezvous
                           (lowest active rank) moved to another host
``fleet_rebalance``        fleet/federation.py — per-host traffic shares
                           redistributed (join/drain/eviction/capacity)
``roster_restore``         fleet/federation.py — boot used the durable
                           roster journal as bootstrap candidates
``slo_burn``               obs/slo.py — an objective's error budget is
                           burning faster than its threshold on BOTH
                           evaluation windows (fast + slow)
``slo_recover``            obs/slo.py — a burning objective fell back
                           under its burn threshold
``perf_regression``        obs/sentinel.py — a route's live throughput
                           (or fetch cost) sustained a drop against
                           its BENCH-seeded baseline
``spill_begin``            durability/manager.py — the queue crossed
                           the spill watermark and the first overflow
                           batch landed in the on-disk WAL
``spill_replay``           tpu/batch.py replay_spilled — one replay
                           round re-dispatched spilled records through
                           block_submit
``replay_complete``        durability/manager.py — every spilled
                           record has been sink-acknowledged; the
                           backlog is empty
``replay_stall``           durability/manager.py watchdog — nonzero
                           unacked backlog with a pinned replay cursor
                           (SLO-declarable: a stuck replay burns an
                           objective instead of rotting silently)
``admission_tighten``      control/plane.py — the burn-driven AIMD
                           loop multiplicatively tightened a tenant's
                           admitted token-bucket rate (cost = the
                           applied lines/sec rate)
``admission_relax``        control/plane.py — additive recovery raised
                           a controller-tightened tenant rate back
                           toward its configured ceiling
``share_decay``            control/plane.py — sustained local burn /
                           breaker / spill pressure decayed this
                           host's advertised fleet capacity weight
``share_restore``          control/plane.py — pressure cleared; the
                           advertised capacity weight recovered a step
``control_freeze``         control/plane.py — a controller tick was
                           skipped (the control_freeze fault drill /
                           controller death): everything stays frozen
                           at last-applied
``durability_reject``      durability/manager.py — ``mode = require``
                           hard-failed an offer (spill budget
                           exhausted or segment append error); the
                           batch is refused, not silently shed
=========================  =================================================

Each event carries ``(ts, site, reason)`` plus whatever context the
site has — ``route``/``lane``/``tenant``/``detail`` — and a **cost
hint** (``cost`` + ``cost_unit``: lines shed, seconds burned, rows
re-decoded), lands in a bounded ring served under ``/healthz``'s
``events`` section, mirrors to the per-reason ``events_{reason}``
counter family (+ the ``degradation_events`` aggregate), and
optionally appends to a JSONL sink.

``emit(..., msg=...)`` also writes the site's legacy stderr line, so
the one emitter owns both the structured journal and the operator
console — decline sites no longer hand-roll prints.

Config (``[metrics]``)::

    events_ring = 256            # journal depth (default)
    events_path = "ev.jsonl"     # optional JSONL sink
    events_max_mb = 64           # rotate the sink past this size
    events_keep = 3              # rotated files kept (ev.jsonl.1 ...)

Fleet correlation: once ``fleet/federation.py`` calls
:meth:`Journal.set_rank`, every event carries a ``rank`` field so the
``/fleetz`` union of rings stays attributable per host.

Cost model: events fire only on degradation (the healthy hot path
never calls in here), so one lock + deque append + counter bump per
occurrence is noise even under a sustained flood — the ring bounds
memory and the stderr half stays rate-limited where the legacy sites
rate-limited it.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from .sink import JsonlSink

DEFAULT_RING = 256

# typed reason codes — the closed vocabulary FC06-adjacent tooling and
# the tests key on; emit() rejects anything else so a typo'd reason is
# a crash in CI, not a silent new counter family
REASONS = (
    "watchdog_decline",
    "busy_decline",
    "breaker_trip",
    "breaker_recover",
    "economics_switch",
    "aot_reject",
    "framing_decline",
    "fused_fallback",
    "device_error",
    "tenant_shed",
    "queue_drop",
    "rendezvous_failover",
    "fleet_rebalance",
    "roster_restore",
    "slo_burn",
    "slo_recover",
    "perf_regression",
    "spill_begin",
    "spill_replay",
    "replay_complete",
    "replay_stall",
    "admission_tighten",
    "admission_relax",
    "share_decay",
    "share_restore",
    "control_freeze",
    "durability_reject",
)
_REASON_SET = frozenset(REASONS)


class Journal:
    """Bounded degradation-event ring (module singleton ``journal``)."""

    def __init__(self, ring: int = DEFAULT_RING):
        self._lock = threading.Lock()
        self._ring: "deque[dict]" = deque(maxlen=ring)
        self._counts: Dict[str, int] = {}
        self._total = 0
        self._sink = JsonlSink("events")
        self._rank: Optional[int] = None

    def configure(self, ring: int = DEFAULT_RING,
                  path: Optional[str] = None,
                  max_mb: Optional[float] = None, keep: int = 3) -> None:
        with self._lock:
            self._ring = deque(self._ring, maxlen=max(1, int(ring)))
        self._sink.open(path, max_mb=max_mb, keep=keep)

    def set_rank(self, rank: Optional[int]) -> None:
        """Fleet correlation: stamp every subsequent event with this
        host's fleet rank (federation.Fleet.start)."""
        self._rank = rank

    def emit(self, site: str, reason: str, *,
             detail: Optional[str] = None, route: Optional[str] = None,
             lane: Optional[int] = None, tenant: Optional[str] = None,
             cost: Optional[float] = None, cost_unit: Optional[str] = None,
             msg: Optional[str] = None) -> dict:
        """Record one degradation event.  ``msg`` (when given) is the
        operator's stderr line — the legacy print the structured event
        replaces."""
        if reason not in _REASON_SET:
            raise ValueError(f"unknown degradation reason: {reason!r} "
                             f"(known: {', '.join(REASONS)})")
        event = {"ts": round(time.time(), 4), "site": site,
                 "reason": reason}
        if self._rank is not None:
            event["rank"] = self._rank
        if detail is not None:
            event["detail"] = str(detail)
        if route is not None:
            event["route"] = route
        if lane is not None:
            event["lane"] = int(lane)
        if tenant is not None:
            event["tenant"] = tenant
        if cost is not None:
            event["cost"] = round(float(cost), 6)
            event["cost_unit"] = cost_unit or "units"
        with self._lock:
            self._ring.append(event)
            self._counts[reason] = self._counts.get(reason, 0) + 1
            self._total += 1
        # counter mirror: the registry has its own lock, taken OUTSIDE
        # ours (no nesting, no ordering hazard)
        from ..utils.metrics import registry as _metrics

        _metrics.inc("degradation_events")
        _metrics.inc(f"events_{reason}")
        if msg:
            print(msg, file=sys.stderr)
        self._sink.write(event)
        return event

    # -- export ------------------------------------------------------------
    def snapshot(self) -> List[dict]:
        """The event ring, oldest first (JSON-safe dicts)."""
        with self._lock:
            return [dict(e) for e in self._ring]

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def total(self) -> int:
        with self._lock:
            return self._total

    def health_section(self) -> dict:
        """The ``events`` section of the ``/healthz`` document."""
        with self._lock:
            return {"total": self._total,
                    "counts": dict(self._counts),
                    "ring": [dict(e) for e in self._ring]}

    def reset(self) -> None:
        """Tests only: empty the ring and counts (the registry's
        mirrored counters reset separately via registry.reset())."""
        with self._lock:
            self._ring.clear()
            self._counts.clear()
            self._total = 0
        self._rank = None

    def close(self) -> None:
        self._sink.close()


# the process-wide journal every degradation site imports
journal = Journal()


def emit(site: str, reason: str, **kw) -> dict:
    """Module-level convenience over ``journal.emit`` (the form the
    decline sites call)."""
    return journal.emit(site, reason, **kw)


def configure_from(config) -> None:
    """Wire ``[metrics] events_ring``/``events_path`` (+ the
    ``events_max_mb``/``events_keep`` rotation pair) — pipeline boot;
    no keys = defaults, ring only."""
    ring = config.lookup_int(
        "metrics.events_ring",
        "metrics.events_ring must be an integer (events kept)",
        DEFAULT_RING)
    path = config.lookup_str(
        "metrics.events_path",
        "metrics.events_path must be a string (file)")
    max_mb = config.lookup_float(
        "metrics.events_max_mb",
        "metrics.events_max_mb must be a number (MB before the JSONL "
        "sink rotates)")
    keep = config.lookup_int(
        "metrics.events_keep",
        "metrics.events_keep must be an integer (rotated files kept)", 3)
    try:
        journal.configure(ring=ring, path=path, max_mb=max_mb, keep=keep)
    except OSError as e:
        print(f"events: cannot open {path} ({e}); journal keeps the "
              "in-memory ring only", file=sys.stderr)
        journal.configure(ring=ring)
