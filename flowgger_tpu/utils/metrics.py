"""Per-stage counters and latency histograms.

The reference has no observability at all — diagnostics are bare stderr
writes and its declared ``log`` dependency is never used (SURVEY.md §5).
This registry gives every pipeline stage cheap thread-safe counters and
the batched decode path latency histograms, reported as one JSON line
on a configurable interval:

    [metrics]
    interval = 10            # seconds; 0/absent = disabled
    path = "metrics.jsonl"   # default: stderr

Counter names: input_lines, decoded_records, decode_errors,
encode_errors, invalid_utf8, enqueued, output_written, output_errors,
batches, batch_lines, fallback_rows.  ``batch_seconds`` is a histogram
(count/sum/min/max/p50/p99 over a sliding window); the named histogram
family (``observe(name, value)``) adds ``queue_wait_seconds`` (sampled
sojourn time of queued items, bounded_queue/fairqueue) and
``e2e_batch_seconds`` (flush→emit wall per dispatched batch,
tpu/batch.py) so latency, not just throughput, is measurable.

Overlap executor stages report as cumulative seconds
(``dispatch_seconds`` submit-side pack+dispatch, ``fetch_seconds``
fetch-behind wall, ``overlap_stall_seconds`` window backpressure) plus
the ``inflight_depth`` gauge — see tpu/overlap.py.

Lane dispatch / compile stability (tpu/overlap.py LaneSet,
tpu/device_common.py cache+prewarm, tpu/pack.py bucketing):
``lane_depth`` (deepest lane) and per-lane ``lane{i}_depth`` gauges,
``lane{i}_rows`` counters, per-lane ``lane{i}_route_{path}_spr``
EWMA gauges, ``distinct_compiled_shapes`` gauge (every (rows, max_len)
shape packed so far), and the ``compile_cache_hits`` /
``compile_cache_misses`` / ``prewarmed_shapes`` counters — a second
cold process of an identical config with ``input.tpu_compile_cache_dir``
set should report zero misses.

Fused decode→encode routes (tpu/fused_routes.py): ``fused_rows`` (rows
emitted through a fused single-program route, plus the per-route
``fused_rows_{route}`` family), ``fused_fallbacks`` (batches that
declined from the fused tier to the split path, plus
``fused_fallbacks_{route}``), and the per-route
``fetch_bytes_per_row_{route}`` / ``emit_bytes_per_row_{route}`` gauges
— the fused acceptance is fetch under emit on every route.  Fused
compile-watchdog declines fold into the shared
``device_encode_compile_declines`` counter; per-lane fused-vs-split
economics export as ``lane{i}_route_fused_spr`` alongside the
device/host gauges.

Multi-tenant serving (tenancy/): per-tenant ``tenant_{name}_lines`` /
``tenant_{name}_bytes`` (admitted), ``tenant_{name}_drops`` (admission
denials), ``tenant_{name}_shed`` (queue-pressure sheds) counters and
the ``tenant_{name}_state`` gauge (0 admitting / 1 throttled /
2 shed), plus the aggregate ``tenant_lines/bytes/drops/shed``.  Queue
sheds carry per-cause labels: ``queue_dropped_{policy}`` alongside the
aggregate ``queue_dropped``, and ``queue_shed_during_drain`` after the
pipeline enters its drain phase.  Template mining reports
``template_hits``, the ``tenant_templates_distinct`` gauge (and its
per-tenant ``tenant_{name}_templates_distinct`` form), and the
per-template ``tenant_{name}_template_{id}`` counter family (capped;
overflow ids fold into ``tenant_{name}_template_overflow``).

Fleet federation (fleet/): ``fleet_hosts_{state}`` gauges (the local
host counts toward its own state), per-peer ``fleet_peer{rank}_state``
(0..4 in ladder order), ``fleet_peer{rank}_hb_age_ms`` and
``fleet_peer{rank}_share`` (capacity-weighted traffic share) gauges,
the ``fleet_rendezvous_rank`` gauge (the elected rendezvous; -1 while
none), plus the ``fleet_evictions`` / ``fleet_rejoins`` /
``fleet_hb_send_errors`` / ``fleet_hb_retries`` /
``fleet_roster_saves`` / ``fleet_roster_load_errors`` counters.  The whole ``snapshot()`` is what each host's HTTP health
endpoint serves under ``metrics`` (fleet/health.py) — it is JSON-safe
by construction (counters and gauges are numbers, histograms flat
dicts), so the health document needs no second serialization layer.

Observability layer (obs/): degradation rungs journal through
``obs.events`` and mirror here as the ``degradation_events`` aggregate
plus the per-reason ``events_{reason}`` counter family; the whole
registry renders in the Prometheus text exposition format via
``obs.prom.render`` (``GET /metrics``).

SLO plane (obs/slo.py + obs/sentinel.py): per-batch emits land the
``route_rows_{route}`` counter family and the per-route
``e2e_batch_seconds_{route}`` histogram family (tpu/batch.py
``_finish_batch``); the weighted-fair queue lands per-tenant sojourn
samples as ``queue_wait_seconds_{tenant}``.  The SLO engine exports
``slo_{name}_burn_rate`` / ``slo_{name}_budget_remaining`` gauges per
configured objective, and the regression sentinel exports
``sentinel_{route}_ratio`` / ``sentinel_{route}_baseline`` gauges.
Histograms additionally support *observe taps*
(:meth:`Registry.add_observe_tap`) — the SLO engine's per-sample
threshold accounting rides the existing ``observe()`` call with one
dict lookup when no tap is registered.

The declaration tuples below
(``_COUNTERS``/``_SECONDS_NAMES``/``_GAUGE_NAMES``/
``_HISTOGRAM_NAMES``/``_FAMILY_PATTERNS``) are the metric-name
namespace flowcheck rule FC06 resolves every literal call-site name
against — a typo'd counter is a CI finding, not a silent new series.
"""

from __future__ import annotations

import itertools
import json
import sys
import threading
import time
from typing import Dict, Optional, Tuple

_COUNTERS = (
    "input_lines", "decoded_records", "decode_errors", "encode_errors",
    "invalid_utf8", "enqueued", "output_written", "output_errors",
    "batches", "batch_lines", "fallback_rows",
    # robustness / supervision layer
    "queue_dropped", "drain_stragglers", "drain_flush_errors",
    "sink_reconnects", "sink_failovers",
    "thread_crashes", "thread_restarts", "input_reconnects",
    "device_decode_errors", "breaker_trips", "breaker_recoveries",
    # overlap executor (tpu/overlap.py): D2H bytes the compaction +
    # constant-elision path avoided, and encode-route economics picks
    "fetch_bytes_saved", "encode_route_device", "encode_route_host",
    "encode_route_fused",
    # compile stability (tpu/device_common.py): persistent-cache
    # traffic, startup kernel prewarm progress, and the compile
    # watchdog's decline/health accounting
    "compile_cache_hits", "compile_cache_misses", "prewarmed_shapes",
    "prewarm_aot_skips", "device_encode_compile_declines",
    # device-encode tier accounting (tpu/device_common.py driver)
    "device_encode_declined", "device_encode_rows",
    "device_encode_scalar_rows", "device_encode_fetch_bytes",
    "device_encode_out_bytes", "device_encode_wide_batches",
    # multi-chip mesh + fused routes + device framing
    "sharded_kernels", "fused_rows", "fused_fallbacks",
    "framing_rows", "framing_declines", "framing_span_fetch_bytes",
    # zero-JIT boot (tpu/aot.py): artifact-store traffic; per-reason
    # rejects ride the aot_rejects_{reason} family
    "aot_hits", "aot_misses", "aot_rejects",
    # multi-tenant serving (tenancy/): aggregate admission and shed
    # counters — the per-tenant family (tenant_{name}_lines/_bytes/
    # _drops/_shed, tenant_{name}_state gauge) materializes on first
    # use, keyed by tenant name
    "tenant_lines", "tenant_bytes", "tenant_drops", "tenant_shed",
    # queue sheds that happened after the pipeline entered its drain
    # phase (bounded_queue.mark_draining): lets a SIGTERM test tell
    # shed lines from delivered lines
    "queue_shed_during_drain",
    # online template mining (tenancy/templates.py): rows mined; the
    # per-template family is tenant_{name}_template_{id} (+ _overflow)
    "template_hits", "template_tap_errors",
    # fleet federation (fleet/): peers evicted by the missed-heartbeat
    # ladder, local rejoins after a discovered self-eviction, and
    # heartbeat deliveries that failed in transit (partition/churn —
    # normal life at fleet scale, counted not logged).  The state
    # gauges (fleet_hosts_{state}, fleet_peer{rank}_state,
    # fleet_peer{rank}_hb_age_ms) materialize when membership starts
    "fleet_evictions", "fleet_rejoins", "fleet_hb_send_errors",
    # self-healing fleet (PR 14): heartbeat-POST retries before a send
    # is declared failed (utils/retry.py full jitter), durable-roster
    # journal writes, and corrupt/unreadable journal loads (each load
    # error is a clean re-rendezvous, not a crash — fleet/roster.py)
    "fleet_hb_retries", "fleet_roster_saves", "fleet_roster_load_errors",
    # degradation journal (obs/events.py): aggregate event count; the
    # per-reason family is events_{reason}
    "degradation_events",
    # zero-loss ingestion (durability/): WAL spill/replay traffic,
    # unreadable segment/cursor loads (each one degrades — recovered
    # prefix, widened at-least-once window — never a crash), failed
    # fsynced appends, sink acks fired/contained, and output drain
    # barriers that expired before the queue fully drained
    "spill_records", "replayed_lines", "spill_load_errors",
    "spill_io_errors", "sink_acks", "sink_ack_errors",
    "drain_barrier_timeouts",
    # control plane (control/plane.py + fleet/proxy.py): controller
    # ticks that applied a change, ticks skipped by the control_freeze
    # drill site, steering-proxy connections routed / bytes pumped /
    # routing failures (no routable host, dial error)
    "control_applies", "control_freezes", "control_ticks",
    "proxy_connections", "proxy_bytes", "proxy_route_errors",
    # the host-device link, counted where the bytes move
    # (tpu/device_common.py h2d/d2h): uploaded batch bytes and the
    # lines' own share of them, the bytes copied back, the times a
    # thread blocked on the link for them, and the arrays whose copy
    # was begun at their program's dispatch; and each dispatched
    # batch's real rows against its padded bucket (tpu/batch.py)
    "h2d_bytes", "packed_line_bytes", "d2h_bytes", "d2h_calls",
    "d2h_prefetched",
    "batch_rows_real", "batch_rows_padded",
    # lines longer than the device's row (input.tpu_max_line_len),
    # counted a batch at a time where the batch is packed (tpu/pack.py,
    # tpu/framing.py): the rows the device sees clipped and the bytes
    # past the row's width that it never sees; and the rows that a
    # block encoder handed to the scalar oracle one by one
    # (tpu/block_common.py finish_block), for any cause: how many, the
    # bytes they came to at the sink, and those of them that were
    # over-length, so that splice_rows - splice_rows_overlen is what
    # fell back for high bytes, pair caps and escapes.  fallback_rows
    # keeps its meaning: splice_rows less the rows that are not UTF-8.
    # overlen_rows_kept: the over-length rows that stayed on the
    # columnar rfc5424 -> GELF encoder (tpu/encode_gelf_block.py), a
    # batch at a time; on that route overlen_rows is overlen_rows_kept
    # + splice_rows_overlen
    "overlen_rows", "overlen_bytes_clipped",
    "splice_rows", "splice_rows_overlen", "splice_bytes_out",
    "overlen_rows_kept",
    # the host block encoders' timestamp text (tpu/block_common.py
    # vals_scratch), once a call: the rows whose stamp the native
    # formatter wrote (json_f64's notation), and the distinct values
    # that went through a Python format function (every other
    # notation, or json_f64 without the library)
    "ts_text_native_rows", "ts_text_python_values",
)

# cumulative per-stage wall-clock accumulators (add_seconds)
_SECONDS_NAMES = (
    "dispatch_seconds", "fetch_seconds", "overlap_stall_seconds",
    "device_fetch_seconds", "encode_seconds",
    "device_encode_declined_seconds",
    "pack_stage_seconds", "pack_slice_seconds", "pack_copy_seconds",
    # the lane fetcher's compute wall per batch, by the route that
    # served it (tpu/batch.py _pop_emit): host sets the pace, fused
    # and device are the economics' probes
    "route_pop_seconds_host", "route_pop_seconds_fused",
    "route_pop_seconds_device",
    # finish_block's scalar loop and the joining of its pieces, a part
    # of encode_seconds on the lane fetcher's thread
    "splice_seconds",
)

# point-in-time gauges with literal names (set_gauge/init_gauge)
_GAUGE_NAMES = (
    "device_breaker_state", "inflight_depth", "lane_depth",
    "distinct_compiled_shapes", "framing_carry_bytes",
    "tenant_templates_distinct", "fleet_rendezvous_rank",
    # durability tier backlog (durability/manager.py): on-disk WAL
    # bytes/segments and the spilled-but-unacked record count the
    # replay-stall watchdog and fleetctl's spill line key on
    "spill_bytes", "spill_segments", "replay_cursor_lag",
    # control plane (control/plane.py): the autoscale signal (desired
    # routable host count) and this host's applied capacity factor
    # (1.0 = configured weight, < 1.0 = share-feedback decay)
    "fleet_desired_hosts", "control_capacity_factor",
)

# sliding-window histogram family (observe)
_HISTOGRAM_NAMES = (
    "batch_seconds", "queue_wait_seconds", "e2e_batch_seconds",
)

# dynamic name families: ``{placeholder}`` stands for one
# ``[A-Za-z0-9_]+`` segment.  FC06 resolves literal call-site names
# against these too (e.g. the literal "aot_rejects_missing_route"
# resolves via "aot_rejects_{reason}"); f-string call sites are by
# construction members of exactly one family here
_FAMILY_PATTERNS = (
    "lane{i}_depth", "lane{i}_rows", "lane{i}_route_{path}_spr",
    "queue_dropped_{policy}",
    "tenant_{name}_lines", "tenant_{name}_bytes", "tenant_{name}_drops",
    "tenant_{name}_shed", "tenant_{name}_state",
    "tenant_{name}_rate_factor",
    "tenant_{name}_templates_distinct",
    "tenant_{name}_template_{id}", "tenant_{name}_template_overflow",
    "fleet_hosts_{state}", "fleet_peer{rank}_state",
    "fleet_peer{rank}_hb_age_ms", "fleet_peer{rank}_share",
    "aot_rejects_{reason}",
    "fused_rows_{route}", "fused_fallbacks_{route}",
    "fetch_bytes_per_row_{route}", "emit_bytes_per_row_{route}",
    "framing_{path}_spr",
    "events_{reason}",
    # SLO / observability plane (obs/slo.py, obs/sentinel.py,
    # tpu/batch.py _finish_batch, tenancy/fairqueue.py)
    "route_rows_{route}",
    "e2e_batch_seconds_{route}", "queue_wait_seconds_{tenant}",
    "slo_{name}_burn_rate", "slo_{name}_budget_remaining",
    "sentinel_{route}_ratio", "sentinel_{route}_baseline",
)


# kind of each dynamic family in _FAMILY_PATTERNS — the fleet-level
# merge (fleet/federation.merge_metric_snapshots) must sum counters
# and pool histograms while leaving point-in-time gauges per-host, and
# a flat snapshot alone cannot tell them apart
_FAMILY_KINDS = (
    ("lane{i}_depth", "gauge"),
    ("lane{i}_rows", "counter"),
    ("lane{i}_route_{path}_spr", "gauge"),
    ("queue_dropped_{policy}", "counter"),
    ("tenant_{name}_state", "gauge"),
    ("tenant_{name}_rate_factor", "gauge"),
    ("tenant_{name}_templates_distinct", "gauge"),
    ("tenant_{name}_template_overflow", "counter"),
    ("tenant_{name}_template_{id}", "counter"),
    ("tenant_{name}_lines", "counter"),
    ("tenant_{name}_bytes", "counter"),
    ("tenant_{name}_drops", "counter"),
    ("tenant_{name}_shed", "counter"),
    ("fleet_hosts_{state}", "gauge"),
    ("fleet_peer{rank}_state", "gauge"),
    ("fleet_peer{rank}_hb_age_ms", "gauge"),
    ("fleet_peer{rank}_share", "gauge"),
    ("aot_rejects_{reason}", "counter"),
    ("fused_rows_{route}", "counter"),
    ("fused_fallbacks_{route}", "counter"),
    ("fetch_bytes_per_row_{route}", "gauge"),
    ("emit_bytes_per_row_{route}", "gauge"),
    ("framing_{path}_spr", "gauge"),
    ("events_{reason}", "counter"),
    ("route_rows_{route}", "counter"),
    ("e2e_batch_seconds_{route}", "histogram"),
    ("queue_wait_seconds_{tenant}", "histogram"),
    ("slo_{name}_burn_rate", "gauge"),
    ("slo_{name}_budget_remaining", "gauge"),
    ("sentinel_{route}_ratio", "gauge"),
    ("sentinel_{route}_baseline", "gauge"),
)

_classify_cache: Dict[str, Optional[str]] = {}
_CLASSIFY_CACHE_MAX = 4096  # /fleetz feeds REMOTE snapshot keys here:
#                             a skewed peer's churning names must not
#                             grow a process-global cache forever
_family_kind_rx = None


def classify_metric(name: str) -> Optional[str]:
    """``"counter" | "seconds" | "gauge" | "histogram" | None`` for a
    metric name, resolving the declared tuples first and then the
    family patterns above (first match wins — patterns are ordered
    most-specific-first where prefixes overlap)."""
    global _family_kind_rx
    cached = _classify_cache.get(name)
    if cached is not None or name in _classify_cache:
        return cached
    if _family_kind_rx is None:
        import re as _re

        def rx(pattern):
            out, pos = [], 0
            for m in _re.finditer(r"\{[A-Za-z0-9_]+\}", pattern):
                out.append(_re.escape(pattern[pos:m.start()]))
                out.append(r"[A-Za-z0-9_]+")
                pos = m.end()
            out.append(_re.escape(pattern[pos:]))
            return _re.compile("".join(out) + r"\Z")

        _family_kind_rx = [(rx(p), kind) for p, kind in _FAMILY_KINDS]
    kind: Optional[str] = None
    if name in _COUNTERS:
        kind = "counter"
    elif name in _SECONDS_NAMES:
        kind = "seconds"
    elif name in _GAUGE_NAMES:
        kind = "gauge"
    elif name in _HISTOGRAM_NAMES:
        kind = "histogram"
    else:
        for pattern, fam_kind in _family_kind_rx:
            if pattern.match(name):
                kind = fam_kind
                break
    if len(_classify_cache) < _CLASSIFY_CACHE_MAX:
        _classify_cache[name] = kind
    return kind


def window_quantiles(sorted_samples) -> Dict[str, float]:
    """p50/p99 over an already-sorted sample list — the ONE definition
    of this registry's summary quantiles.  Histogram.snapshot() and the
    fleet merge (fleet/federation.merge_metric_snapshots) both call it,
    so a per-host quantile change cannot drift from the fleet view."""
    if not sorted_samples:
        return {}
    n = len(sorted_samples)
    return {"p50": sorted_samples[n // 2],
            "p99": sorted_samples[min(n - 1, int(n * 0.99))]}


class Histogram:
    """Sliding-window latency histogram (last ``window`` samples)."""

    def __init__(self, window: int = 1024):
        self.window = window
        self._samples = []
        self._idx = itertools.count()
        self.count = 0
        self.sum = 0.0
        self._lock = threading.Lock()

    def observe(self, value: float):
        with self._lock:
            self.count += 1
            self.sum += value
            if len(self._samples) < self.window:
                self._samples.append(value)
            else:
                self._samples[next(self._idx) % self.window] = value

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            samples = sorted(self._samples)
            count, total = self.count, self.sum
        if not samples:
            return {"count": 0, "sample_count": 0}
        return {
            "count": count,
            "sum": round(total, 6),
            "min": samples[0],
            **window_quantiles(samples),
            "max": samples[-1],
            # how many window samples back the quantiles above: the
            # window is bounded, so a scraper (and the fleet merge)
            # can judge quantile confidence instead of trusting a p99
            # computed from 3 samples
            "sample_count": len(samples),
        }

    def samples(self, cap: int = 128) -> list:
        """Up to ``cap`` evenly-strided window samples (sorted) — the
        raw material the fleet-level histogram merge pools so merged
        quantiles come from data, not from averaging per-host p99s."""
        with self._lock:
            samples = sorted(self._samples)
        if len(samples) <= cap:
            return [round(s, 6) for s in samples]
        stride = len(samples) / cap
        return [round(samples[int(i * stride)], 6) for i in range(cap)]


class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {name: 0 for name in _COUNTERS}
        self._seconds: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        # named histogram family; batch_seconds keeps its attribute
        # alias (it predates the family and call sites/tests use it)
        self.batch_seconds = Histogram()
        self._hists: Dict[str, Histogram] = {
            "batch_seconds": self.batch_seconds}
        self._reporter: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # reporter sink shared between the interval thread and
        # final_flush: both write through ONE handle under ONE lock, so
        # a drain-time flush can never interleave bytes mid-line with a
        # reporter tick (the two used to open the append path
        # independently)
        self._out_lock = threading.Lock()
        self._out = None
        self._path: Optional[str] = None
        # observe taps: name -> (fn, ...) called after the histogram
        # records a sample (obs/slo.py threshold accounting).  Replaced
        # wholesale under _lock, read without it on the observe path —
        # an observe racing a reconfigure sees either tuple, both valid
        self._observe_taps: Dict[str, tuple] = {}

    def inc(self, name: str, value: int = 1):
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def set_gauge(self, name: str, value: float):
        """Point-in-time values (e.g. device_breaker_state: 0 closed,
        1 open, 2 half-open) — reported alongside counters."""
        with self._lock:
            self._gauges[name] = value

    def init_gauge(self, name: str, value: float):
        """Make a gauge visible in reports without clobbering a live
        value (e.g. a second BatchHandler must not mask an open
        breaker's state with a fresh 0)."""
        with self._lock:
            self._gauges.setdefault(name, value)

    def get_gauge(self, name: str, default: float = 0):
        with self._lock:
            return self._gauges.get(name, default)

    def add_seconds(self, name: str, value: float):
        """Accumulate a per-stage wall-clock share (pipeline stage
        timings: device_fetch_seconds, encode_seconds, ...)."""
        with self._lock:
            self._seconds[name] = self._seconds.get(name, 0.0) + value

    def observe(self, name: str, value: float):
        """One sample into the named histogram family (created on
        first use): queue_wait_seconds, e2e_batch_seconds, ..."""
        h = self._hists.get(name)
        if h is None:
            with self._lock:
                h = self._hists.setdefault(name, Histogram())
        h.observe(value)
        taps = self._observe_taps.get(name)
        if taps:
            for tap in taps:
                tap(value)

    def add_observe_tap(self, name: str, fn) -> None:
        """Register ``fn(value)`` to run after every ``observe(name,
        ...)`` sample — the SLO engine's per-objective good/bad
        accounting.  Taps must be cheap and never raise."""
        with self._lock:
            self._observe_taps[name] = self._observe_taps.get(name, ()) \
                + (fn,)

    def clear_observe_taps(self) -> None:
        with self._lock:
            self._observe_taps = {}

    def histogram(self, name: str) -> Histogram:
        h = self._hists.get(name)
        if h is None:
            with self._lock:
                h = self._hists.setdefault(name, Histogram())
        return h

    def get(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def snapshot(self, include_hist_samples: bool = False
                 ) -> Dict[str, object]:
        """Flat JSON-safe snapshot.  ``include_hist_samples`` adds each
        histogram's bounded sample ring (the fleet /fleetz merge pools
        them for honest merged quantiles); the periodic JSONL reporter
        leaves it off so report lines stay one-screen."""
        with self._lock:
            counters = dict(self._counters)
            seconds = {k: round(v, 6) for k, v in self._seconds.items()}
            gauges = dict(self._gauges)
            hists = dict(self._hists)
        snap: Dict[str, object] = {"ts": round(time.time(), 3)}
        snap.update(counters)
        snap.update(seconds)
        snap.update(gauges)
        for name, h in hists.items():
            hsnap = h.snapshot()
            if include_hist_samples:
                hsnap["samples"] = h.samples()
            snap[name] = hsnap
        return snap

    def export(self) -> Dict[str, dict]:
        """Typed snapshot for renderers that need counter/gauge/
        histogram kinds kept apart (obs/prom.py — Prometheus TYPE
        lines)."""
        with self._lock:
            return {
                "counters": dict(self._counters),
                "seconds": dict(self._seconds),
                "gauges": dict(self._gauges),
                "histograms": {k: h.snapshot()
                               for k, h in self._hists.items()},
            }

    def reset(self):
        with self._lock:
            for k in self._counters:
                self._counters[k] = 0
            self._seconds.clear()
            self._gauges.clear()
            self.batch_seconds = Histogram()
            self._hists = {"batch_seconds": self.batch_seconds}
            self._observe_taps = {}

    # -- periodic reporter -------------------------------------------------
    def start_reporter(self, interval: float, path: Optional[str] = None):
        if interval <= 0 or self._reporter is not None:
            return
        self._path = path
        if path:
            try:
                self._out = open(path, "a")
            except OSError as e:
                print(f"metrics: cannot open {path} ({e}); reporting "
                      "to stderr", file=sys.stderr)
                self._path = None
                self._out = None

        def run():
            while not self._stop.wait(interval):
                self._write_snapshot()

        self._reporter = threading.Thread(target=run, daemon=True,
                                          name="metrics-reporter")
        self._reporter.start()

    def _write_snapshot(self) -> None:
        line = json.dumps(self.snapshot())
        with self._out_lock:
            out = self._out if self._out is not None else sys.stderr
            print(line, file=out, flush=True)

    def stop_reporter(self):
        self._stop.set()
        if self._reporter is not None:
            self._reporter.join(timeout=2)
            self._reporter = None
        self._stop = threading.Event()
        # release the sink and clear the stale path: a final_flush
        # after stop must not re-open a file the reporter no longer
        # owns (the old code left _path behind forever)
        with self._out_lock:
            if self._out is not None:
                self._out.close()
                self._out = None
            self._path = None

    def final_flush(self):
        """One last snapshot at shutdown — short-lived runs would
        otherwise exit between reporter ticks.  Writes through the
        reporter's own handle under its lock (never a second
        independent open of the same append path — the interleaved-
        bytes race the old implementation had)."""
        if self._reporter is None:
            return
        self._write_snapshot()


# process-wide registry; pipeline stages import and increment this
registry = Registry()


def configure_from(config) -> None:
    """Start the reporter (and optional XLA profiler trace) if [metrics]
    is configured (pipeline boot).  Also wires the observability layer:
    span tracing (obs/trace.py) and the degradation-event journal
    (obs/events.py) read their ``[metrics]`` keys here."""
    interval = config.lookup_int(
        "metrics.interval", "metrics.interval must be an integer", 0)
    path = config.lookup_str("metrics.path", "metrics.path must be a string")
    if interval and interval > 0:
        registry.start_reporter(float(interval), path)
    profile_dir = config.lookup_str(
        "metrics.jax_profile_dir", "metrics.jax_profile_dir must be a string")
    if profile_dir:
        global _profile_dir
        _profile_dir = profile_dir
        start_jax_profiler(profile_dir)
    from ..obs import events as _events
    from ..obs import slo as _slo
    from ..obs import trace as _trace

    _trace.configure_from(config)
    _events.configure_from(config)
    _slo.configure_from(config)


_profiling = False
# the directory on-demand profiling (SIGUSR2 / POST /profile) captures
# into: metrics.jax_profile_dir when configured, else a per-pid default
_profile_dir: Optional[str] = None


def _default_profile_dir() -> str:
    import os
    import tempfile

    return f"{tempfile.gettempdir()}/flowgger-xprof-{os.getpid()}"


def start_jax_profiler(log_dir: str) -> None:
    """Capture an XLA device trace of the batched decode path (viewable
    with tensorboard/xprof).  Stopped by stop_jax_profiler at drain."""
    global _profiling
    if _profiling:
        return
    try:
        import jax

        jax.profiler.start_trace(log_dir)
        _profiling = True
        print(f"jax profiler tracing to {log_dir}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 - profiling must never kill ingest
        print(f"jax profiler unavailable: {e}", file=sys.stderr)


def stop_jax_profiler() -> None:
    global _profiling
    if not _profiling:
        return
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception:  # noqa: BLE001  # flowcheck: disable=FC04 -- shutdown best-effort; profiling must never block drain
        pass
    _profiling = False


def toggle_jax_profiler() -> Tuple[bool, str]:
    """On-demand profiling flip (SIGUSR2 handler and the health
    server's ``POST /profile`` both land here): start a trace into the
    configured — or default per-pid — directory when idle, stop the
    running one otherwise.  Returns (now profiling?, log dir) so a
    soak-run operator can capture an xprof trace without a restart."""
    log_dir = _profile_dir or _default_profile_dir()
    if _profiling:
        stop_jax_profiler()
        print(f"jax profiler stopped (trace in {log_dir})",
              file=sys.stderr)
    else:
        start_jax_profiler(log_dir)
    return _profiling, log_dir
