"""BatchHandler: the TPU-path replacement for ScalarHandler.

Accumulates framed lines into a batch arena, ships the arena to the
device (pack + columnar decode in one jitted call), materializes Records,
encodes, and enqueues — preserving input order and the reference's
per-line error behavior (stderr + drop, line_splitter.rs:37-54).

Latency bound (SURVEY.md §7 hard-parts): the batch flushes when it
reaches ``input.tpu_batch_size`` lines (default 16384), when
``input.tpu_flush_ms`` (default 50) elapses with data pending, or at end
of stream — at most one batch-fill window of added latency vs the
scalar path.
"""

from __future__ import annotations

import sys
import threading
from typing import List, Optional

import numpy as np

from ..config import Config
from ..encoders import EncodeError
from ..splitters import Handler, ScalarHandler
from ..record import Record
from .. import tenancy as _tenancy
from ..obs import events as _events
from ..obs.trace import tracer as _tracer
from ..utils import faultinject as _faults
from ..utils.metrics import registry as _metrics

DEFAULT_BATCH_SIZE = 16384
DEFAULT_FLUSH_MS = 50
DEFAULT_MAX_LINE_LEN = 512


_NO_MERGER = object()  # sentinel: block mode only when the caller wires a merger


class BatchHandler(Handler):
    def __init__(self, tx, decoder, encoder, config: Optional[Config] = None,
                 fmt: str = "rfc5424", start_timer: bool = True,
                 merger=_NO_MERGER, supervisor=None):
        self.tx = tx
        self.encoder = encoder
        self.fmt = fmt
        # Block mode (one pre-framed EncodedBlock per batch) engages only
        # when the pipeline hands us its merger, so standalone handlers
        # keep the per-message queue contract.
        self._block_mode = merger is not _NO_MERGER
        self._merger = None if merger is _NO_MERGER else merger
        # scalar path for fallback rows and capnp handle_record
        self.scalar = ScalarHandler(tx, decoder, encoder)
        cfg = config or Config.from_string("")
        self._cfg = cfg
        # WAL spill tier (durability/manager.py): set by the pipeline
        # when [durability] is armed.  _guarded_dispatch diverts fresh
        # packed batches to disk instead of blocking on a full queue;
        # replay_spilled() re-enters them with sink-ack cursors.
        self.durability = None
        # device-decode circuit breaker: trips the whole handler onto the
        # scalar-oracle path on sustained device failure (None = disabled
        # via input.tpu_breaker = false, legacy fail-fast behavior)
        from .breaker import DecodeBreaker

        self._breaker = DecodeBreaker.from_config(cfg)
        self._auto_scalars: dict = {}  # per-class oracles for auto fallback
        self.batch_size = cfg.lookup_int(
            "input.tpu_batch_size", "input.tpu_batch_size must be an integer",
            DEFAULT_BATCH_SIZE)
        self.flush_ms = cfg.lookup_int(
            "input.tpu_flush_ms", "input.tpu_flush_ms must be an integer",
            DEFAULT_FLUSH_MS)
        self.max_len = cfg.lookup_int(
            "input.tpu_max_line_len", "input.tpu_max_line_len must be an integer",
            DEFAULT_MAX_LINE_LEN)
        pack_threads = cfg.lookup_int(
            "input.pack_threads",
            "input.pack_threads must be an integer (threads)", None)
        if pack_threads is not None:
            if pack_threads < 1:
                from ..config import ConfigError

                raise ConfigError("input.pack_threads must be >= 1")
            # only an explicit key touches the (module-wide) pack
            # setting, so a later default-configured handler can never
            # silently reset another handler's thread slicing
            from . import pack as _pack_mod

            _pack_mod.configure_pack_threads(pack_threads)
        self._lines: List[bytes] = []
        self._chunks: List[bytes] = []      # complete-line regions (fast path)
        self._chunk_lines = 0
        self._span_chunks: List[bytes] = []  # syslen regions + frame spans
        self._span_sets: List = []
        self._span_count = 0
        # online template mining (tenancy/templates.py): None unless
        # tenant.templates = "on" — the off path tracks nothing and the
        # only residue is `is None` checks
        from ..tenancy.templates import TemplateMinerSet

        self._miners = TemplateMinerSet.from_config(cfg)
        # per-ingest (tenant, line-count) runs, parallel to the pending
        # chunk/span/line arenas, so rows attribute to the tenant whose
        # connection delivered them (ingestion order is pack order) for
        # mining AND for the fair queue's lane choice on Record-route
        # emits; tracked while mining or while the ingest thread
        # carries a tenant tag (tenancy enabled)
        self._chunk_runs: List = []
        self._span_runs: List = []
        self._line_runs: List = []
        # template-ID enrichment rides the Record route (per-row JSON
        # fields don't fit the constant-segment block encoders), GELF
        # output only
        self._enrich_hook = None
        if self._miners is not None and self._miners.enrich:
            from ..encoders.gelf import GelfEncoder as _Gelf
            from ..tenancy.templates import make_gelf_enricher

            if type(encoder) is _Gelf:
                self._enrich_hook = make_gelf_enricher(self._miners)
                self.scalar.record_hook = self._enrich_hook
        # block routes with mined span channels pin the host encode path
        # (the miner consumes the fetched decode columns)
        self._mine_block = (self._miners is not None
                            and fmt in ("rfc5424", "rfc3164", "ltsv",
                                        "jsonl", "dns"))
        self._lock = threading.Lock()
        # serializes batch decodes so a timer flush racing a size flush
        # cannot reorder output
        self._decode_lock = threading.Lock()
        self._flush_t0 = 0.0
        self._timer: Optional[threading.Timer] = None
        self._start_timer = start_timer
        # per-handler hysteresis for the device-encode route (declines /
        # cooldown counters owned here, updated by device_gelf)
        self._device_route_state: dict = {}
        # multi-chip mesh: rows shard over dp, bytes over sp (SURVEY
        # §2.8 mapping).  "auto" engages whenever more than one real
        # device is visible; "on" also engages on the virtual CPU mesh
        # (tests); "off" disables.  Lane dispatch (below) supersedes the
        # mesh when it resolves to >1 lane — each chip then decodes its
        # own batches instead of a shard of every batch.
        self._mesh = None
        self._mesh_checked = False
        self._sharded: dict = {}
        self._mesh_mode = cfg.lookup_str(
            "input.tpu_mesh", "input.tpu_mesh must be a string", "auto")
        if self._mesh_mode not in ("auto", "on", "off"):
            from ..config import ConfigError

            raise ConfigError("input.tpu_mesh must be auto, on or off")
        self._mesh_sp = cfg.lookup_int(
            "input.tpu_sp", "input.tpu_sp must be an integer", 1)
        if self._mesh_sp < 1:
            from ..config import ConfigError

            raise ConfigError("input.tpu_sp must be >= 1")
        # fused decode→encode routes (tpu/fused_routes.py): "auto"
        # (default) runs the single-program fused tier whenever the
        # (in-format, out-format) route has a registered fused program,
        # declining to the split decode/encode path under the compile
        # watchdog; "off" pins the split path; "on" is "auto" plus a
        # startup notice when this config can never fuse
        self._fuse_mode = cfg.lookup_str(
            "input.tpu_fuse", "input.tpu_fuse must be a string", "auto")
        if self._fuse_mode not in ("auto", "on", "off"):
            from ..config import ConfigError

            raise ConfigError("input.tpu_fuse must be auto, on or off")
        # shape bucketing: pack row counts quantize to a small geometric
        # grid so steady-state traffic compiles a handful of shapes
        # (padding rows are masked — emitted bytes never change).  Like
        # pack_threads, only an explicit key touches the module-wide
        # grid so a default handler can't reset another's buckets.
        from . import pack as _pack_mod

        shape_buckets = cfg.lookup_int(
            "input.tpu_shape_buckets",
            "input.tpu_shape_buckets must be an integer (bucket count)",
            None)
        if shape_buckets is not None:
            if shape_buckets < 1:
                from ..config import ConfigError

                raise ConfigError("input.tpu_shape_buckets must be >= 1")
            _pack_mod.configure_shape_buckets(
                _pack_mod.shape_bucket_grid(shape_buckets, self.batch_size))
        # overlap executor: the block route submits batches into a set
        # of per-device lanes (tpu/overlap.py LaneSet) — default one
        # lane (the PR 4 in-flight window); with multiple real devices
        # (or an explicit input.tpu_lanes) batches round-robin across
        # lanes, each with its own fetcher thread, submit-ahead depth,
        # and route economics, while the LaneSet's FIFO sequencer keeps
        # blocks reaching the merger in strict batch order.  Every
        # synchronous-emit path fences ALL lanes first.
        from .overlap import (LaneSet, RouteEconomics,
                              inflight_depth_from_config, resolve_lanes)

        lanes, lane_devs = resolve_lanes(cfg, self._mesh_mode)
        if lanes > 1:
            # lanes own the devices; the sharded mesh would re-shard
            # each lane's batch across every chip and serialize them
            self._mesh_mode = "off"
        self._lane_devices = lane_devs
        self._econs = [
            RouteEconomics.from_config(
                cfg, label=f"lane{i}" if lanes > 1 else None)
            for i in range(lanes)
        ]
        self._window = LaneSet(
            inflight_depth_from_config(cfg), self._pop_emit, lanes=lanes,
            name=f"tpu-{fmt}", supervisor=supervisor)
        # zero-JIT boot (input.tpu_aot_dir): install — or, when the
        # pipeline already loaded it, revalidate against this handler's
        # max_len + bucket grid — the AOT artifact store before any
        # kernel dispatch.  Loaded programs replace trace+compile at
        # every call site below; the JIT + watchdog + persistent-cache
        # ladder stays the fallback for any miss/reject.
        from . import pack as _pack_aot
        from .aot import setup_aot

        setup_aot(cfg, max_len=self.max_len,
                  grid=_pack_aot.active_bucket_grid())
        # persistent compile cache: wire before any kernel dispatch so
        # every compile below lands in it
        from .device_common import (cache_placed_outside,
                                    enable_compile_cache)

        cache_key = cfg.lookup_str(
            "input.tpu_compile_cache_dir",
            "input.tpu_compile_cache_dir must be a string (directory)",
            None)
        enable_compile_cache(cache_key)
        # someone named the cache's place (the key, or the environment):
        # the production signal the prewarm default keys on
        self._cache_placed = cache_placed_outside() or bool(cache_key)
        self._prewarm_cfg = cfg.lookup_bool(
            "input.tpu_prewarm", "input.tpu_prewarm must be a boolean",
            None)
        self._supervisor = supervisor
        # direct span->bytes encodes for rfc5424 routes
        from ..encoders.capnp import CapnpEncoder
        from ..encoders.gelf import GelfEncoder
        from ..encoders.ltsv import LTSVEncoder
        from ..encoders.passthrough import PassthroughEncoder
        from ..encoders.rfc3164 import RFC3164Encoder
        from ..encoders.rfc5424 import RFC5424Encoder

        passthrough_ok = (type(encoder) is PassthroughEncoder
                          and encoder.header_time_format is None)
        self._passthrough_ok = passthrough_ok
        self._fast_encode = (
            (fmt == "rfc5424"
             and (type(encoder) in (GelfEncoder, RFC5424Encoder,
                                    LTSVEncoder, CapnpEncoder)
                  or passthrough_ok))
            or (fmt in ("rfc3164", "ltsv", "gelf", "auto")
                and type(encoder) in (GelfEncoder, CapnpEncoder,
                                      LTSVEncoder, RFC5424Encoder))
            or (fmt in ("jsonl", "dns")
                and type(encoder) in (GelfEncoder, LTSVEncoder))
            or (fmt == "rfc3164"
                and (passthrough_ok
                     or type(encoder) is RFC3164Encoder)))
        # opt-in extra auto legs (input.auto_extra_formats): jsonl/dns
        # classes for the mixed-format dispatch; empty = classic table
        from .autodetect import auto_extra_formats

        self._auto_extras = (auto_extra_formats(cfg) if fmt == "auto"
                             else ())
        # single source of truth for kernel dispatch: fmt -> batch decoder
        auto_ltsv = self._auto_ltsv_decoder(cfg) if fmt == "auto" else None
        self._auto_ltsv = auto_ltsv
        self._kernel_fn = {
            "rfc5424": lambda lines: _decode_rfc5424_batch(lines, self.max_len),
            "ltsv": lambda lines: _decode_ltsv_batch(
                lines, self.max_len, self.scalar.decoder),
            "gelf": lambda lines: _decode_gelf_batch(lines, self.max_len),
            "rfc3164": lambda lines: _decode_rfc3164_batch(lines, self.max_len),
            "jsonl": lambda lines: _decode_jsonl_batch(lines, self.max_len),
            "dns": lambda lines: _decode_dns_batch(lines, self.max_len),
            "auto": lambda lines: _decode_auto_batch(
                lines, self.max_len, auto_ltsv, self._auto_extras),
        }.get(fmt)
        # the block route is config-static: if it can never engage, say
        # so once at startup — a *_tpu format that silently drops to the
        # per-record path is a ~30x throughput cliff the user should
        # see, not discover (VERDICT r3 weak #7)
        if self._block_mode:
            reason = self._route_cliff_reason()
            if reason:
                print(
                    f"flowgger-tpu: columnar block route disabled for "
                    f"format '{fmt}' ({reason}); throughput falls to the "
                    f"per-record path (~30x slower)", file=sys.stderr)
            elif self._fuse_mode == "on" and self._fused_route() is None:
                # the REAL runtime gate (_fused_route), not just
                # route_for: template mining and a mesh-owned format
                # also pin the split path, and "on" promises a notice
                # whenever this config can never fuse
                print(
                    'flowgger-tpu: input.tpu_fuse = "on" but this '
                    f"config cannot fuse format '{fmt}' (no registered "
                    "fused program for the route, template mining on, "
                    "or a sharded mesh owns the format); using the "
                    "split decode/encode path", file=sys.stderr)
        # device-resident framing (tpu/framing.py): "auto" lifts the
        # record-boundary scan + arena pack onto the accelerator
        # whenever the columnar block route is engaged on a non-CPU
        # backend (the mesh/lanes "auto" precedent; "on" also engages
        # on the CPU backend — tests/benches; "off" pins the host
        # splitters).  Raw transport chunks then reach this handler
        # through per-connection _RawSession objects instead of
        # pre-framed regions, and the splitter does zero scanning.
        from .framing import FramingEconomics

        self._framing_mode = cfg.lookup_str(
            "input.tpu_framing", "input.tpu_framing must be a string",
            "auto")
        if self._framing_mode not in ("auto", "on", "off"):
            from ..config import ConfigError

            raise ConfigError("input.tpu_framing must be auto, on or off")
        self._framing_econ = FramingEconomics.from_config(cfg)
        self._raw_sessions: List = []
        self._raw_est = 0
        framing_engaged = False
        if (self._framing_mode != "off" and self._block_mode
                and self.fmt != "auto" and self._kernel_fn is not None
                and self._block_route_ok()):
            if self._framing_mode == "on":
                framing_engaged = True
            else:
                import jax

                framing_engaged = jax.default_backend() != "cpu"
        if framing_engaged and self._sharded_for(self.fmt) is not None:
            # the sharded mesh owns this format's batches (it re-shards
            # host arrays across every chip); framing's lane-committed
            # device arrays would fight that placement
            framing_engaged = False
        self._framing_engaged = framing_engaged
        if (self._framing_mode == "on" and not framing_engaged
                and self._block_mode):
            print(
                'flowgger-tpu: input.tpu_framing = "on" but this config '
                f"cannot device-frame format '{fmt}' (the columnar "
                "block route is disabled, auto format, or a sharded "
                "mesh owns the format); using the host splitters",
                file=sys.stderr)
        # background kernel prewarm: compile the configured format's
        # decode (+ engaged device-encode) kernels for the shape-bucket
        # grid now, so the first real batch of each steady-state shape
        # never eats a cold compile or a watchdog decline.  Default: on
        # exactly when the persistent compile cache was given a place
        # (the production signal); input.tpu_prewarm forces either way.
        # auto format skips (its per-class legs compile lazily per mix).
        prewarm = self._prewarm_cfg
        if prewarm is None:
            prewarm = self._cache_placed
        if (prewarm and self._block_mode and fmt != "auto"
                and self._kernel_fn is not None and self._block_route_ok()):
            from . import pack as _pack_mod
            from .device_common import prewarm_kernels

            grid = (_pack_mod.active_bucket_grid()
                    or (_pack_mod.bucket_rows(self.batch_size),))
            prewarm_kernels(
                fmt, self.max_len, grid, encoder=self.encoder,
                merger=self._merger,
                ltsv_decoder=(self.scalar.decoder if fmt == "ltsv"
                              else None),
                supervisor=supervisor,
                devices=[d for d in self._lane_devices if d is not None]
                or None,
                # warm the fused program only when dispatch can
                # actually use it — _fused_route() is the same gate
                # _emit_fast consults (fuse mode, template mining,
                # sharded mesh), so prewarm never background-compiles
                # a program that is never dispatched
                fused_route=self._fused_route())

    @property
    def _econ(self):
        """Lane-0 route economics (single-lane compatibility alias;
        multi-lane callers read ``_econs``)."""
        return self._econs[0]

    # -- Handler interface -------------------------------------------------
    def ingest_chunk(self, region: bytes) -> None:
        """Fast path fed by Line/NulSplitter: a region of *complete*
        separator-terminated messages straight off the wire — no
        per-message Python objects; native code does the framing at
        flush (the separator rides ``ingest_sep``, set by the splitter).
        """
        tag = _tenancy.current_name()
        with self._lock:
            self._chunks.append(region)
            n = region.count(self.ingest_sep)
            self._chunk_lines += n
            if self._miners is not None or tag is not None:
                self._chunk_runs.append(
                    (tag or _tenancy.DEFAULT_TENANT, n))
            full = self._pending_locked() >= self.batch_size
            if not full and self._timer is None and self._start_timer:
                self._timer = threading.Timer(self.flush_ms / 1000.0, self.flush)
                self._timer.daemon = True
                self._timer.start()
        if full:
            self.flush(drain=False)

    def ingest_spans(self, chunk: bytes, starts, lens) -> None:
        """Fast path fed by SyslenSplitter: a region plus pre-scanned
        frame offset/length arrays — zero per-message Python for the
        reference's ``framed=true`` mode."""
        tag = _tenancy.current_name()
        with self._lock:
            self._span_chunks.append(chunk)
            self._span_sets.append((starts, lens))
            self._span_count += len(starts)
            if self._miners is not None or tag is not None:
                self._span_runs.append(
                    (tag or _tenancy.DEFAULT_TENANT, len(starts)))
            full = self._pending_locked() >= self.batch_size
            if not full and self._timer is None and self._start_timer:
                self._timer = threading.Timer(self.flush_ms / 1000.0, self.flush)
                self._timer.daemon = True
                self._timer.start()
        if full:
            self.flush(drain=False)

    def wants_raw(self, framing: str) -> bool:
        """Device framing engaged for this framing: the splitter hands
        raw chunks via ``open_raw`` and does zero scanning."""
        return (self._framing_engaged
                and framing in ("line", "nul", "syslen"))

    def open_raw(self, framing: str):
        """One per-connection raw-framing session (the RegionBuffer):
        accumulates raw transport chunks and the carry-over tail for
        records split across chunk boundaries; framed at flush."""
        sess = _RawSession(self, framing)
        with self._lock:
            self._raw_sessions.append(sess)
        return sess

    def _pending_locked(self) -> int:
        return (self._chunk_lines + self._span_count + len(self._lines)
                + self._raw_est)

    def handle_bytes(self, raw: bytes) -> None:
        tag = _tenancy.current_name()
        with self._lock:
            self._lines.append(raw)
            if self._miners is not None or tag is not None:
                tenant = tag or _tenancy.DEFAULT_TENANT
                if self._line_runs and self._line_runs[-1][0] == tenant:
                    self._line_runs[-1][1] += 1
                else:
                    self._line_runs.append([tenant, 1])
            full = self._pending_locked() >= self.batch_size
            if not full and self._timer is None and self._start_timer:
                self._timer = threading.Timer(self.flush_ms / 1000.0, self.flush)
                self._timer.daemon = True
                self._timer.start()
        if full:
            self.flush(drain=False)

    def handle_record(self, record: Record) -> None:
        self._window.fence()  # keep queue order vs in-flight batches
        self.scalar.handle_record(record)

    def flush(self, drain: bool = True) -> None:
        """Decode pending input.  Block-route batches are *submitted*
        into the in-flight window (the fetcher thread fetches and emits
        them behind us, in order); ``drain=True`` (timer and
        end-of-stream flushes) additionally fences the window so every
        submitted batch has reached the queue before returning."""
        with self._lock:
            lines, self._lines = self._lines, []
            chunks, self._chunks = self._chunks, []
            self._chunk_lines = 0
            spans = (self._span_chunks, self._span_sets)
            self._span_chunks, self._span_sets = [], []
            self._span_count = 0
            chunk_runs, self._chunk_runs = self._chunk_runs, []
            span_runs, self._span_runs = self._span_runs, []
            line_runs, self._line_runs = self._line_runs, []
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
        with self._decode_lock:
            import time as _time

            t0 = _time.perf_counter()
            # the e2e_batch_seconds anchor every batch dispatched from
            # this flush measures against (decode lock serializes
            # flushes, so an instance attribute is race-free)
            self._flush_t0 = t0
            n0 = _metrics.get("input_lines")
            if self._raw_sessions:
                # raw-framing sessions snapshot *inside* the decode
                # lock: region assembly chains each session's carry
                # across flushes, so snapshot order must equal
                # processing order no matter which thread flushes
                with self._lock:
                    raw = [(s, s.chunks) for s in self._raw_sessions
                           if s.chunks]
                    for s, _ch in raw:
                        s.chunks = []
                        s.nbytes = 0
                        self._raw_est -= s.est
                        s.est = 0
                for s, ch in raw:
                    self._decode_raw(s, ch)
                with self._lock:
                    carry_total = sum(len(s.carry)
                                      for s in self._raw_sessions)
                _metrics.set_gauge("framing_carry_bytes", carry_total)
            if chunks:
                self._decode_chunks(chunks, chunk_runs or None)
            if spans[0]:
                self._decode_spans(*spans, runs=span_runs or None)
            if lines:
                self._decode_batch(lines, line_runs or None)
            _metrics.add_seconds("dispatch_seconds",
                                 _time.perf_counter() - t0)
            if drain:
                self._window.fence()
            _metrics.inc("batches")
            _metrics.inc("batch_lines", _metrics.get("input_lines") - n0)
            _metrics.batch_seconds.observe(_time.perf_counter() - t0)

    def close(self) -> None:
        """Fence and stop the in-flight window's fetcher thread; the
        handler stays usable (a later submit respawns it).  Called at
        pipeline drain so long-lived processes don't accumulate idle
        fetcher threads across handler generations."""
        self._window.close()

    # -- WAL replay (durability/manager.py) --------------------------------
    def replay_spilled(self, limit: Optional[int] = None) -> int:
        """Re-enter spilled WAL records through the normal dispatch
        path: each record re-packs from its raw chunk + span vectors
        (byte-identical to the original pack) and rides an ack that
        advances the persisted replay cursor only once the sink flushed
        the bytes.  ``limit`` caps replayed records (None = drain the
        whole backlog).  Returns the number of lines replayed."""
        mgr = self.durability
        if mgr is None or not mgr.backlog():
            return 0
        from . import pack

        total_lines = 0
        replayed = 0
        while limit is None or replayed < limit:
            want = mgr.replay_batch if limit is None \
                else min(mgr.replay_batch, limit - replayed)
            recs = mgr.next_records(want)
            if not recs:
                break
            for rec in recs:
                if rec.fmt != self.fmt:
                    # config changed across the restart: the record
                    # still replays (bytes are bytes), but decode runs
                    # under this handler's format
                    print(f"durability: replaying a '{rec.fmt}' record "
                          f"through the '{self.fmt}' handler",
                          file=sys.stderr)
                with self._decode_lock:
                    packed = pack.pack_spans_2d(
                        [rec.body], [(rec.starts, rec.lens)],
                        self.max_len)
                    self._guarded_dispatch(
                        packed, runs=rec.runs,
                        ack=mgr.make_ack(rec.seq, rec.idx))
                _metrics.inc("replayed_lines", rec.n)
                total_lines += rec.n
                replayed += 1
            _events.emit(
                "durability", "spill_replay", route=self.fmt,
                cost=len(recs), cost_unit="records",
                msg=f"replayed {len(recs)} spilled record(s) "
                    f"({mgr.backlog()} pending)")
        # every replayed batch reaches the queue before we return, so
        # callers (boot replay, drain) can sequence against the sink
        self._window.fence()
        return total_lines

    # -- multi-chip mesh ---------------------------------------------------
    def _sharded_for(self, fmt: str):
        """Lazily build (and cache) the ShardedDecode for one format;
        None when the mesh doesn't engage (single device, cpu backend in
        "auto" mode, or tpu_mesh="off")."""
        if self._mesh_mode == "off":
            return None
        if fmt in ("jsonl", "dns"):
            # no mesh kernels for the new formats yet: lane dispatch is
            # their multi-chip story (each lane decodes its own batches)
            return None
        if fmt in self._sharded:
            return self._sharded[fmt]
        sharded = None
        try:
            import jax

            if not self._mesh_checked:
                self._mesh_checked = True
                if self.max_len % self._mesh_sp:
                    raise ValueError(
                        f"tpu_max_line_len {self.max_len} not divisible "
                        f"by tpu_sp={self._mesh_sp}")
                # Multi-host: decode is embarrassingly parallel over
                # records, so each host shards only its OWN ingest
                # stream across its OWN chips (dp within host).  A
                # global-device mesh would device_put host-local batches
                # with a global sharding — rows outside this host's
                # addressable shard would be dropped and fetches would
                # crash on non-addressable arrays.
                devs = (jax.local_devices() if jax.process_count() > 1
                        else jax.devices())
                engage = len(devs) > 1 and (
                    self._mesh_mode == "on"
                    or jax.default_backend() != "cpu")
                if engage:
                    from ..parallel.mesh import make_decode_mesh

                    self._mesh = make_decode_mesh(devs, sp=self._mesh_sp)
            if self._mesh is not None:
                from ..parallel.mesh import ShardedDecode
                from .rfc5424 import best_extract_impl

                kw = ({"extract_impl": best_extract_impl()}
                      if fmt == "rfc5424" else {})
                sharded = ShardedDecode(self._mesh, fmt, **kw)
                _metrics.inc("sharded_kernels")
        except ValueError as e:
            # e.g. device count not divisible by tpu_sp: surface once,
            # run single-device rather than dying mid-stream
            print(f"tpu_mesh disabled: {e}", file=sys.stderr)
            self._mesh_mode = "off"
            return None
        self._sharded[fmt] = sharded
        return sharded

    # -- batched decode ----------------------------------------------------
    @staticmethod
    def _auto_ltsv_decoder(config):
        from ..decoders.ltsv import LTSVDecoder

        return LTSVDecoder(config)

    def _decode_chunks(self, chunks: List[bytes], runs=None) -> None:
        from . import pack

        region = b"".join(chunks)
        sep = self.ingest_sep
        if self._kernel_fn is None or not self._device_allowed():
            # no columnar kernel, or the breaker is open: split once in
            # C speed and run the scalar oracle per line (after fencing
            # the window so older device batches keep their place)
            self._window.fence()
            self._scalar_region(region, sep)
            return
        import time as _time

        bid = _tracer.begin(self.fmt)
        tp0 = _time.perf_counter()
        _tracer.enter("pack")
        packed = pack.pack_region_2d(
            region, self.max_len, sep=sep[0],
            strip_cr=self.ingest_strip_cr)
        if bid is not None:
            _tracer.span(bid, "pack", tp0, _time.perf_counter(),
                         rows=int(packed[5]), nbytes=len(region))
        self._guarded_dispatch(packed, runs, trace=bid)

    def _decode_spans(self, span_chunks, span_sets, runs=None) -> None:
        from . import pack

        if self._kernel_fn is None or not self._device_allowed():
            self._window.fence()
            for chunk, (starts, lens) in zip(span_chunks, span_sets):
                for s, ln in zip(starts.tolist(), lens.tolist()):
                    self._scalar_handle(chunk[s:s + ln])
            return
        import time as _time

        bid = _tracer.begin(self.fmt)
        tp0 = _time.perf_counter()
        _tracer.enter("pack")
        packed = pack.pack_spans_2d(span_chunks, span_sets, self.max_len)
        if bid is not None:
            _tracer.span(bid, "pack", tp0, _time.perf_counter(),
                         rows=int(packed[5]))
        self._guarded_dispatch(packed, runs, trace=bid)

    # -- device-resident framing (raw sessions) ----------------------------
    def _decode_raw(self, sess, chunks) -> None:
        """Frame one session's pending raw bytes: device framing when
        the tier is engaged/healthy/economical, else the host splitter
        logic applied at flush — same records, same order, either way.
        The carry-over tail (a record split across chunk or flush
        boundaries) stays in the session."""
        region = sess.carry + b"".join(chunks) if sess.carry \
            else b"".join(chunks)
        sess.carry = b""
        if not region or sess.dead:
            return
        runs_tag = None
        if self._miners is not None or sess.tag is not None:
            runs_tag = sess.tag or _tenancy.DEFAULT_TENANT
        from . import framing as _framing

        state = _framing.cooldown_state(self._device_route_state,
                                        sess.framing)
        breaker_open = not self._device_allowed()
        use_device = (not breaker_open
                      and not _framing.in_cooldown(state)
                      and self._framing_econ.allow_framing())
        if sess.framing == "syslen":
            self._decode_raw_syslen(sess, region, state, use_device,
                                    breaker_open, runs_tag)
        else:
            self._decode_raw_sep(sess, region, state, use_device,
                                 breaker_open, runs_tag)

    def _decode_raw_sep(self, sess, region, state, use_device,
                        breaker_open, runs_tag) -> None:
        import time as _time

        from . import framing as _framing

        sep = sess.sep
        cut = region.rfind(sep)
        if cut < 0:
            sess.carry = region
            return
        framed, sess.carry = region[:cut + 1], region[cut + 1:]
        n = framed.count(sep)
        runs = [(runs_tag, n)] if runs_tag is not None else None
        charge = getattr(sess, "charge", None)
        if charge is not None and n:
            # record-aligned admission for raw (device-framed) sessions:
            # charge the tenant exactly what the host splitter would
            # have — one all-or-nothing admit per framed region, counted
            # in records and bytes.  A denial sheds the framed region
            # (the carry tail stays; its bytes are charged when framed)
            if not charge.admit_region(n, len(framed)):
                return
        if breaker_open:
            # breaker-open scalar oracle, same bytes (fence first so
            # older device batches keep their place)
            self._window.fence()
            self._scalar_raw_lines(framed, sep, sess.framing == "line")
            return
        if use_device:
            lane = self._window.next_lane()
            bid = _tracer.begin(self.fmt)
            t0 = _time.perf_counter()
            _tracer.enter("frame")
            try:
                _faults.maybe_raise("device_decode")
                packed, _consumed, _err = _framing.device_frame_region(
                    framed, sess.framing, self.max_len, n_records=n,
                    device=self._lane_devices[lane])
            except _framing.FramingDeclined:
                _framing.note_decline(state)
                _tracer.end(bid)
            except Exception as e:  # noqa: BLE001 - device degradation boundary
                _tracer.end(bid)
                if self._breaker is None:
                    raise
                self._device_failed(e)
            else:
                _framing.note_success(state)
                t1 = _time.perf_counter()
                if bid is not None:
                    _tracer.span(bid, "frame", t0, t1, rows=n,
                                 nbytes=len(framed), note="device")
                self._framing_econ.observe("framing", n, t1 - t0)
                self._guarded_dispatch(packed, runs, lane=lane,
                                       trace=bid)
                return
        from . import pack

        bid = _tracer.begin(self.fmt)
        t0 = _time.perf_counter()
        _tracer.enter("pack")
        packed = pack.pack_region_2d(framed, self.max_len, sep=sep[0],
                                     strip_cr=sess.framing == "line")
        t1 = _time.perf_counter()
        if bid is not None:
            _tracer.span(bid, "pack", t0, t1, rows=n,
                         nbytes=len(framed), note="host-frame")
        self._framing_econ.observe("hostpack", n, t1 - t0)
        self._guarded_dispatch(packed, runs, trace=bid)

    def _decode_raw_syslen(self, sess, region, state, use_device,
                           breaker_open, runs_tag) -> None:
        import time as _time

        from ..splitters import _scan_syslen_region
        from . import framing as _framing

        if use_device and not breaker_open:
            lane = self._window.next_lane()
            bid = _tracer.begin(self.fmt)
            t0 = _time.perf_counter()
            _tracer.enter("frame")
            try:
                _faults.maybe_raise("device_decode")
                packed, consumed, err = _framing.device_frame_region(
                    region, "syslen", self.max_len,
                    n_records=max(region.count(b" "), 1),
                    device=self._lane_devices[lane])
            except _framing.FramingDeclined:
                _framing.note_decline(state)
                _tracer.end(bid)
            except Exception as e:  # noqa: BLE001 - device degradation boundary
                _tracer.end(bid)
                if self._breaker is None:
                    raise
                self._device_failed(e)
            else:
                _framing.note_success(state)
                n = packed[5]
                charge = getattr(sess, "charge", None)
                if (n and charge is not None
                        and not charge.admit_region(
                            int(n), int(packed[4][:n].sum()))):
                    # record-aligned shed: the framed records drop as a
                    # unit (host-splitter admission parity); the carry
                    # tail stays with the session
                    _tracer.end(bid)
                    self._finish_raw_syslen(sess, region, consumed, err)
                    return
                if n:
                    t1 = _time.perf_counter()
                    if bid is not None:
                        _tracer.span(bid, "frame", t0, t1, rows=int(n),
                                     nbytes=len(region), note="device")
                    self._framing_econ.observe("framing", n, t1 - t0)
                    runs = ([(runs_tag, n)] if runs_tag is not None
                            else None)
                    self._guarded_dispatch(packed, runs, lane=lane,
                                           trace=bid)
                else:
                    _tracer.end(bid)
                self._finish_raw_syslen(sess, region, consumed, err)
                return
        t0 = _time.perf_counter()
        starts, lens, n, consumed, err = _scan_syslen_region(region)
        charge = getattr(sess, "charge", None)
        if charge is not None and n and not charge.admit_region(
                int(n), int(lens.sum())):
            # same record-aligned shed on the host-framed tier
            self._finish_raw_syslen(sess, region, consumed, err)
            return
        if breaker_open:
            self._window.fence()
            for s, ln in zip(starts.tolist(), lens.tolist()):
                self._scalar_handle(region[s:s + ln])
            self._finish_raw_syslen(sess, region, consumed, err)
            return
        if n:
            from . import pack

            bid = _tracer.begin(self.fmt)
            _tracer.enter("pack")
            packed = pack.pack_spans_2d([region[:consumed]],
                                        [(starts, lens)], self.max_len)
            t1 = _time.perf_counter()
            if bid is not None:
                _tracer.span(bid, "pack", t0, t1, rows=int(n),
                             nbytes=consumed, note="host-frame")
            self._framing_econ.observe("hostpack", n, t1 - t0)
            runs = [(runs_tag, n)] if runs_tag is not None else None
            self._guarded_dispatch(packed, runs, trace=bid)
        self._finish_raw_syslen(sess, region, consumed, err)

    def _finish_raw_syslen(self, sess, region, consumed, err) -> None:
        sess.carry = region[consumed:]
        if err:
            # host-scan parity: a malformed length prefix ends the
            # connection (the session goes dead; the splitter's next
            # push sees it and closes the stream like the host path)
            print("Can't read message's length", file=sys.stderr)
            sess.dead = True
            sess.carry = b""

    def _scalar_raw_lines(self, framed: bytes, sep: bytes,
                          strip_cr: bool) -> None:
        lines = framed.split(sep)
        lines.pop()  # framed regions end with the separator
        for raw in lines:
            if strip_cr and raw.endswith(b"\r"):
                raw = raw[:-1]
            self._scalar_handle(raw)

    def _dispatch_packed(self, packed, deferred=None, runs=None,
                         lane=None, trace=None, ack=None) -> None:
        """Route one packed tuple through the right decode/encode tier.
        ``deferred`` (single-element list) is set True when the batch
        was submitted to the in-flight window instead of emitted
        synchronously.  ``trace`` is the flight-recorder batch ID
        (None when tracing is off).  ``ack`` is a durability replay
        acknowledgment (see _guarded_dispatch)."""
        _count_rows(packed)
        if self._fast_encode:
            self._emit_fast(packed, deferred, runs, lane, trace, ack)
            return
        if self.fmt == "auto":
            from .autodetect import decode_auto_packed

            self._window.fence()
            self._emit(decode_auto_packed(packed, self.max_len,
                                          self._auto_ltsv,
                                          self._auto_extras), runs)
            if ack is not None:
                ack()
            return
        self._window.fence()
        self._emit(_decode_packed(self.fmt, packed, self.scalar.decoder),
                   runs)
        if ack is not None:
            ack()

    def _decode_batch(self, lines: List[bytes], runs=None) -> None:
        if self._kernel_fn is None or not self._device_allowed():
            # no columnar kernel (or breaker open): scalar per line
            self._window.fence()
            for raw in lines:
                self._scalar_handle(raw)
            return
        bid = None
        try:
            _faults.maybe_raise("device_decode")
            if self._fast_encode:
                import time as _time

                from . import pack

                bid = _tracer.begin(self.fmt)
                tp0 = _time.perf_counter()
                _tracer.enter("pack")
                packed = pack.pack_lines_2d(lines, self.max_len)
                if bid is not None:
                    _tracer.span(bid, "pack", tp0, _time.perf_counter(),
                                 rows=int(packed[5]))
                deferred = [False]
                _count_rows(packed)
                self._emit_fast(packed, deferred, runs, trace=bid)
                if not deferred[0]:
                    # emitted synchronously: close the trace here (a
                    # deferred batch closes it at its sequenced emit)
                    self._finish_batch(bid, self._flush_t0,
                                       rows=int(packed[5]))
            else:
                results = self._kernel_fn(lines)
                self._window.fence()
                self._emit(results, runs)
        except Exception as e:  # noqa: BLE001 - device degradation boundary
            _tracer.end(bid)
            if self._breaker is None:
                raise
            self._device_failed(e)
            self._window.fence()
            for raw in lines:
                self._scalar_handle(raw)
            return
        self._record_sync_success()

    # -- degradation / circuit breaker -------------------------------------
    def _device_allowed(self) -> bool:
        return self._breaker is None or self._breaker.allow()

    def _device_failed(self, e: BaseException) -> None:
        # flowcheck: disable=FC07 -- called both under the flush decode lock AND off-lock from the lane fetcher/sequencer threads; staging would need a drain hook on every caller for one emit per failed device batch on an already-cold decline path
        _events.emit(
            "batch", "device_error", route=self.fmt,
            detail=f"{type(e).__name__}: {e}",
            msg=f"device decode failed ({type(e).__name__}: {e}); "
                f"re-decoding the batch through the scalar oracle")
        self._breaker.record_failure(e)

    def _record_sync_success(self) -> None:
        """A device batch completed synchronously (no deferred fetch)."""
        if self._breaker is not None and self._window.pending() == 0:
            self._breaker.record_success()

    def _guarded_dispatch(self, packed, runs=None, lane=None,
                          trace=None, ack=None) -> None:
        """Route one packed tuple to the device tier, degrading to the
        scalar oracle (same bytes, no lines lost) on any device/XLA
        error when the breaker is armed.  ``lane`` pins the dispatch
        lane (device framing already committed the batch there);
        ``trace`` is the flight-recorder batch ID.  ``ack`` is the
        durability replay acknowledgment riding a replayed batch (never
        set on fresh ingest); it travels with the batch to the sink and
        fires once the bytes are flushed downstream."""
        if (ack is None and self.durability is not None
                and self.durability.should_spill()):
            # queue past the watermark: divert this fresh batch to the
            # on-disk WAL instead of blocking ingest on a full queue.
            # The pack keeps the raw chunk plus per-row start/length
            # vectors, so the spilled record reconstructs byte-exactly
            # at replay.  mode=require raises (DurabilityError) when
            # the spill tier itself cannot take the batch.
            _batch, _lens, chunk, starts, orig_lens, n_real = packed
            if n_real and self.durability.spill(
                    self.fmt, chunk, starts, orig_lens, int(n_real),
                    runs=runs):
                _tracer.end(trace)
                return
        deferred = [False]
        try:
            _faults.maybe_raise("device_decode")
            self._dispatch_packed(packed, deferred, runs, lane, trace,
                                  ack)
        except Exception as e:  # noqa: BLE001 - device degradation boundary
            _tracer.end(trace)
            if self._breaker is None:
                raise
            self._device_failed(e)
            # drain every lane before emitting this batch's scalar
            # re-decode, so mid-window failures keep batch order.  A
            # second ferried failure surfacing from the fence must not
            # leak past this boundary and drop the current batch: the
            # fence has fully drained by the time it re-raises, so
            # record the failure and continue to the fallback
            try:
                self._window.fence()
            except Exception as fe:  # noqa: BLE001 - device degradation boundary
                self._device_failed(fe)
            self._scalar_fallback_packed(packed)
            return
        if not deferred[0]:
            # completed synchronously; deferred batches are judged at
            # fetch time in _pop_emit instead — and this batch's
            # flush→emit wall is complete right here
            self._record_sync_success()
            self._finish_batch(trace, self._flush_t0, rows=int(packed[5]))

    def _scalar_handle(self, raw: bytes) -> None:
        """One line through the right scalar oracle, honoring the
        splitter flags set on this handler."""
        if self.fmt == "auto":
            handler = self._auto_scalar_for(raw)
        else:
            handler = self.scalar
        handler.quiet_empty = self.quiet_empty
        handler.bare_errors = self.bare_errors
        handler.handle_bytes(raw)

    def _auto_scalar_for(self, raw: bytes) -> ScalarHandler:
        """auto format: classify the line host-side (same decision table
        as the device kernel) and use that class's scalar oracle, so the
        degraded path stays byte-identical to the columnar one."""
        from .autodetect import (F_DNS, F_GELF, F_JSONL, F_LTSV,
                                 F_RFC3164, F_RFC5424, classify)

        cls = classify(raw, self._auto_extras)
        handler = self._auto_scalars.get(cls)
        if handler is None:
            if cls == F_RFC5424:
                decoder = self.scalar.decoder
            elif cls == F_LTSV:
                decoder = self._auto_ltsv or self._auto_ltsv_decoder(self._cfg)
            elif cls == F_GELF:
                from ..decoders import GelfDecoder

                decoder = GelfDecoder(self._cfg)
            elif cls == F_JSONL:
                from ..decoders import JSONLDecoder

                decoder = JSONLDecoder(self._cfg)
            elif cls == F_DNS:
                from ..decoders import DNSDecoder

                decoder = DNSDecoder(self._cfg)
            else:
                from ..decoders import RFC3164Decoder

                decoder = RFC3164Decoder(self._cfg)
            handler = ScalarHandler(self.tx, decoder, self.encoder)
            handler.record_hook = self.scalar.record_hook
            self._auto_scalars[cls] = handler
        return handler

    def _scalar_region(self, region: bytes, sep: bytes) -> None:
        lines = region.split(sep)
        lines.pop()  # regions end with the separator
        if self.ingest_strip_cr:
            lines = [ln[:-1] if ln.endswith(b"\r") else ln
                     for ln in lines]
        for raw in lines:
            self._scalar_handle(raw)

    def _scalar_fallback_packed(self, packed) -> None:
        """Re-decode one packed tuple's rows through the scalar oracle:
        the pack keeps the raw chunk plus per-row start/length vectors,
        so the original line bytes reconstruct exactly."""
        _batch, _lens, chunk, starts, orig_lens, n_real = packed
        for i in range(n_real):
            s = int(starts[i])
            self._scalar_handle(bytes(chunk[s:s + int(orig_lens[i])]))

    def _block_route_ok(self) -> bool:
        """Cheap applicability check, evaluated before any kernel work so
        an inapplicable route never pays a wasted device decode."""
        if not self._block_mode or self.fmt not in ("rfc5424", "rfc3164",
                                                     "ltsv", "gelf",
                                                     "jsonl", "dns",
                                                     "auto"):
            return False
        if self._enrich_hook is not None:
            # per-row _template_id fields don't fit the constant-segment
            # block encoders: enrichment rides the Record path
            return False
        from ..encoders.gelf import GelfEncoder
        from ..encoders.ltsv import LTSVEncoder
        from ..encoders.passthrough import PassthroughEncoder
        from ..encoders.rfc5424 import RFC5424Encoder
        from .block_common import merger_suffix

        if merger_suffix(self._merger) is None:
            return False
        from ..encoders.capnp import CapnpEncoder

        if (type(self.encoder) is CapnpEncoder
                and self.fmt in ("rfc5424", "rfc3164", "ltsv", "gelf")):
            # columnar capnp (the reference's default kafka output wire
            # format, mod.rs:104) from every kernel decoder; capnp_extra
            # is a constant blob on this route, so extras stay on the
            # fast tier here.  A typed ltsv_schema keeps the Record
            # path (per-value typing is per-row host work).
            if self.fmt == "ltsv":
                return not getattr(self.scalar.decoder, "schema", None)
            return True
        if self.fmt == "rfc3164":
            from ..encoders.rfc3164 import RFC3164Encoder

            if type(self.encoder) is RFC3164Encoder:
                # syslog->syslog relay re-encode; the prepend-timestamp
                # option is wall-clock-at-encode-time (per-call)
                return self.encoder.header_time_format is None
            if type(self.encoder) in (LTSVEncoder, RFC5424Encoder):
                return True
            if type(self.encoder) is GelfEncoder:
                from .encode_rfc3164_gelf_block import (
                    gelf_extra_consts_3164,
                )

                return gelf_extra_consts_3164(
                    self.encoder.extra) is not None
            return self._passthrough_ok
        if self.fmt == "ltsv":
            # LTSV decode block-encodes GELF, LTSV (self re-encode),
            # RFC5424, and capnp; typed-schema support (and its
            # per-row fallbacks) lives in the encoders
            if type(self.encoder) in (LTSVEncoder, RFC5424Encoder):
                return not getattr(self.scalar.decoder, "schema", None)
            if type(self.encoder) is not GelfEncoder:
                return False
            from .encode_ltsv_gelf_block import gelf_extra_consts_ltsv

            return gelf_extra_consts_ltsv(self.encoder.extra) is not None
        if self.fmt == "gelf":
            if type(self.encoder) in (LTSVEncoder, RFC5424Encoder):
                return True
            return (type(self.encoder) is GelfEncoder
                    and not self.encoder.extra)
        if self.fmt in ("jsonl", "dns"):
            # the new formats block-encode GELF and LTSV (the
            # high-volume production outputs); everything else keeps
            # the Record path
            if type(self.encoder) is LTSVEncoder:
                return True
            return (type(self.encoder) is GelfEncoder
                    and not self.encoder.extra)
        if self.fmt == "auto":
            # every classic class leg supports all four columnar
            # encoders (round 5); the opt-in jsonl/dns legs support
            # GELF/LTSV only; gelf_extra still needs static placement
            if type(self.encoder) is GelfEncoder and self.encoder.extra:
                return False
            enc_ok = (GelfEncoder, LTSVEncoder) if self._auto_extras \
                else (GelfEncoder, CapnpEncoder, LTSVEncoder,
                      RFC5424Encoder)
            return (type(self.encoder) in enc_ok
                    and not (self._auto_ltsv and self._auto_ltsv.schema))
        if type(self.encoder) is GelfEncoder:
            # extras with static placement ride the columnar route as
            # constant segments (encode_gelf_block.gelf_extra_slots)
            from .encode_gelf_block import gelf_extra_slots

            return gelf_extra_slots(self.encoder.extra) is not None
        if type(self.encoder) is PassthroughEncoder:
            return self._passthrough_ok
        return type(self.encoder) in (RFC5424Encoder, LTSVEncoder)

    def _route_cliff_reason(self) -> Optional[str]:
        """Why ``_block_route_ok`` can never be true for this config
        (None when the block route engages).  Config-static, evaluated
        once at construction for the startup warning.  Each branch names
        the key that ACTUALLY blocks this (fmt, encoder) pair — never a
        key whose removal would still leave the route disabled."""
        if self._block_route_ok():
            return None
        if self._enrich_hook is not None:
            return ("tenant.template_enrich is set (per-record "
                    "_template_id rides the Record path)")
        from ..encoders.gelf import GelfEncoder
        from ..encoders.passthrough import PassthroughEncoder
        from .block_common import merger_suffix

        if merger_suffix(self._merger) is None:
            return (f"output.framing {type(self._merger).__name__} has "
                    "no block merger")
        enc = self.encoder
        t = type(enc)
        no_columnar = (f"output.format {t.__name__} has no columnar "
                       f"encoder for input format '{self.fmt}'")
        from ..encoders.capnp import CapnpEncoder

        from ..encoders.ltsv import LTSVEncoder
        from ..encoders.rfc5424 import RFC5424Encoder

        if t in (CapnpEncoder, LTSVEncoder, RFC5424Encoder):
            if (self.fmt == "auto" and self._auto_extras
                    and t in (CapnpEncoder, RFC5424Encoder)):
                return ("input.auto_extra_formats is set (the jsonl/dns "
                        "legs block-encode GELF/LTSV only)")
            if self.fmt in ("ltsv", "auto"):
                # every class leg supports these encoders; the only
                # blocker left is the typed schema on the ltsv leg
                return "input.ltsv_schema is set"
            return no_columnar
        if t is GelfEncoder:
            # GELF output is columnar for every kernel format, so the
            # only possible blockers are the extras / the auto schema
            if enc.extra:
                if self.fmt in ("rfc5424", "rfc3164", "ltsv"):
                    return ("output.gelf_extra keys need dynamic "
                            "placement (leading '_' or a fixed-key "
                            "overwrite)")
                return "output.gelf_extra is set"
            if (self.fmt == "auto" and self._auto_ltsv
                    and self._auto_ltsv.schema):
                return "input.ltsv_schema is set"
            return no_columnar
        if t is PassthroughEncoder and self.fmt in ("rfc5424", "rfc3164"):
            return "output.syslog_prepend_timestamp is set"
        from ..encoders.rfc3164 import RFC3164Encoder

        if t is RFC3164Encoder and self.fmt == "rfc3164":
            return "output.syslog_prepend_timestamp is set"
        return no_columnar

    def _fused_route(self):
        """The registered fused decode→encode route for this handler's
        config, or None: fuse mode off, auto format (its per-class legs
        submit at fetch time), template mining on (the miner consumes
        host-fetched decode columns the fused tier never materializes),
        the sharded mesh owning the batch, or simply no fused program
        for this (format, encoder, merger)."""
        if (self._fuse_mode == "off" or self.fmt == "auto"
                or self._mine_block):
            return None
        if self._sharded_for(self.fmt) is not None:
            return None
        from . import fused_routes

        return fused_routes.route_for(
            self.fmt, self.encoder, self._merger,
            self.scalar.decoder if self.fmt == "ltsv" else None)

    def _emit_fast(self, packed, deferred=None, runs=None,
                   lane=None, trace=None, ack=None) -> None:
        """Span→bytes encode for one packed tuple: the columnar block
        route when engaged (submitted onto the next dispatch lane; that
        lane's fetcher thread fetches and encodes behind us, and the
        LaneSet sequencer emits in strict batch order), else the per-row
        fast path (gelf/passthrough only), else the Record path.
        ``lane`` (device framing) reuses an already-reserved lane whose
        device holds the batch; ``trace`` rides the window payload so
        the lane fetcher / sequencer stages land on the same batch
        trace."""
        if self._block_route_ok():
            import time as _time

            if deferred is not None:
                deferred[0] = True
            if lane is None:
                lane = self._window.next_lane()
            if len(self._lane_devices) > 1:
                _metrics.inc(f"lane{lane}_rows", int(packed[5]))
            ctx = (trace, self._flush_t0, ack)
            if _tracer.active:
                # a replayed batch carries no trace: the thread must
                # not stay bound to the one it minted last
                _tracer.bind(trace)
            if self.fmt == "auto":
                # the auto merger submits its per-class kernels at fetch
                # time, on the lane's fetcher thread (default device:
                # the per-class legs share one jit cache)
                ts0 = _time.perf_counter()
                _tracer.enter("submit")
                self._window.submit(lane, (None, packed, runs, ctx))
                if trace is not None:
                    _tracer.span(trace, "submit", ts0,
                                 _time.perf_counter())
                return
            route = self._fused_route()
            if route is not None:
                from . import fused_routes

                state = fused_routes.cooldown_state(
                    self._device_route_state, route)
                if state.get("cooldown", 0) > 0:
                    # fused tier cooling down after declines: stay on
                    # the split submit below for this batch
                    state["cooldown"] -= 1
                elif self._econs[lane % len(self._econs)].allow_fused():
                    # commit inputs to the lane device now; the fused
                    # program itself dispatches on the lane fetcher
                    # thread, where a compile-watchdog wait can never
                    # stall ingest
                    td0 = _time.perf_counter()
                    _tracer.enter("decode")
                    handle = fused_routes.submit(
                        route, packed, self._lane_devices[lane])
                    ts0 = _time.perf_counter()
                    if trace is not None:
                        _tracer.span(trace, "decode", td0, ts0,
                                     rows=int(packed[5]),
                                     note=f"fused:{route.name} commit")
                        _tracer.enter("submit")
                    self._window.submit(lane, (handle, packed, runs,
                                               ctx))
                    if trace is not None:
                        _tracer.span(trace, "submit", ts0,
                                     _time.perf_counter())
                    return
            td0 = _time.perf_counter()
            _tracer.enter("decode")
            handle = block_submit(
                self.fmt, packed, self._sharded_for(self.fmt),
                self._lane_devices[lane])
            ts0 = _time.perf_counter()
            if trace is not None:
                _tracer.span(trace, "decode", td0, ts0,
                             rows=int(packed[5]), note="split dispatch")
                _tracer.enter("submit")
            self._window.submit(lane, (handle, packed, runs, ctx))
            if trace is not None:
                _tracer.span(trace, "submit", ts0, _time.perf_counter())
            return
        from ..encoders.gelf import GelfEncoder
        from ..encoders.passthrough import PassthroughEncoder

        self._window.fence()
        if (self.fmt == "rfc5424" and self._enrich_hook is None
                and type(self.encoder) in (GelfEncoder,
                                           PassthroughEncoder)):
            # per-row span->bytes encode; with template enrichment on,
            # fall through to the Record path below so every row gets
            # its _template_id stamped before encode
            self._emit_encoded(
                _encode_packed_rfc5424_gelf(packed, self.encoder), runs)
            if ack is not None:
                # per-message route: rows were enqueued individually,
                # so the replay ack fires on enqueue (weaker than the
                # block route's sink-flush ack, still at-least-once)
                ack()
            return
        if self.fmt == "auto":
            from .autodetect import decode_auto_packed

            self._emit(decode_auto_packed(packed, self.max_len,
                                          self._auto_ltsv,
                                          self._auto_extras), runs)
            if ack is not None:
                ack()
            return
        self._emit(_decode_packed(self.fmt, packed, self.scalar.decoder),
                   runs)
        if ack is not None:
            ack()

    def _pop_emit(self, payload, lane: int = 0):
        """Fetch + encode one in-flight entry on a lane fetcher thread
        (concurrent across lanes); returns the emit closure the LaneSet
        sequencer runs in global submit order."""
        handle, packed, runs, ctx = payload
        bid, t_flush, ack = ctx
        import time as _time

        t0 = _time.perf_counter()
        if _tracer.active:
            _tracer.bind(bid)
        stats: dict = {}
        econ = self._econs[lane % len(self._econs)]
        try:
            _faults.maybe_raise("device_decode")
            emit = self._pop_emit_inner(handle, packed, stats, econ,
                                        runs, bid, ack)
        except Exception as e:  # noqa: BLE001 - device degradation boundary
            if self._breaker is None:
                _tracer.end(bid)
                raise
            self._device_failed(e)

            # emitted under the sequencer turnstile: the scalar re-
            # decode still lands at the batch's position in the stream
            def fallback():
                self._scalar_fallback_packed(packed)
                self._finish_batch(bid, t_flush, rows=int(packed[5]))

            return fallback
        # measure the route's compute wall now — the sequencer wait
        # ahead of emission is cross-lane scheduling, not route cost
        compute_s = _time.perf_counter() - t0 - stats.get("declined_s", 0.0)
        path = stats.get("path")
        if path is not None:
            # the fetcher's busy seconds by route: the host path sets
            # the pace, the other two are the economics' probes
            _metrics.add_seconds(f"route_pop_seconds_{path}", compute_s)
        t_done = _time.perf_counter()
        _tracer.enter("sequence")

        def finish():
            t_emit0 = _time.perf_counter()
            if bid is not None:
                # the gap between compute finishing and the turnstile
                # opening is cross-lane scheduling: its own span
                _tracer.span(bid, "sequence", t_done, t_emit0)
                _tracer.enter("emit")
            try:
                emit()
            except Exception as e:  # noqa: BLE001 - device degradation boundary
                # the emit closure is still inside the degradation
                # boundary (it ran inside _pop_emit_inner pre-lanes): a
                # failure here re-decodes the batch through the scalar
                # oracle at its sequenced position instead of ferrying
                # and losing the lines
                if self._breaker is None:
                    _tracer.end(bid)
                    raise
                self._device_failed(e)
                self._scalar_fallback_packed(packed)
                self._finish_batch(bid, t_flush, rows=int(packed[5]))
                return
            if bid is not None:
                _tracer.span(bid, "emit", t_emit0, _time.perf_counter(),
                             rows=int(packed[5]))
            if self._breaker is not None:
                self._breaker.record_success()
            if path is not None:
                # feed this lane's device-vs-host encode-route economics
                # (tpu/overlap.py) with the measured wall share; wall
                # burned by a declined device attempt (compile-watchdog
                # waits) is the device tier's fault, not the host
                # path's — already subtracted
                econ.observe(path, int(packed[5]), compute_s)
            self._finish_batch(bid, t_flush, rows=int(packed[5]))

        return finish

    def _finish_batch(self, bid, t_flush: float, rows: int = 0) -> None:
        """One batch fully emitted: observe the flush→emit wall
        (e2e_batch_seconds, plus the per-route family the SLO engine
        and regression sentinel key on), count the route's rows, and
        close its flight-recorder trace."""
        import time as _time

        if _faults.enabled() and _faults.fire("route_throttle"):
            # the sentinel drill: an injected per-batch delay collapses
            # this route's lines/s with no byte-level change —
            # obs/sentinel.py must surface it as perf_regression
            _time.sleep(0.05)
        e2e = (_time.perf_counter() - t_flush) if t_flush else None
        if e2e is not None:
            _metrics.observe("e2e_batch_seconds", e2e)
            _metrics.observe(f"e2e_batch_seconds_{self.fmt}", e2e)
        if rows:
            _metrics.inc(f"route_rows_{self.fmt}", int(rows))
        _tracer.end(bid, e2e)

    def _pop_emit_inner(self, handle, packed, stats=None, econ=None,
                        runs=None, bid=None, ack=None):
        """Fetch + encode one entry; returns a zero-arg emit closure
        (runs later, under the sequencer) so lanes can compute
        concurrently without reordering the merger stream.  ``bid``
        is the flight-recorder batch ID the lane-side spans (fetch/
        encode) land on.  ``ack`` (durability replay) rides the emitted
        block to the sink, or fires on enqueue for per-record emits."""
        import time as _time

        if econ is None:
            econ = self._econs[0]
        t0 = _time.perf_counter()
        # the merged auto legs interleave fetch and encode: one stage
        _tracer.enter("encode" if self.fmt == "auto" else "fetch")
        if self.fmt == "auto":
            from .autodetect import decode_auto_packed, encode_auto_gelf_blocks

            res = encode_auto_gelf_blocks(packed, self.encoder,
                                          self._merger, self._auto_ltsv,
                                          self._device_route_state,
                                          self._sharded_for,
                                          self._auto_extras)
            if res is None:
                results = decode_auto_packed(packed, self.max_len,
                                             self._auto_ltsv,
                                             self._auto_extras)
                if bid is not None:
                    _tracer.span(bid, "encode", t0,
                                 _time.perf_counter(), note="auto-record")
                return lambda: (self._emit(results, runs),
                                ack() if ack is not None else None)
            # per-leg fetch time is folded into encode_seconds here: the
            # merger interleaves four kernels' fetches with their encodes
            t1 = _time.perf_counter()
            _metrics.add_seconds("encode_seconds", t1 - t0)
            if bid is not None:
                _tracer.span(bid, "encode", t0, t1, rows=int(packed[5]),
                             note="auto merged fetch+encode")
            return lambda: self._emit_block(res, packed[5], ack)
        ltsv_dec = self.scalar.decoder if self.fmt == "ltsv" else None
        from . import fused_routes as _fr

        fused_declined_s = 0.0
        if isinstance(handle, _fr.FusedHandle):
            tf0 = _time.perf_counter()
            fres, ffetch_s = _fr.fetch_encode(
                handle, packed, self.encoder, self._merger, ltsv_dec,
                self._device_route_state)
            if fres is not None:
                tf1 = _time.perf_counter()
                if stats is not None:
                    stats["path"] = "fused"
                    stats["declined_s"] = 0.0
                _metrics.add_seconds("device_fetch_seconds", ffetch_s)
                _metrics.add_seconds("encode_seconds",
                                     tf1 - tf0 - ffetch_s)
                if bid is not None:
                    _tracer.span(bid, "fetch", tf0, tf0 + ffetch_s,
                                 note="fused")
                    _tracer.span(bid, "encode", tf0 + ffetch_s, tf1,
                                 rows=int(packed[5]), note="fused")
                return lambda: self._emit_block(fres, packed[5], ack)
            # fused tier declined (compile pending, cooldown, or tier
            # fraction): fall back to the split path right here on the
            # lane fetcher thread — re-dispatch the split decode on the
            # same lane device and continue down the existing ladder.
            # The wall burned by the declined fused attempt is charged
            # to the decline metric, not to the split path's economics
            # sample (subtracted via stats["declined_s"] below).
            fused_declined_s = _time.perf_counter() - tf0
            _metrics.add_seconds("device_encode_declined_seconds",
                                 fused_declined_s)
            _metrics.inc("fused_fallbacks")
            _metrics.inc(f"fused_fallbacks_{handle.route.name}")
            _events.emit("batch", "fused_fallback",
                         route=handle.route.name,
                         cost=fused_declined_s, cost_unit="declined_s")
            handle = block_submit(self.fmt, packed, None, handle.device)
        mined: list = []
        column_tap = None
        if self._mine_block:
            # pure span extraction on this (concurrent) fetcher thread;
            # the observe itself runs inside the sequenced emit closure
            # below, so template IDs assign in batch order and stay
            # stable across runs and lane counts
            column_tap = lambda host_out: mined.append(
                self._miners.extract_block(self.fmt, packed, host_out))
        res, fetch_s, declined_s = block_fetch_encode(
            self.fmt, handle, packed, self.encoder, self._merger,
            ltsv_dec, self._device_route_state,
            # mining consumes the fetched decode columns: pin the host
            # block path while it is on (the device-encode tier elides
            # exactly the channels the miner reads)
            allow_device=econ.allow_device() and not self._mine_block,
            stats=stats, column_tap=column_tap)
        if stats is not None:
            stats["declined_s"] = declined_s + fused_declined_s
        if res is None:
            # the route declined after the fact (e.g. an oversized
            # ltsv_schema or a configured suffix): Record path
            results = _decode_packed(self.fmt, packed, self.scalar.decoder)
            if bid is not None:
                _tracer.span(bid, "encode", t0, _time.perf_counter(),
                             note="record-path")
            return lambda: (self._emit(results, runs),
                            ack() if ack is not None else None)
        t2 = _time.perf_counter()
        _metrics.add_seconds("device_fetch_seconds", fetch_s)
        _metrics.add_seconds("encode_seconds",
                             t2 - t0 - fetch_s - declined_s)
        if bid is not None:
            # fetch interleaves with encode inside the driver, so the
            # two spans split the measured wall at the fetch share
            _tracer.span(bid, "fetch", t0, t0 + fetch_s,
                         note=stats.get("path") if stats else None)
            _tracer.span(bid, "encode", t0 + fetch_s, t2,
                         rows=int(packed[5]),
                         note=stats.get("path") if stats else None)
        if mined and mined[0] is not None:
            def emit_mined():
                self._miners.observe_rows(mined[0], runs)
                self._emit_block(res, packed[5], ack)

            return emit_mined
        return lambda: self._emit_block(res, packed[5], ack)

    def _emit_block(self, res, n_real: int, ack=None) -> None:
        _metrics.inc("input_lines", n_real)
        if self._breaker is not None:
            self._breaker.observe_batch(n_real, res.fallback_rows)
        if res.fallback_rows:
            _metrics.inc("fallback_rows", res.fallback_rows)
        for error, line in res.errors:
            if error == "__utf8__":
                _metrics.inc("invalid_utf8")
                print("Invalid UTF-8 input", file=sys.stderr)
                continue
            _metrics.inc("decode_errors")
            if self.bare_errors:
                print(error, file=sys.stderr)
            else:
                stripped = line.strip()
                if not (self.quiet_empty and not stripped):
                    print(f"{error}: [{stripped}]", file=sys.stderr)
        count = len(res.block)
        if count:
            _metrics.inc("decoded_records", count)
            _metrics.inc("enqueued", count)
            if ack is not None:
                # the replay ack rides the block to the sink: it fires
                # in outputs.ack_item once the bytes are flushed
                # downstream, and only then does the WAL cursor advance
                res.block.ack_cb = ack
            self.tx.put(res.block)
        elif ack is not None:
            # every row decoded to an error (nothing reaches the sink):
            # the record is fully consumed, so acknowledge it now
            ack()

    def _emit_encoded(self, results, runs=None) -> None:
        """Emit pre-encoded bytes from the span->bytes fast path."""
        _metrics.inc("input_lines", len(results))
        expanded = self._expand_runs(runs, len(results))
        prev_tag = _tenancy.current_name() if expanded is not None else None
        try:
            self._emit_encoded_rows(results, expanded)
        finally:
            if expanded is not None:
                _tenancy.set_current(prev_tag)

    def _emit_encoded_rows(self, results, expanded) -> None:
        for i, res in enumerate(results):
            if res.encoded is None:
                if res.error == "__utf8__":
                    _metrics.inc("invalid_utf8")
                    print("Invalid UTF-8 input", file=sys.stderr)
                    continue
                _metrics.inc("decode_errors")
                if self.bare_errors:
                    print(res.error, file=sys.stderr)
                else:
                    stripped = res.line.strip()
                    if not (self.quiet_empty and not stripped):
                        print(f"{res.error}: [{stripped}]", file=sys.stderr)
                continue
            _metrics.inc("decoded_records")
            _metrics.inc("enqueued")
            if expanded is not None:
                _tenancy.set_current(expanded[i])
            self.tx.put(res.encoded)

    def _emit(self, results, runs=None) -> None:
        _metrics.inc("input_lines", len(results))
        # Per-row tenant attribution via the ingest-order runs when they
        # cover this batch (results are in row order, error rows
        # included): drives both mining/enrichment AND the fair queue's
        # lane choice, so a mixed-tenant Record-route batch never lands
        # wholesale on whichever tenant's thread happened to flush.  A
        # run mismatch falls back to the emitting thread's tag rather
        # than smearing rows across tenants non-deterministically.
        expanded = self._expand_runs(runs, len(results))
        default_tenant = None
        if self._miners is not None and expanded is None:
            default_tenant = _tenancy.current_or_default()
        prev_tag = _tenancy.current_name() if expanded is not None else None
        try:
            self._emit_rows(results, expanded, default_tenant)
        finally:
            if expanded is not None:
                _tenancy.set_current(prev_tag)

    @staticmethod
    def _expand_runs(runs, n_rows: int):
        if runs and sum(n for _, n in runs) == n_rows:
            return [t for t, n in runs for _ in range(n)]
        return None

    def _emit_rows(self, results, expanded, default_tenant) -> None:
        for i, res in enumerate(results):
            if res.record is None:
                if res.error == "__utf8__":
                    _metrics.inc("invalid_utf8")
                    print("Invalid UTF-8 input", file=sys.stderr)
                    continue
                _metrics.inc("decode_errors")
                if self.bare_errors:
                    print(res.error, file=sys.stderr)
                else:
                    stripped = res.line.strip()
                    if not (self.quiet_empty and not stripped):
                        print(f"{res.error}: [{stripped}]", file=sys.stderr)
                continue
            if self._miners is not None:
                tenant = expanded[i] if expanded is not None else default_tenant
                # with enrichment the hook both mines and stamps
                # _template_id pre-encode
                if self._enrich_hook is not None:
                    self._enrich_hook(res.record, tenant)
                else:
                    self._miners.observe_msg(tenant, res.record.msg or "")
            try:
                encoded = self.encoder.encode(res.record)
            except EncodeError as e:
                _metrics.inc("encode_errors")
                stripped = res.line.strip()
                if not (self.quiet_empty and not stripped):
                    print(f"{e}: [{stripped}]", file=sys.stderr)
                continue
            _metrics.inc("decoded_records")
            _metrics.inc("enqueued")
            if expanded is not None:
                # lane attribution for the fair queue: the put rides
                # the row's own tenant tag, not the flusher's
                _tenancy.set_current(expanded[i])
            self.tx.put(encoded)


# bound on a single session's buffered region (bytes) before a flush is
# forced regardless of the record estimate — keeps a no-separator flood
# (or a giant syslen body) from growing the RegionBuffer unboundedly
_RAW_REGION_CAP = 4 << 20


class _RawSession:
    """Per-connection RegionBuffer for device-resident framing.

    One splitter ``run`` (one connection/stream) owns one session: raw
    chunks accumulate here untouched, the handler frames them at flush
    (device kernel or host fallback), and the carry-over tail — a
    record split across a chunk or flush boundary — stays in the
    session between flushes.  ``tag`` pins the whole session to the
    connection's tenant (one stream = one tenant), so per-row run
    attribution is exact without per-chunk record counts.

    ``est`` is the pending-record estimate driving the batch-size
    flush trigger: exact for line/nul (one memchr-speed separator
    count per chunk), an upper bound for syslen (each frame consumes
    at least one space).
    """

    def __init__(self, handler, framing: str):
        self.handler = handler
        self.framing = framing
        self.sep = b"\0" if framing == "nul" else b"\n"
        self.carry = b""
        self.chunks: List[bytes] = []
        self.est = 0
        self.nbytes = 0
        self.dead = False
        self.tag = _tenancy.current_name()

    def push(self, chunk: bytes) -> bool:
        """Buffer one raw chunk; returns False when the session died
        (a mid-stream framing error — the splitter closes the stream
        like the host scan does)."""
        if self.dead:
            return False
        h = self.handler
        est = chunk.count(b" " if self.framing == "syslen" else self.sep)
        with h._lock:
            self.chunks.append(chunk)
            self.nbytes += len(chunk)
            self.est += est
            h._raw_est += est
            full = (h._pending_locked() >= h.batch_size
                    or self.nbytes + len(self.carry) >= _RAW_REGION_CAP)
            if not full and h._timer is None and h._start_timer:
                h._timer = threading.Timer(h.flush_ms / 1000.0, h.flush)
                h._timer.daemon = True
                h._timer.start()
        if full:
            h.flush(drain=False)
        return not self.dead

    def finish(self, idle: bool = False) -> None:
        """End of stream: flush pending data, then resolve the carry
        with the host splitters' exact EOF semantics — line/nul emit a
        trailing partial frame (BufRead::lines parity), syslen prints
        the host scan's short-read / bad-length message."""
        h = self.handler
        h.flush(drain=True)
        with h._lock:
            carry, self.carry = self.carry, b""
            if self in h._raw_sessions:
                h._raw_sessions.remove(self)
        if self.dead:
            return
        if self.framing == "syslen":
            from ..splitters import SyslenSplitter

            # stderr parity with SyslenSplitter._run_spans: a carry
            # mid-body is a short read; an idle timeout outside a body
            # (even with a partial length prefix buffered) closes
            # quietly; only a hard EOF on a non-body carry is a
            # bad-length error
            if carry and SyslenSplitter._mid_body(carry):
                print("failed to fill whole buffer", file=sys.stderr)
            elif idle:
                print(
                    "Client hasn't sent any data for a while - Closing "
                    "idle connection", file=sys.stderr)
            elif carry:
                print("Can't read message's length", file=sys.stderr)
            return
        if carry:
            if self.framing == "line" and carry.endswith(b"\r"):
                carry = carry[:-1]
            charge = getattr(self, "charge", None)
            if charge is not None and not charge.admit_region(
                    1, len(carry)):
                # EOF partial frame charges like the host splitter's
                # handle_bytes(raw): one record, its bytes
                return
            h.handle_bytes(carry)


def _count_rows(packed) -> None:
    """One dispatched batch's real rows against the bucket it was
    padded to (tpu/pack.py bucket_rows): what crosses the link is the
    bucket."""
    _metrics.inc("batch_rows_real", int(packed[5]))
    _metrics.inc("batch_rows_padded", int(packed[0].shape[0]))


def block_submit(fmt, packed, sharded=None, device=None):
    """Dispatch one packed tuple's kernel asynchronously (JAX futures);
    pair with block_fetch_encode.  ``sharded`` (parallel.mesh.
    ShardedDecode) swaps in the multi-chip mesh kernel.  ``device``
    (lane dispatch) commits the inputs to that device before the jit
    call, so the decode — and every downstream device-encode stage that
    reuses the handle's device arrays — runs on the lane's chip."""
    batch, lens = packed[0], packed[1]
    if device is not None and sharded is None:
        from .device_common import h2d

        # committed placement: the jit executes on the lane device and
        # jnp.asarray inside the submit fns is a no-op on these
        batch, lens = h2d(batch, lens, device)
    if fmt == "rfc3164":
        from . import rfc3164

        return rfc3164.decode_rfc3164_submit(batch, lens, sharded)
    if fmt == "ltsv":
        from . import ltsv

        return ltsv.decode_ltsv_submit(batch, lens, sharded)
    if fmt == "gelf":
        from . import gelf

        return gelf.decode_gelf_submit(batch, lens, sharded)
    if fmt == "jsonl":
        from . import jsonl

        return jsonl.decode_jsonl_submit(batch, lens, sharded)
    if fmt == "dns":
        from . import dns

        return dns.decode_dns_submit(batch, lens, sharded)
    from . import rfc5424

    return rfc5424.decode_rfc5424_submit(batch, lens, sharded=sharded)


def block_fetch_encode(fmt, handle, packed, encoder, merger,
                       ltsv_decoder=None, route_state=None,
                       allow_device=True, stats=None, column_tap=None):
    """Block on a submitted kernel and run the format's columnar block
    encoder; returns (BlockResult-or-None, fetch_seconds,
    declined_seconds) — the last is wall time burned by a declined
    device-encode attempt, so callers can keep stage metrics additive.

    ``allow_device=False`` skips the device-encode tier outright (the
    route economics measured the host block path as cheaper on this
    backend); ``stats`` (optional dict) gets ``stats["path"]`` set to
    ``"device"`` or ``"host"`` for whichever tier produced the block.
    ``column_tap`` (template mining) is called with the fetched decode
    channels on the host path — callers that set it pass
    ``allow_device=False`` so the channels are actually fetched; a tap
    failure is contained (counted + logged), never a lost batch."""
    import time as _time

    t0 = _time.perf_counter()
    declined_s = 0.0
    # decline/cooldown hysteresis is per format: in auto mode several
    # legs share the caller's dict, and one leg's success must not
    # reset another leg's decline count (nor double-decrement cooldowns)
    if route_state is not None:
        route_state = route_state.setdefault(fmt, {})
    if fmt == "rfc3164":
        from ..encoders.passthrough import PassthroughEncoder
        from ..encoders.rfc3164 import RFC3164Encoder
        from . import (
            device_rfc3164,
            encode_passthrough_block,
            encode_rfc3164_3164_block,
            encode_rfc3164_gelf_block,
            encode_rfc5424_block,
            rfc3164,
        )
        from ..encoders.rfc5424 import RFC5424Encoder

        if allow_device and device_rfc3164.route_ok(encoder, merger):
            res, fetch_s = device_rfc3164.fetch_encode(
                handle, packed, encoder, merger, route_state)
            if res is not None:
                if stats is not None:
                    stats["path"] = "device"
                return res, fetch_s, 0.0
            declined_s = _time.perf_counter() - t0
            _metrics.add_seconds("device_encode_declined_seconds",
                                 declined_s)
            t0 = _time.perf_counter()
        elif allow_device and type(encoder) is RFC5424Encoder:
            # PR 19: rfc3164→rfc5424 device leg (shared SD-assembly
            # core with the rfc5424→rfc5424 kernel)
            from . import device_rfc5424_out

            if device_rfc5424_out.route_ok(encoder, merger):
                res, fetch_s = device_rfc5424_out.fetch_encode_3164(
                    handle, packed, encoder, merger, route_state)
                if res is not None:
                    if stats is not None:
                        stats["path"] = "device"
                    return res, fetch_s, 0.0
                declined_s = _time.perf_counter() - t0
                _metrics.add_seconds("device_encode_declined_seconds",
                                     declined_s)
                t0 = _time.perf_counter()
        host_out = rfc3164.decode_rfc3164_fetch(handle)
        t1 = _time.perf_counter()
        _tracer.enter("encode")
        _tap_columns(column_tap, host_out)
        from ..encoders.capnp import CapnpEncoder
        from ..encoders.ltsv import LTSVEncoder
        from . import encode_capnp_block, encode_ltsv_block

        fn3164 = {
            PassthroughEncoder:
                encode_passthrough_block.encode_rfc3164_passthrough_block,
            RFC3164Encoder:
                encode_rfc3164_3164_block.encode_rfc3164_3164_block,
            CapnpEncoder:
                encode_capnp_block.encode_rfc3164_capnp_block,
            LTSVEncoder:
                encode_ltsv_block.encode_rfc3164_ltsv_block,
            RFC5424Encoder:
                encode_rfc5424_block.encode_rfc3164_rfc5424_block,
        }.get(type(encoder),
              encode_rfc3164_gelf_block.encode_rfc3164_gelf_block)
        res = fn3164(
            packed[2], packed[3], packed[4], host_out, packed[5],
            packed[0].shape[1], encoder, merger)
    elif fmt == "ltsv":
        from . import device_ltsv, encode_ltsv_gelf_block, ltsv

        if allow_device and device_ltsv.route_ok(encoder, merger,
                                                 ltsv_decoder):
            res, fetch_s = device_ltsv.fetch_encode(
                handle, packed, encoder, merger, route_state,
                ltsv_decoder)
            if res is not None:
                if stats is not None:
                    stats["path"] = "device"
                return res, fetch_s, 0.0
            declined_s = _time.perf_counter() - t0
            _metrics.add_seconds("device_encode_declined_seconds",
                                 declined_s)
            t0 = _time.perf_counter()
        host_out = ltsv.decode_ltsv_fetch(handle)
        t1 = _time.perf_counter()
        _tracer.enter("encode")
        _tap_columns(column_tap, host_out)
        from ..encoders.capnp import CapnpEncoder
        from ..encoders.ltsv import LTSVEncoder
        from ..encoders.rfc5424 import RFC5424Encoder

        if type(encoder) is CapnpEncoder:
            from . import encode_capnp_block

            res = encode_capnp_block.encode_ltsv_capnp_block(
                packed[2], packed[3], packed[4], host_out, packed[5],
                packed[0].shape[1], encoder, merger, ltsv_decoder)
        elif type(encoder) is LTSVEncoder:
            from . import encode_ltsv_block

            res = encode_ltsv_block.encode_ltsv_ltsv_block(
                packed[2], packed[3], packed[4], host_out, packed[5],
                packed[0].shape[1], encoder, merger, ltsv_decoder)
        elif type(encoder) is RFC5424Encoder:
            from . import encode_rfc5424_block

            res = encode_rfc5424_block.encode_ltsv_rfc5424_block(
                packed[2], packed[3], packed[4], host_out, packed[5],
                packed[0].shape[1], encoder, merger, ltsv_decoder)
        else:
            res = encode_ltsv_gelf_block.encode_ltsv_gelf_block(
                packed[2], packed[3], packed[4], host_out, packed[5],
                packed[0].shape[1], encoder, merger, ltsv_decoder)
    elif fmt == "jsonl":
        from ..encoders.ltsv import LTSVEncoder
        from . import encode_jsonl_block, jsonl

        # no device-encode tier for the new formats (yet): the host
        # block path is the fast tier, so the fetch is unconditional
        host_out = jsonl.decode_jsonl_fetch(handle)
        t1 = _time.perf_counter()
        _tracer.enter("encode")
        _tap_columns(column_tap, host_out)
        if type(encoder) is LTSVEncoder:
            res = encode_jsonl_block.encode_jsonl_ltsv_block(
                packed[2], packed[3], packed[4], host_out, packed[5],
                packed[0].shape[1], encoder, merger)
        else:
            res = encode_jsonl_block.encode_jsonl_gelf_block(
                packed[2], packed[3], packed[4], host_out, packed[5],
                packed[0].shape[1], encoder, merger)
    elif fmt == "dns":
        from ..encoders.ltsv import LTSVEncoder
        from . import dns, encode_dns_block

        host_out = dns.decode_dns_fetch(handle)
        t1 = _time.perf_counter()
        _tracer.enter("encode")
        _tap_columns(column_tap, host_out)
        if type(encoder) is LTSVEncoder:
            res = encode_dns_block.encode_dns_ltsv_block(
                packed[2], packed[3], packed[4], host_out, packed[5],
                packed[0].shape[1], encoder, merger)
        else:
            res = encode_dns_block.encode_dns_gelf_block(
                packed[2], packed[3], packed[4], host_out, packed[5],
                packed[0].shape[1], encoder, merger)
    elif fmt == "gelf":
        from ..encoders.ltsv import LTSVEncoder
        from ..encoders.rfc5424 import RFC5424Encoder
        from . import device_gelf_gelf, encode_gelf_gelf_block, gelf

        if allow_device and device_gelf_gelf.route_ok(encoder, merger):
            res, fetch_s = device_gelf_gelf.fetch_encode(
                handle, packed, encoder, merger, route_state)
            if res is not None:
                if stats is not None:
                    stats["path"] = "device"
                return res, fetch_s, 0.0
            declined_s = _time.perf_counter() - t0
            _metrics.add_seconds("device_encode_declined_seconds",
                                 declined_s)
            t0 = _time.perf_counter()
        host_out = gelf.decode_gelf_fetch(handle)
        t1 = _time.perf_counter()
        _tracer.enter("encode")
        from ..encoders.capnp import CapnpEncoder

        if type(encoder) is LTSVEncoder:
            from . import encode_ltsv_block

            res = encode_ltsv_block.encode_gelf_ltsv_block(
                packed[2], packed[3], packed[4], host_out, packed[5],
                packed[0].shape[1], encoder, merger)
        elif type(encoder) is CapnpEncoder:
            from . import encode_capnp_block

            res = encode_capnp_block.encode_gelf_capnp_block(
                packed[2], packed[3], packed[4], host_out, packed[5],
                packed[0].shape[1], encoder, merger)
        elif type(encoder) is RFC5424Encoder:
            from . import encode_rfc5424_block

            res = encode_rfc5424_block.encode_gelf_rfc5424_block(
                packed[2], packed[3], packed[4], host_out, packed[5],
                packed[0].shape[1], encoder, merger)
        else:
            res = encode_gelf_gelf_block.encode_gelf_gelf_block(
                packed[2], packed[3], packed[4], host_out, packed[5],
                packed[0].shape[1], encoder, merger)
    else:
        from . import rfc5424

        # the rfc5424 device-encode tier is per output leg: GELF keeps
        # its original module; the PR 19 legs (rfc5424/ltsv/capnp out)
        # each bring their own kernel + route gate.  One module per
        # encoder type, so at most one device attempt per batch.
        dev_mod = _rfc5424_device_module(encoder)
        if (allow_device and dev_mod is not None
                and dev_mod.route_ok(encoder, merger)):
            res, fetch_s = dev_mod.fetch_encode(handle, packed,
                                                encoder, merger,
                                                route_state)
            if res is not None:
                if stats is not None:
                    stats["path"] = "device"
                return res, fetch_s, 0.0
            # charge the declined attempt to its own metric, not to the
            # host path's fetch or encode share
            declined_s = _time.perf_counter() - t0
            _metrics.add_seconds("device_encode_declined_seconds",
                                 declined_s)
            t0 = _time.perf_counter()
        host_out = rfc5424.decode_rfc5424_fetch(handle)
        t1 = _time.perf_counter()
        _tracer.enter("encode")
        _tap_columns(column_tap, host_out)
        res = _encode_block_from_host(host_out, packed, encoder, merger)
    if stats is not None and res is not None:
        stats["path"] = "host"
    return res, t1 - t0, declined_s


def _rfc5424_device_module(encoder):
    """The split device-encode module for an rfc5424-input batch, keyed
    on the concrete output encoder type — None when no device kernel
    exists for this leg (host block path is the only tier)."""
    from ..encoders.capnp import CapnpEncoder
    from ..encoders.gelf import GelfEncoder
    from ..encoders.ltsv import LTSVEncoder
    from ..encoders.rfc5424 import RFC5424Encoder

    t = type(encoder)
    if t is GelfEncoder:
        from . import device_gelf

        return device_gelf
    if t is RFC5424Encoder:
        from . import device_rfc5424_out

        return device_rfc5424_out
    if t is LTSVEncoder:
        from . import device_ltsv_out

        return device_ltsv_out
    if t is CapnpEncoder:
        from . import device_capnp

        return device_capnp
    return None


def _tap_columns(column_tap, host_out) -> None:
    """Run the template-mining column tap over one fetched kernel
    output; mining is a statistics stage, so a tap failure is counted
    and logged but never costs the batch."""
    if column_tap is None:
        return
    try:
        column_tap(host_out)
    except Exception as e:  # noqa: BLE001 - stats stage, never lose the batch
        _metrics.inc("template_tap_errors")
        print(f"template column tap failed ({type(e).__name__}: {e}); "
              "batch not mined", file=sys.stderr)


def _encode_block_from_host(host_out, packed, encoder, merger):
    """Columnar block encode from fetched kernel channels, dispatched
    on the encoder type (caller pre-checked applicability)."""
    from ..encoders.capnp import CapnpEncoder
    from ..encoders.ltsv import LTSVEncoder
    from ..encoders.passthrough import PassthroughEncoder
    from ..encoders.rfc5424 import RFC5424Encoder
    from . import (
        encode_capnp_block,
        encode_gelf_block,
        encode_ltsv_block,
        encode_passthrough_block,
        encode_rfc5424_block,
    )

    batch, lens, chunk, starts, orig_lens, n_real = packed
    fn = {
        PassthroughEncoder:
            encode_passthrough_block.encode_rfc5424_passthrough_block,
        RFC5424Encoder: encode_rfc5424_block.encode_rfc5424_rfc5424_block,
        LTSVEncoder: encode_ltsv_block.encode_rfc5424_ltsv_block,
        CapnpEncoder: encode_capnp_block.encode_rfc5424_capnp_block,
    }.get(type(encoder), encode_gelf_block.encode_rfc5424_gelf_block)
    return fn(chunk, starts, orig_lens, host_out, n_real, batch.shape[1],
              encoder, merger)


def _encode_packed_rfc5424_gelf(packed, encoder):
    import jax.numpy as jnp

    from ..encoders.passthrough import PassthroughEncoder
    from . import encode_gelf, encode_passthrough, rfc5424

    batch, lens, chunk, starts, orig_lens, n_real = packed
    host_out = rfc5424.decode_rfc5424_host(batch, lens)
    if type(encoder) is PassthroughEncoder:
        return encode_passthrough.encode_rfc5424_passthrough(
            chunk, starts, orig_lens, host_out, n_real, batch.shape[1], encoder)
    return encode_gelf.encode_rfc5424_gelf(chunk, starts, orig_lens, host_out,
                                           n_real, batch.shape[1], encoder)


def _decode_packed(fmt, packed, decoder=None):
    """Run the columnar kernel + materializer for one packed tuple
    (batch, lens, chunk, starts, orig_lens, n_real)."""
    import jax.numpy as jnp

    batch, lens, chunk, starts, orig_lens, n_real = packed
    if fmt == "rfc5424":
        from . import materialize, rfc5424

        host_out = rfc5424.decode_rfc5424_host(batch, lens)
        return materialize.materialize(chunk, starts, lens, orig_lens, host_out,
                                       n_real, max_len=batch.shape[1])
    jb, jl = jnp.asarray(batch), jnp.asarray(lens)
    if fmt == "ltsv":
        from . import ltsv, materialize_ltsv

        out = ltsv.decode_ltsv_jit(jb, jl)
        host_out = {k: np.asarray(v) for k, v in out.items()}
        return materialize_ltsv.materialize_ltsv(chunk, starts, orig_lens, host_out,
                                                 n_real, batch.shape[1], decoder)
    if fmt == "gelf":
        from . import gelf, materialize_gelf

        host_out = gelf.decode_gelf_fetch(
            gelf.decode_gelf_submit(batch, lens))
        return materialize_gelf.materialize_gelf(chunk, starts, orig_lens, host_out,
                                                 n_real, batch.shape[1])
    if fmt == "jsonl":
        from . import jsonl, materialize_jsonl

        host_out = jsonl.decode_jsonl_fetch(
            jsonl.decode_jsonl_submit(batch, lens))
        return materialize_jsonl.materialize_jsonl(
            chunk, starts, orig_lens, host_out, n_real, batch.shape[1])
    if fmt == "dns":
        from . import dns, materialize_dns

        host_out = dns.decode_dns_fetch(dns.decode_dns_submit(batch, lens))
        return materialize_dns.materialize_dns(
            chunk, starts, orig_lens, host_out, n_real, batch.shape[1])
    if fmt == "rfc3164":
        from ..utils.timeparse import current_year_utc
        from . import materialize_rfc3164, rfc3164

        out = rfc3164.decode_rfc3164_jit(jb, jl, np.int32(current_year_utc()))
        host_out = {k: np.asarray(v) for k, v in out.items()}
        return materialize_rfc3164.materialize_rfc3164(
            chunk, starts, orig_lens, host_out, n_real, batch.shape[1])
    raise ValueError(f"no kernel for format {fmt}")


def _decode_gelf_batch(lines, max_len):
    from . import pack

    return _decode_packed("gelf", pack.pack_lines_2d(lines, max_len))


def _decode_jsonl_batch(lines, max_len):
    from . import pack

    return _decode_packed("jsonl", pack.pack_lines_2d(lines, max_len))


def _decode_dns_batch(lines, max_len):
    from . import pack

    return _decode_packed("dns", pack.pack_lines_2d(lines, max_len))


def _decode_auto_batch(lines, max_len, ltsv_decoder=None, extras=()):
    from .autodetect import decode_auto_batch

    return decode_auto_batch(lines, max_len, ltsv_decoder, extras)


def _decode_ltsv_batch(lines, max_len, decoder):
    from . import pack

    return _decode_packed("ltsv", pack.pack_lines_2d(lines, max_len), decoder)


def _decode_rfc5424_batch(lines, max_len):
    from . import pack

    return _decode_packed("rfc5424", pack.pack_lines_2d(lines, max_len))


def _decode_rfc3164_batch(lines, max_len):
    from . import pack

    return _decode_packed("rfc3164", pack.pack_lines_2d(lines, max_len))

