"""Orchestrator: config → component factories → queue wiring → run.

Parity model: /root/reference/src/flowgger/mod.rs:95-472 — defaults,
factory match arms, output-framing inference table, bounded queue, output
consumer startup, blocking input loop.

TPU extension: ``input.format`` values suffixed ``_tpu`` (rfc5424_tpu,
gelf_tpu, ltsv_tpu, auto_tpu) select the batched columnar decode path
(flowgger_tpu.tpu): the scalar decoder for that format is still
constructed as the per-line fallback oracle, and the handler factory
returns a BatchHandler instead of a ScalarHandler.
"""

from __future__ import annotations

import queue
from typing import Optional

from .config import Config, ConfigError
from .decoders import (
    GelfDecoder,
    InvalidDecoder,
    LTSVDecoder,
    RFC3164Decoder,
    RFC5424Decoder,
)
from .encoders import (
    CapnpEncoder,
    GelfEncoder,
    LTSVEncoder,
    PassthroughEncoder,
    RFC3164Encoder,
    RFC5424Encoder,
)
from .mergers import LineMerger, NulMerger, SyslenMerger
from .splitters import ScalarHandler

# mod.rs:101-109
DEFAULT_INPUT_FORMAT = "rfc5424"
DEFAULT_INPUT_TYPE = "syslog-tls"
DEFAULT_OUTPUT_FORMAT = "gelf"
DEFAULT_OUTPUT_FRAMING = "noop"
DEFAULT_OUTPUT_TYPE = "kafka"
DEFAULT_QUEUE_SIZE = 10_000_000


def get_input(input_type: str, config: Config):
    """Input factory (mod.rs:181-193)."""
    if input_type == "redis":
        from .inputs.redis_input import RedisInput

        return RedisInput(config)
    if input_type == "stdin":
        from .inputs import StdinInput

        return StdinInput(config)
    if input_type in ("tcp", "syslog-tcp"):
        from .inputs.tcp_input import TcpInput

        return TcpInput(config)
    if input_type in ("tcp_co", "tcpco", "syslog-tcp_co", "syslog-tcpco"):
        from .inputs.tcp_input import TcpCoInput

        return TcpCoInput(config)
    if input_type in ("tls", "syslog-tls"):
        from .inputs.tls_input import TlsInput

        return TlsInput(config)
    if input_type in ("tls_co", "tlsco", "syslog-tls_co", "syslog-tlsco"):
        from .inputs.tls_input import TlsCoInput

        return TlsCoInput(config)
    if input_type == "udp":
        from .inputs.udp_input import UdpInput

        return UdpInput(config)
    if input_type == "file":
        from .inputs.file_input import FileInput

        return FileInput(config)
    raise ConfigError(f"Invalid input type: {input_type}")


def get_output(output_type: str, config: Config):
    """Output factory (mod.rs:235-243)."""
    from .outputs import DebugOutput, FileOutput, KafkaOutput, TlsOutput

    if output_type == "stdout":
        return DebugOutput(config)
    if output_type == "kafka":
        return KafkaOutput(config)
    if output_type in ("tls", "syslog-tls"):
        return TlsOutput(config)
    if output_type == "debug":
        return DebugOutput(config)
    if output_type == "file":
        return FileOutput(config)
    raise ConfigError(f"Invalid output type: {output_type}")


_TPU_FORMATS = {
    "rfc5424_tpu": "rfc5424",
    "gelf_tpu": "gelf",
    "ltsv_tpu": "ltsv",
    "rfc3164_tpu": "rfc3164",
    "jsonl_tpu": "jsonl",
    "dns_tpu": "dns",
    "auto_tpu": "auto",
}


def get_decoder(input_format: str, config: Config):
    """Decoder factory (mod.rs:413-422), extended with the *_tpu formats."""
    base = _TPU_FORMATS.get(input_format, input_format)
    if input_format == "capnp":
        return InvalidDecoder(config)
    if base == "gelf":
        return GelfDecoder(config)
    if base == "ltsv":
        return LTSVDecoder(config)
    if base == "jsonl":
        from .decoders import JSONLDecoder

        return JSONLDecoder(config)
    if base == "dns":
        from .decoders import DNSDecoder

        return DNSDecoder(config)
    if base in ("rfc5424", "auto"):
        return RFC5424Decoder(config)
    if base == "rfc3164":
        return RFC3164Decoder(config)
    raise ConfigError(f"Unknown input format: {input_format}")


def get_encoder(output_format: str, config: Config):
    """Encoder factory (mod.rs:429-437)."""
    if output_format == "capnp":
        return CapnpEncoder(config)
    if output_format in ("gelf", "json"):
        return GelfEncoder(config)
    if output_format == "ltsv":
        return LTSVEncoder(config)
    if output_format == "rfc3164":
        return RFC3164Encoder(config)
    if output_format == "rfc5424":
        return RFC5424Encoder(config)
    if output_format == "passthrough":
        return PassthroughEncoder(config)
    raise ConfigError(f"Unknown output format: {output_format}")


def get_merger(output_framing: str, config: Config):
    """Framing-name → merger (mod.rs:453-460)."""
    if output_framing in ("noop", "nop", "none", "capnp"):
        return None
    if output_framing == "line":
        return LineMerger(config)
    if output_framing == "nul":
        return NulMerger(config)
    if output_framing == "syslen":
        return SyslenMerger(config)
    raise ConfigError(f"Invalid framing type: {output_framing}")


def infer_output_framing(output_format: str, output_type: str) -> str:
    """Framing inference when output.framing is absent (mod.rs:444-452)."""
    if output_format == "capnp" or output_type == "kafka":
        return "noop"
    if output_type == "debug" or output_format == "ltsv":
        return "line"
    if output_format == "gelf":
        return "nul"
    return DEFAULT_OUTPUT_FRAMING


class Pipeline:
    """Wired-but-not-yet-running pipeline; ``run()`` blocks on the input.

    Splitting construction from running keeps the pieces testable the way
    the reference's tests poke at components with an in-memory channel
    (udp_input.rs:182-233)."""

    def __init__(self, config: Config):
        input_format = config.lookup_str(
            "input.format", "input.format must be a string", DEFAULT_INPUT_FORMAT
        )
        input_type = config.lookup_str(
            "input.type", "input.type must be a string", DEFAULT_INPUT_TYPE
        )
        self.input = get_input(input_type, config)
        self.decoder = get_decoder(input_format, config)
        output_format = config.lookup_str(
            "output.format", "output.format must be a string", DEFAULT_OUTPUT_FORMAT
        )
        self.encoder = get_encoder(output_format, config)
        output_type = config.lookup_str(
            "output.type", "output.type must be a string", DEFAULT_OUTPUT_TYPE
        )
        self.output = get_output(output_type, config)
        output_framing = config.lookup_str(
            "output.framing", "output.framing must be a string"
        )
        if output_framing is None:
            output_framing = infer_output_framing(output_format, output_type)
        self.merger = get_merger(output_framing, config)
        queue_size = config.lookup_int(
            "input.queuesize", "input.queuesize must be a size integer", DEFAULT_QUEUE_SIZE
        )
        queue_policy = config.lookup_str(
            "input.queue_policy",
            'input.queue_policy must be "block", "drop_newest" or "drop_oldest"',
            "block")
        from .utils.bounded_queue import POLICIES, PolicyQueue

        if queue_policy not in POLICIES:
            raise ConfigError(
                'input.queue_policy must be "block", "drop_newest" or '
                '"drop_oldest"')
        # multi-tenant serving: a configured [tenants] table (or a
        # tenant.default_* rate) builds the tenant registry, swaps the
        # single bounded queue for the weighted-fair multi-queue, and
        # makes handler_factory wrap every connection in token-bucket
        # admission.  Unconfigured -> None, and the pipeline builds the
        # exact pre-tenancy objects below (zero added overhead)
        from .tenancy.registry import TenantRegistry

        self.tenants = TenantRegistry.from_config(
            config, fallback_policy=queue_policy)
        if self.tenants is not None:
            from .tenancy.fairqueue import WeightedFairQueue

            self.tx: "queue.Queue[Optional[bytes]]" = WeightedFairQueue(
                maxsize=queue_size, registry=self.tenants)
        else:
            self.tx = PolicyQueue(maxsize=queue_size, policy=queue_policy)
        # zero-loss ingestion ([durability]): the WAL spill tier arms
        # only on the *_tpu formats — the spill record is the packed-
        # region shape (chunk + span vectors) only the batch handler
        # produces.  A scalar pipeline asking for it gets a warning,
        # not silent false durability.
        from .durability.manager import DurabilityManager

        self.durability = None
        if input_format in _TPU_FORMATS:
            self.durability = DurabilityManager.from_config(config)
            if self.durability is not None:
                self.durability.attach_queue(self.tx)
        else:
            _dmode = config.lookup_str(
                "durability.mode",
                'durability.mode must be "off", "spill" or "require"',
                "off")
            if _dmode != "off":
                import sys

                _dmsg = (f'durability.mode = "{_dmode}" requires a '
                         f"*_tpu input format (got '{input_format}')")
                if _dmode == "require":
                    # "require" promised no silent loss: refusing to
                    # start beats booting a lossy pipeline quietly
                    raise ConfigError(_dmsg)
                print(f"{_dmsg}; the spill tier is disabled",
                      file=sys.stderr)
        self.input_format = input_format
        self.config = config
        # template mining for scalar pipelines (the batch handler owns
        # its own miner set; building both would double-count)
        self._scalar_miners = None
        if input_format not in _TPU_FORMATS:
            from .tenancy.templates import TemplateMinerSet

            self._scalar_miners = TemplateMinerSet.from_config(config)
        self._handlers: list = []
        import threading

        self._handler_lock = threading.Lock()
        from .supervise import Supervisor
        from .utils import faultinject as _faultinject
        from .utils import metrics as _metrics_mod

        _metrics_mod.configure_from(config)
        _faultinject.configure_from(config)
        self.supervisor = Supervisor(config)
        # fleet federation (input.tpu_fleet = true): membership +
        # health export + drain-on-departure for multi-host lane
        # scale-out.  Construction is cheap and socket-free; run()
        # starts the listener/ticker.  Unconfigured -> None, zero
        # added overhead (fleet/federation.py)
        from .fleet import Fleet

        self.fleet = Fleet.from_config(
            config, supervisor=self.supervisor,
            on_drain=self._fleet_drain_signal)
        # standalone observability listener ([metrics] prom_port):
        # fleet-off deployments scrape GET /metrics (and /trace, POST
        # /profile) without joining a fleet — with fleet on, the fleet
        # health server carries the same legs and this stays None.
        # Started in run() beside the fleet agent, stopped at drain.
        self._obs_server = None
        # feedback control ([control]): burn-driven admission, share
        # feedback, autoscale signal.  Unconfigured -> None — zero
        # threads, zero hot-path cost (control/plane.py).  Started in
        # run() after the fleet (the proxy routes off the live
        # roster); stopped at drain frozen-at-last-applied.
        from .control import ControlPlane

        self.control = ControlPlane.from_config(
            config, tenants=self.tenants, fleet=self.fleet,
            tx=self.tx, durability=self.durability)
        if self.control is not None and self.fleet is not None:
            self.fleet.set_control_source(self.control.fleetz_section)
        if input_format in _TPU_FORMATS:
            # multi-host: join the JAX process group before any device
            # op so the decode mesh's dp axis can span every host's
            # chips (no-op without the tpu_coordinator keys)
            from .parallel.distributed import init_distributed

            init_distributed(config)
            # zero-JIT boot: load the AOT artifact store first
            # (input.tpu_aot_dir; no key = no-op) — when it carries a
            # warmed xla-cache and no explicit cache dir is configured,
            # it points JAX's persistent cache inside the artifact dir
            from .tpu.aot import setup_aot

            setup_aot(config)
            # persistent XLA compile cache (input.tpu_compile_cache_dir)
            # must be wired before the first kernel dispatch so every
            # compile this process pays — including the handler's
            # startup prewarm — lands in it (no key = no-op)
            from .tpu.device_common import setup_compile_cache

            setup_compile_cache(config)
            if self.fleet is not None:
                # advertised fleet capacity defaults to the resolved
                # lane count: a 4-chip host should absorb 4x a 1-chip
                # host's traffic share unless input.tpu_fleet_capacity
                # pins something else (fleet/membership.py shares())
                from .tpu.overlap import resolve_lanes

                lanes, _ = resolve_lanes(config)
                self.fleet.set_default_capacity(float(lanes))

    def handler_factory(self, peer=None):
        """Per-connection handler.  ``peer`` is the transport's source
        identity (peer IP for tcp/tls, the path for file inputs, None
        for peerless transports) — with tenancy configured it selects
        the tenant whose admission buckets the connection charges."""
        handler = self._base_handler()
        if self.tenants is not None:
            from .tenancy.admission import AdmissionHandler

            return AdmissionHandler(handler, self.tenants.resolve(peer))
        return handler

    def _base_handler(self):
        if self.input_format in _TPU_FORMATS:
            # ONE batch handler shared by every connection thread: the
            # reference's per-connection decode state is per-line and
            # stateless, but batches fragment per connection — sharing
            # aggregates all connections into full batches (the handler
            # is internally locked; message interleaving across
            # connections is unspecified in the reference too, mod.rs
            # queue semantics).  Per-connection framing attributes are
            # identical for every connection of one input by
            # construction (single input.framing config).
            with self._handler_lock:
                if self._handlers:
                    return self._handlers[0]
                from .tpu.batch import BatchHandler

                # the handler's in-flight fetcher thread spawns through
                # the supervisor: a crashed fetcher restarts (with
                # backoff + metrics) instead of wedging the window
                handler = BatchHandler(
                    self.tx, self.decoder, self.encoder, self.config,
                    fmt=_TPU_FORMATS[self.input_format], merger=self.merger,
                    supervisor=self.supervisor,
                )
                handler.durability = self.durability
                self._handlers.append(handler)
                return handler
        # ScalarHandlers are stateless (no buffered batch, flush is a
        # no-op) so they are NOT tracked for drain — tracking every
        # per-connection (and, for UDP tenancy, per-source) handler
        # would grow _handlers unboundedly in a long-lived process
        handler = ScalarHandler(self.tx, self.decoder, self.encoder)
        handler.record_hook = self._scalar_record_hook()
        return handler

    def _scalar_record_hook(self):
        """Template mining/enrichment for scalar (non-*_tpu) pipelines:
        the batch handler wires its own miners (tpu/batch.py); without
        this, ``tenant.templates = "on"`` on a scalar pipeline would
        silently mine nothing."""
        if self._scalar_miners is None:
            return None
        from .encoders import GelfEncoder
        from .tenancy.templates import make_gelf_enricher

        if self._scalar_miners.enrich and type(self.encoder) is GelfEncoder:
            return make_gelf_enricher(self._scalar_miners)
        from .tenancy import current_or_default

        miners = self._scalar_miners

        def mine(record, tenant=None):
            miners.observe_msg(tenant or current_or_default(),
                               record.msg or "")

        return mine

    def start_output(self):
        # sinks spawn their consumer threads through the supervisor so a
        # crashed worker restarts (with backoff + metrics) instead of
        # silently wedging the bounded queue
        self.output.supervisor = self.supervisor
        return self.output.start(self.tx, self.merger)

    def _drain(self, threads):
        """Flush pending batches and drain the queue through the sinks —
        the reference loses in-flight queue contents on shutdown
        (SURVEY.md §5 checkpoint/resume); we flush instead.  For batch
        handlers ``flush()`` also fences **every** dispatch lane of the
        in-flight submit/fetch executor (tpu/overlap.py LaneSet), so
        every batch any lane still holds reaches the queue — in batch
        order — before SHUTDOWN is enqueued."""
        # drain-on-departure, phase 1: stop being routable and announce
        # `draining` to fleet peers BEFORE the flush, so a load
        # balancer stops sending new traffic while in-flight batches
        # emit byte-identically through the fence-all path below
        if self.fleet is not None:
            self.fleet.enter_draining()
        # from here on, queue sheds also count queue_shed_during_drain:
        # a drain test can tell shed lines from delivered lines
        mark = getattr(self.tx, "mark_draining", None)
        if mark is not None:
            mark()
        # bounded-wait for in-flight connection handler threads (tcp/tls
        # thread-per-connection inputs) so their last lines land before
        # the flush/queue barrier below; stragglers stay daemonized and
        # are counted, same contract as the output-thread stragglers
        join_handlers = getattr(self.input, "join_handlers", None)
        if join_handlers is not None:
            still_alive = join_handlers(timeout=2.0)
            if still_alive:
                from .utils.metrics import registry as _metrics

                _metrics.inc("drain_stragglers", still_alive)
        for handler in self._handlers:
            try:
                handler.flush()
                close = getattr(handler, "close", None)
                if close is not None:
                    close()
            except Exception:  # noqa: BLE001 - best-effort during shutdown
                # the batch is lost either way, but losing it silently
                # would make a truncated output file look like an input
                # problem: say so and count it
                import sys
                import traceback

                from .utils.metrics import registry as _metrics

                _metrics.inc("drain_flush_errors")
                print("drain: final flush failed, batch lost:",
                      file=sys.stderr)
                traceback.print_exc()
        if self.durability is not None:
            # replay-on-drain: spilled batches re-enter through the
            # (already flushed and fenced) handlers so nothing rides
            # out the process on disk unnecessarily.  The replay
            # happens BEFORE the queue drain barrier below, so
            # replayed blocks and the live tail both clear the sinks
            # before any SHUTDOWN is enqueued — replay can never
            # interleave with sink teardown.
            for handler in self._handlers:
                replay = getattr(handler, "replay_spilled", None)
                if replay is None:
                    continue
                try:
                    replay()
                except Exception:  # noqa: BLE001 - best-effort during shutdown
                    import sys
                    import traceback

                    from .utils.metrics import registry as _metrics

                    _metrics.inc("drain_flush_errors")
                    print("drain: spill replay failed; the WAL keeps "
                          "the records for the next boot:",
                          file=sys.stderr)
                    traceback.print_exc()
        # drain barrier: every enqueued item must be consumed AND
        # task_done'd by a sink before SHUTDOWN goes in.  The WFQ
        # already delivers its control lane last, but the barrier makes
        # the ordering explicit for every queue type — and sink acks
        # fire before task_done, so replay cursors are settled here too
        self._await_queue_drain()
        if self.durability is not None:
            self.durability.stop()
        from .outputs import SHUTDOWN

        for _ in threads:
            self.tx.put(SHUTDOWN)
        for t in threads:
            t.join(timeout=30)
        import sys

        from .utils import metrics as _metrics_mod

        stragglers = [t for t in threads if t.is_alive()]
        if stragglers:
            # a sink that ignored SHUTDOWN for 30s is abandoned, not
            # silently forgotten: name it and count it
            _metrics_mod.registry.inc("drain_stragglers", len(stragglers))
            names = ", ".join(t.name for t in stragglers)
            print(f"drain: {len(stragglers)} output thread(s) still alive "
                  f"after 30s, abandoning: [{names}]", file=sys.stderr)
        _metrics_mod.registry.final_flush()
        _metrics_mod.stop_jax_profiler()
        # the control plane stops frozen-at-last-applied: tightened
        # tenant rates and a decayed capacity weight stay exactly
        # where the last tick put them (never reset-to-open), the
        # ticker and steering proxy just stop
        if self.control is not None:
            self.control.stop()
        # the SLO engine's evaluator (and the sentinel riding its
        # ticker) stops with the pipeline — a drained process must not
        # keep journaling slo_burn events off a frozen traffic rate
        from .obs import slo as _slo

        _slo.engine.stop()
        # drain-on-departure, phase 2: every queued batch reached the
        # sinks — announce `departed` and stop the fleet threads
        if self.fleet is not None:
            self.fleet.shutdown()
        if self._obs_server is not None:
            self._obs_server.stop()
            self._obs_server = None

    def _await_queue_drain(self, deadline_s: float = 30.0) -> None:
        """Block until the sinks have consumed and ``task_done``'d every
        enqueued item (outputs ack before task_done, so durability
        replay cursors are settled when this returns).  A sink that
        cannot drain within ``deadline_s`` is surfaced, not waited on
        forever — counted in ``drain_barrier_timeouts``."""
        import sys
        import time

        if getattr(self.tx, "unfinished_tasks", None) is None:
            return
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            if self.tx.unfinished_tasks == 0:
                return
            time.sleep(0.01)
        from .utils.metrics import registry as _metrics

        _metrics.inc("drain_barrier_timeouts")
        print(f"drain: queue barrier timed out after {deadline_s:.0f}s "
              f"({self.tx.unfinished_tasks} item(s) still in flight)",
              file=sys.stderr)

    def _install_signal_handlers(self, threads):
        import os
        import signal
        import threading as _threading

        if _threading.current_thread() is not _threading.main_thread():
            return

        def handle(signum, frame):
            print(f"Received signal {signum}, draining and exiting",
                  file=__import__("sys").stderr)
            self._drain(threads)
            os._exit(0)

        signal.signal(signal.SIGTERM, handle)
        signal.signal(signal.SIGINT, handle)

        def profile_toggle(signum, frame):
            # on-demand xprof capture for soak runs: SIGUSR2 starts a
            # trace into metrics.jax_profile_dir (or a per-pid default)
            # and a second SIGUSR2 stops it — no restart, no config
            # edit (the health server's POST /profile is the same flip)
            from .utils import metrics as _m

            _m.toggle_jax_profiler()

        if hasattr(signal, "SIGUSR2"):
            signal.signal(signal.SIGUSR2, profile_toggle)

    def _fleet_drain_signal(self):
        """`POST /drain` on the health endpoint (fleetctl drain): route
        through the SIGTERM path so a remote drain and a local one are
        the same code — fence lanes, flush, drain the queue, exit."""
        import os
        import signal

        os.kill(os.getpid(), signal.SIGTERM)

    def run(self):
        threads = self.start_output()
        if not isinstance(threads, list):
            threads = [threads]
        self._install_signal_handlers(threads)
        # fleet membership goes live only once the pipeline can serve:
        # sinks are up, signal handlers (the drain path peers rely on)
        # are installed
        if self.fleet is not None:
            self.fleet.start()
        else:
            from .obs import prom as _prom

            self._obs_server = _prom.maybe_start_from(
                self.config, supervisor=self.supervisor)
        if self.control is not None:
            # after fleet.start(): the controller's steering proxy and
            # share loop read the live membership roster
            self.control.start()
        if self.durability is not None and self.durability.backlog():
            # crash recovery: a previous life left unacked records in
            # the WAL — replay them through the sinks BEFORE fresh
            # ingest is admitted, so restart ordering is replay-then-
            # live and the at-least-once window closes at boot
            import sys

            handler = self._base_handler()
            replayed = handler.replay_spilled()
            if replayed:
                print(f"durability: replayed {replayed} spilled line(s) "
                      f"from {self.durability.dir}", file=sys.stderr)
        # the accept loop runs supervised: a crash in the transport
        # restarts it (bounded by [supervisor] config) instead of
        # killing the daemon while consumers still hold the queue
        self.supervisor.run(self.input.accept, "input-accept",
                            (self.handler_factory,))
        # Input ended (EOF on stdin, etc.): drain before exiting rather
        # than killing the daemon consumers mid-write.
        self._drain(threads)
        if self.input_format in _TPU_FORMATS:
            # a compile the watchdog declined may still be running on
            # its worker thread: wait for it (bounded) so the process
            # never exits with a thread inside XLA.  The signal path
            # needs no such wait — it leaves through os._exit.
            from .tpu.device_common import join_compile_workers

            alive = join_compile_workers()
            if alive:
                import sys

                print(f"drain: {alive} kernel compile(s) still running "
                      "at exit", file=sys.stderr)


def start(config_file: str):
    """Library entry point (lib.rs:18-20, mod.rs:395-472): blocks forever."""
    try:
        config = Config.from_path(config_file)
    except OSError as e:
        raise ConfigError(f"Unable to read the config file [{config_file}]: {e}")
    Pipeline(config).run()
