"""Overlap executor: in-flight window ordering, backpressure, fencing,
exception ferrying, route economics, and thread-sliced pack.

The handler-level tests ride the rfc5424 block route with host-side
encoders (passthrough/LTSV: no device-encode kernel compiles), so they
run fast on any backend while still exercising the real submit-ahead /
fetch-behind machinery.
"""

import queue
import threading
import time

import numpy as np
import pytest

from flowgger_tpu.config import Config, ConfigError
from flowgger_tpu.tpu.overlap import InflightWindow, RouteEconomics
from flowgger_tpu.utils import faultinject
from flowgger_tpu.utils.metrics import registry


@pytest.fixture(autouse=True)
def _clean():
    registry.reset()
    faultinject.reset()
    yield
    faultinject.reset()


# ---------------------------------------------------------------------------
# InflightWindow
# ---------------------------------------------------------------------------

def test_window_preserves_fifo_order_under_variable_pop_latency():
    done = []

    def pop(item):
        time.sleep(0.002 if item % 3 == 0 else 0.0)
        done.append(item)

    w = InflightWindow(2, pop)
    for i in range(24):
        w.submit(i)
    w.fence()
    assert done == list(range(24))
    w.close()


def test_window_backpressure_blocks_and_counts_stall():
    gate = threading.Event()
    done = []

    def pop(item):
        gate.wait(5.0)
        done.append(item)

    w = InflightWindow(2, pop)
    w.submit(1)
    w.submit(2)  # window full: 1 popping + 1 queued
    t = threading.Thread(target=lambda: w.submit(3))
    t.start()
    time.sleep(0.05)
    assert t.is_alive()  # blocked on the full window
    gate.set()
    t.join(timeout=5)
    assert not t.is_alive()
    w.fence()
    assert done == [1, 2, 3]
    assert registry.snapshot().get("overlap_stall_seconds", 0) > 0
    w.close()


def test_window_fence_waits_for_inflight_pop():
    slow = threading.Event()
    done = []

    def pop(item):
        slow.wait(2.0)
        done.append(item)

    w = InflightWindow(4, pop)
    w.submit("a")
    threading.Timer(0.05, slow.set).start()
    w.fence()  # must block until the pop lands
    assert done == ["a"]
    w.close()


def test_window_ferries_pop_exception_to_fence():
    def pop(item):
        if item == "boom":
            raise RuntimeError("device died")

    w = InflightWindow(2, pop)
    w.submit("ok")
    w.submit("boom")
    with pytest.raises(RuntimeError, match="device died"):
        w.fence()
    w.fence()  # exception consumed; window stays usable
    w.submit("ok2")
    w.fence()
    w.close()


def test_window_depth_zero_is_inline_serial():
    done = []
    w = InflightWindow(0, done.append)
    w.submit(1)
    assert done == [1]  # popped on the calling thread, immediately
    w.fence()
    w.close()


def test_window_depth_gauge_returns_to_zero():
    w = InflightWindow(2, lambda item: time.sleep(0.001))
    for i in range(8):
        w.submit(i)
    w.fence()
    assert registry.get_gauge("inflight_depth") == 0
    w.close()


# ---------------------------------------------------------------------------
# RouteEconomics
# ---------------------------------------------------------------------------

def test_economics_probes_device_then_host_then_picks_winner():
    e = RouteEconomics(probe_every=10)
    assert e.allow_device()          # no samples: device probe first
    e.observe("device", 1000, 1.0)   # 1ms/row
    assert not e.allow_device()      # host comparison sample next
    e.observe("host", 1000, 0.1)     # 0.1ms/row: host wins by 10x
    picks = [e.allow_device() for _ in range(20)]
    assert picks.count(True) == 2    # only the scheduled re-probes
    assert registry.get("encode_route_device") == 1
    assert registry.get("encode_route_host") == 1


def test_economics_prefers_device_when_it_wins():
    e = RouteEconomics(probe_every=10)
    e.observe("device", 1000, 0.01)
    e.observe("host", 1000, 1.0)
    picks = [e.allow_device() for _ in range(20)]
    # device keeps the traffic except the scheduled host re-samples
    assert picks.count(False) == 2


def test_economics_healthy_device_never_pays_host_probe():
    """A device tier measuring at accelerator speed keeps all traffic:
    the one-batch host comparison only happens when the device is
    measurably slow (the CPU backend)."""
    e = RouteEconomics(probe_every=10)
    assert e.allow_device()
    e.observe("device", 1_000_000, 1.0)  # 1us/row: accelerator-fast
    assert all(e.allow_device() for _ in range(20))


def test_economics_disabled_always_allows_device():
    e = RouteEconomics(enabled=False)
    e.observe("device", 10, 100.0)
    e.observe("host", 10, 0.001)
    assert all(e.allow_device() for _ in range(8))


def test_economics_from_config():
    e = RouteEconomics.from_config(Config.from_string(
        "[input]\ntpu_encode_economics = false\n"
        "tpu_encode_probe_every = 7\n"))
    assert e.enabled is False and e.probe_every == 7


def test_config_validation():
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.encoders.passthrough import PassthroughEncoder
    from flowgger_tpu.mergers import LineMerger
    from flowgger_tpu.tpu.batch import BatchHandler

    for bad in ("tpu_inflight = -1\n", "pack_threads = 0\n"):
        cfg = Config.from_string("[input]\n" + bad)
        with pytest.raises(ConfigError):
            BatchHandler(queue.Queue(), RFC5424Decoder(cfg),
                         PassthroughEncoder(cfg), cfg, fmt="rfc5424",
                         start_timer=False, merger=LineMerger())


# ---------------------------------------------------------------------------
# BatchHandler through the window: ordering + byte identity
# ---------------------------------------------------------------------------

LINES = [
    b"<23>1 2015-08-05T15:53:45.637824Z host-a app 69 42 - the quick brown fox",
    b"<165>1 2003-10-11T22:14:15.003Z mymachine evntslog - ID47 "
    b'[exampleSDID@32473 iut="3" eventSource="App"] BOMAn application event',
    b"not a valid syslog line at all",
    b"<13>1 2024-01-01T00:00:00Z h app p m - plain message",
    b"<13>1 2024-06-01T00:00:00.5Z h2 app2 p m - second message",
]


def _stream_handler(inflight, fault_spec=None, breaker_cfg="", repeats=12):
    """Feed repeats x LINES through the rfc5424 block route (passthrough
    encoder: host block encode after the device decode) with the given
    window depth; returns the drained sink bytes in queue order."""
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.encoders.passthrough import PassthroughEncoder
    from flowgger_tpu.mergers import LineMerger
    from flowgger_tpu.outputs import stream_bytes
    from flowgger_tpu.tpu.batch import BatchHandler

    faultinject.reset()
    if fault_spec:
        faultinject.configure({"device_decode": fault_spec})
    cfg = Config.from_string(
        "[input]\ntpu_batch_size = 5\n"
        f"tpu_inflight = {inflight}\n" + breaker_cfg)
    tx = queue.Queue()
    merger = LineMerger()
    handler = BatchHandler(tx, RFC5424Decoder(cfg), PassthroughEncoder(cfg),
                           cfg, fmt="rfc5424", start_timer=False,
                           merger=merger)
    for _ in range(repeats):  # one device batch per cycle
        handler.ingest_chunk(b"".join(ln + b"\n" for ln in LINES))
    handler.flush()
    out = b""
    while not tx.empty():
        data, _ = stream_bytes(tx.get_nowait(), merger)
        out += data
    return out, handler


def test_windowed_stream_matches_serial_and_scalar_order():
    """The overlap path (window 2) must emit byte-identical output, in
    the same order, as the strictly serial path (window 0)."""
    serial, _ = _stream_handler(inflight=0)
    windowed, handler = _stream_handler(inflight=2)
    assert windowed == serial and serial.count(b"\n") >= 48
    assert handler._window.pending() == 0


@pytest.mark.faults
def test_device_fault_mid_window_keeps_order_and_bytes():
    """ISSUE acceptance: a device killed mid-window (faults at both
    dispatch and fetch sites) must leave the merger output byte-
    identical to the fault-free run — failed batches re-decode through
    the scalar oracle at their window position."""
    clean, _ = _stream_handler(inflight=2)
    registry.reset()
    faulty, handler = _stream_handler(
        inflight=2, fault_spec="every:3",
        breaker_cfg="tpu_breaker_failures = 3\n"
                    "tpu_breaker_cooldown_ms = 1\n")
    assert faulty == clean
    assert registry.get("device_decode_errors") >= 2


@pytest.mark.faults
def test_breaker_trip_drains_window_before_scalar_batches():
    """When the breaker opens, later batches take the ingest-side scalar
    path — which must fence the window first so a still-in-flight device
    batch cannot be overtaken."""
    from flowgger_tpu.tpu.breaker import OPEN

    clean, _ = _stream_handler(inflight=2)
    registry.reset()
    faulty, handler = _stream_handler(
        inflight=2, fault_spec="first:6",
        breaker_cfg="tpu_breaker_failures = 2\n"
                    "tpu_breaker_cooldown_ms = 3600000\n")
    assert faulty == clean
    assert handler._breaker.state == OPEN
    assert registry.get("breaker_trips") == 1


def test_windowed_stream_overlap_metrics_present():
    _stream_handler(inflight=2)
    snap = registry.snapshot()
    assert snap.get("dispatch_seconds", 0) > 0
    assert snap.get("fetch_seconds", 0) > 0
    assert "inflight_depth" in snap


# ---------------------------------------------------------------------------
# thread-sliced pack
# ---------------------------------------------------------------------------

def test_pack_threads_slicing_matches_single_thread(monkeypatch):
    from flowgger_tpu import native
    from flowgger_tpu.tpu import pack

    # force the numpy fallback so the Python-side slicing is what runs
    monkeypatch.setattr(native, "pack_chunk_native",
                        lambda *a, **k: None)
    lines = [f"line number {i} with some payload {i * 7}".encode()
             for i in range(1000)]
    region = b"".join(ln + b"\n" for ln in lines)
    try:
        pack.configure_pack_threads(1)
        b1, l1, *_ = pack.pack_region_2d(region, 64)
        pack.configure_pack_threads(4)
        b4, l4, *_ = pack.pack_region_2d(region, 64)
    finally:
        pack.configure_pack_threads(1)
    assert np.array_equal(b1, b4) and np.array_equal(l1, l4)
