"""Flight recorder (flowgger_tpu/obs/): span tracing, the degradation
event journal, and Prometheus exposition.

Covers the PR's acceptance bars: every degradation rung emits exactly
one typed event per occurrence; GET /metrics parses under a strict
pure-python exposition-format parser (TYPE lines, label escaping,
monotonic counter suffixes); the trace ring dumps Chrome trace JSON
with the required ph/ts/dur/pid/tid keys per span; the metrics
reporter/final_flush write race is gone; and SIGUSR2/POST /profile
toggle the XLA profiler without a restart."""

import json
import os
import queue
import re
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from flowgger_tpu.config import Config
from flowgger_tpu.obs import events as obs_events
from flowgger_tpu.obs import prom as obs_prom
from flowgger_tpu.obs import trace as obs_trace
from flowgger_tpu.utils import faultinject
from flowgger_tpu.utils.metrics import Registry, registry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TRACE_DUMP = os.path.join(_REPO, "tools", "trace_dump.py")


@pytest.fixture(autouse=True)
def _clean():
    registry.reset()
    obs_events.journal.reset()
    obs_events.journal.configure()
    obs_trace.tracer.configure("off")
    faultinject.reset()
    yield
    obs_trace.tracer.configure("off")
    obs_events.journal.reset()
    obs_events.journal.configure()
    faultinject.reset()
    registry.reset()


# ---------------------------------------------------------------------------
# strict exposition-format parser (the GET /metrics contract)
# ---------------------------------------------------------------------------

_METRIC_NAME = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*\Z")
_LABEL_NAME = re.compile(r"[a-zA-Z_][a-zA-Z0-9_]*\Z")
_TYPES = ("counter", "gauge", "summary", "histogram", "untyped")


def _parse_labels(raw, problems, where):
    """Validate one ``{k="v",...}`` block char-by-char (escape rules:
    \\\\, \\", \\n only)."""
    i, labels = 0, {}
    while i < len(raw):
        j = raw.index("=", i)
        name = raw[i:j]
        if not _LABEL_NAME.match(name):
            problems.append(f"{where}: bad label name {name!r}")
            return labels
        if raw[j + 1] != '"':
            problems.append(f"{where}: label value not quoted")
            return labels
        i, val, closed = j + 2, [], False
        while i < len(raw):
            c = raw[i]
            if c == "\\":
                if i + 1 >= len(raw) or raw[i + 1] not in ('\\', '"', "n"):
                    problems.append(f"{where}: bad escape in label value")
                    return labels
                val.append(raw[i:i + 2])
                i += 2
                continue
            if c == '"':
                closed = True
                i += 1
                break
            if c == "\n":
                problems.append(f"{where}: raw newline in label value")
                return labels
            val.append(c)
            i += 1
        if not closed:
            problems.append(f"{where}: unterminated label value")
            return labels
        labels[name] = "".join(val)
        if i < len(raw):
            if raw[i] != ",":
                problems.append(f"{where}: expected ',' between labels")
                return labels
            i += 1
    return labels


def parse_exposition(text):
    """Strict parse; returns (samples, types, problems).  ``samples``
    maps sample name -> [(labels, value)], ``types`` metric name ->
    declared type."""
    problems, samples, types = [], {}, {}
    if not text.endswith("\n"):
        problems.append("document must end with a newline")
    for lineno, line in enumerate(text.splitlines(), 1):
        where = f"line {lineno}"
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4 or parts[3] not in _TYPES:
                problems.append(f"{where}: malformed TYPE line")
                continue
            if parts[2] in types:
                problems.append(f"{where}: duplicate TYPE for {parts[2]}")
            if not _METRIC_NAME.match(parts[2]):
                problems.append(f"{where}: bad metric name {parts[2]!r}")
            types[parts[2]] = parts[3]
            continue
        if line.startswith("#"):
            continue  # HELP / comments
        m = re.match(r"([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})? (\S+)\Z",
                     line)
        if not m:
            problems.append(f"{where}: malformed sample {line!r}")
            continue
        name, _, rawlabels, rawval = m.groups()
        labels = _parse_labels(rawlabels, problems, where) \
            if rawlabels else {}
        if rawval not in ("+Inf", "-Inf", "NaN"):
            try:
                float(rawval)
            except ValueError:
                problems.append(f"{where}: unparseable value {rawval!r}")
                continue
        base = name
        for suffix in ("_sum", "_count", "_bucket"):
            if name.endswith(suffix) and name[: -len(suffix)] in types:
                base = name[: -len(suffix)]
        if base not in types:
            problems.append(f"{where}: sample {name!r} has no TYPE line")
        else:
            t = types[base]
            if t == "counter":
                if not name.endswith("_total"):
                    problems.append(
                        f"{where}: counter {name!r} lacks _total suffix")
                if rawval not in ("+Inf", "NaN") and float(rawval) < 0:
                    problems.append(f"{where}: negative counter {name!r}")
        samples.setdefault(name, []).append((labels, rawval))
    return samples, types, problems


def _populated_registry():
    reg = Registry()
    reg.inc("input_lines", 123)
    reg.inc("queue_dropped", 4)
    reg.add_seconds("dispatch_seconds", 1.5)
    reg.set_gauge("inflight_depth", 2)
    reg.set_gauge("device_breaker_state", 1)
    for v in (0.01, 0.02, 0.5):
        reg.batch_seconds.observe(v)
        reg.observe("e2e_batch_seconds", v * 2)
        reg.observe("queue_wait_seconds", v / 2)
    return reg


def test_exposition_parses_strictly():
    obs_events.emit("queue", "queue_drop", detail="drop_newest", cost=1,
                    cost_unit="items")
    obs_events.emit("breaker", "breaker_trip", detail="errors")
    text = obs_prom.render(_populated_registry(), obs_events.journal)
    samples, types, problems = parse_exposition(text)
    assert problems == [], "\n".join(problems)
    # counters carry the monotonic suffix and their TYPE
    assert types["flowgger_input_lines_total"] == "counter"
    assert samples["flowgger_input_lines_total"][0][1] == "123"
    # cumulative stage seconds render as counters too
    assert types["flowgger_dispatch_seconds_total"] == "counter"
    # gauges
    assert types["flowgger_inflight_depth"] == "gauge"
    # histogram families render as summaries with quantiles + sum/count
    assert types["flowgger_batch_seconds"] == "summary"
    q = {lab["quantile"] for lab, _ in
         samples["flowgger_batch_seconds"]}
    assert q == {"0.5", "0.99"}
    assert samples["flowgger_batch_seconds_count"][0][1] == "3"
    assert "flowgger_e2e_batch_seconds_sum" in samples
    assert "flowgger_queue_wait_seconds_count" in samples
    # the journal's labeled mirror
    by_reason = samples["flowgger_degradation_events_by_reason_total"]
    assert {lab["reason"] for lab, _ in by_reason} == \
        {"queue_drop", "breaker_trip"}


def test_label_escaping_round_trips():
    nasty = 'a"b\\c\nd'
    line = obs_prom.render_labeled("flowgger_x", {"k": nasty}, 1)
    samples, types, problems = parse_exposition(
        "# TYPE flowgger_x gauge\n" + line + "\n")
    assert problems == []
    (labels, _val), = samples["flowgger_x"]
    unescaped = (labels["k"].replace("\\n", "\n").replace('\\"', '"')
                 .replace("\\\\", "\\"))
    assert unescaped == nasty


def test_metric_name_sanitization():
    assert obs_prom.metric_name("lane0_route_device_spr") == \
        "flowgger_lane0_route_device_spr"
    assert _METRIC_NAME.match(obs_prom.metric_name("weird-name.x"))


# ---------------------------------------------------------------------------
# degradation event journal: one typed event per rung occurrence
# ---------------------------------------------------------------------------

def _events_of(reason):
    return [e for e in obs_events.journal.snapshot()
            if e["reason"] == reason]


def test_emit_rejects_unknown_reason():
    with pytest.raises(ValueError):
        obs_events.emit("x", "not_a_reason")


def test_event_counters_mirror():
    obs_events.emit("queue", "queue_drop", detail="drop_newest")
    obs_events.emit("queue", "queue_drop", detail="drop_oldest")
    assert registry.get("degradation_events") == 2
    assert registry.get("events_queue_drop") == 2
    assert obs_events.journal.counts() == {"queue_drop": 2}


def test_event_ring_is_bounded():
    obs_events.journal.configure(ring=8)
    for i in range(50):
        obs_events.emit("queue", "queue_drop", detail=str(i))
    snap = obs_events.journal.snapshot()
    assert len(snap) == 8 and snap[-1]["detail"] == "49"
    assert obs_events.journal.total() == 50


def test_event_jsonl_sink(tmp_path):
    path = tmp_path / "events.jsonl"
    obs_events.journal.configure(path=str(path))
    obs_events.emit("admission", "tenant_shed", tenant="acme", cost=7,
                    cost_unit="lines")
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 1
    ev = json.loads(lines[0])
    assert ev["reason"] == "tenant_shed" and ev["tenant"] == "acme"
    assert ev["cost"] == 7 and ev["cost_unit"] == "lines"


def test_sink_write_failure_disables_never_raises(tmp_path):
    from flowgger_tpu.obs.sink import JsonlSink

    s = JsonlSink("test")
    path = tmp_path / "s.jsonl"
    s.open(str(path))
    s._fd.close()  # the volume dies under the handle
    s.write({"a": 1})  # must disable, not raise into the caller
    assert not s.active
    s.write({"a": 2})  # and stay quiet afterwards


def test_journal_survives_dead_sink(tmp_path):
    path = tmp_path / "ev.jsonl"
    obs_events.journal.configure(path=str(path))
    obs_events.journal._sink._fd.close()
    # a degradation site emitting into a dead sink must still record
    # in-memory and never see the I/O failure
    obs_events.emit("queue", "queue_drop", detail="drop_newest")
    assert obs_events.journal.counts() == {"queue_drop": 1}


def test_fair_queue_emits_events_outside_mutex():
    from flowgger_tpu.tenancy.fairqueue import WeightedFairQueue
    from flowgger_tpu.tenancy.registry import TenantRegistry
    from flowgger_tpu.tenancy import set_current

    emitted_under_mutex = []
    orig_emit = obs_events.journal.emit

    reg = TenantRegistry.from_config(Config.from_string(
        '[tenants.noisy]\npeers = ["10.0.0.1"]\n'
        'queue_policy = "drop_oldest"\n'))
    q = WeightedFairQueue(maxsize=1, registry=reg)

    def spy(*a, **kw):
        emitted_under_mutex.append(q.mutex.locked())
        return orig_emit(*a, **kw)

    obs_events.journal.emit = spy
    set_current("noisy")
    try:
        q.put(b"one")
        q.put(b"two")  # sheds the lane head
    finally:
        set_current(None)
        obs_events.journal.emit = orig_emit
    assert emitted_under_mutex == [False]  # staged, drained after release
    (ev,) = _events_of("queue_drop")
    assert ev["tenant"] == "noisy"


def test_decode_batch_device_error_closes_trace():
    from flowgger_tpu.decoders import RFC5424Decoder
    from flowgger_tpu.encoders import GelfEncoder
    from flowgger_tpu.mergers import NulMerger
    from flowgger_tpu.tpu.batch import BatchHandler

    obs_trace.tracer.configure("ring")
    faultinject.configure_from(Config.from_string(
        '[faults]\ndevice_decode = "every:1"\n'))
    cfg = Config.from_string("")
    tx = queue.Queue()
    h = BatchHandler(tx, RFC5424Decoder(), GelfEncoder(cfg), cfg,
                     start_timer=False, merger=NulMerger(cfg))
    # handle_bytes path -> _decode_batch: the injected device error
    # must not leak an open trace entry
    h.handle_bytes(b"<13>1 2015-08-05T15:53:45Z h a p m - ok")
    h.flush()
    h.close()
    assert obs_trace.tracer.stats()["open"] == 0
    assert not tx.empty()  # degradation boundary held


test_decode_batch_device_error_closes_trace = pytest.mark.faults(
    test_decode_batch_device_error_closes_trace)


def test_queue_drop_rung_policy_queue():
    from flowgger_tpu.utils.bounded_queue import PolicyQueue

    q = PolicyQueue(maxsize=1, policy="drop_newest")
    q.put(b"a")
    q.put(b"b")  # full -> shed incoming
    (ev,) = _events_of("queue_drop")
    assert ev["site"] == "queue" and ev["detail"] == "drop_newest"
    assert registry.get("queue_dropped") == 1


def test_queue_drop_rung_fair_queue_attributes_tenant():
    from flowgger_tpu.tenancy.fairqueue import WeightedFairQueue
    from flowgger_tpu.tenancy.registry import TenantRegistry
    from flowgger_tpu.tenancy import set_current

    reg = TenantRegistry.from_config(Config.from_string(
        '[tenants.noisy]\npeers = ["10.0.0.1"]\n'
        'queue_policy = "drop_oldest"\n'))
    q = WeightedFairQueue(maxsize=1, registry=reg)
    set_current("noisy")
    try:
        q.put(b"one")
        q.put(b"two")  # full -> noisiest sheddable lane loses its head
    finally:
        set_current(None)
    (ev,) = _events_of("queue_drop")
    assert ev["tenant"] == "noisy"
    assert ev["cost"] == 1 and ev["cost_unit"] == "lines"


def test_tenant_shed_rung():
    from flowgger_tpu.tenancy.admission import TenantState
    from flowgger_tpu.tenancy.registry import TenantRegistry

    reg = TenantRegistry.from_config(Config.from_string(
        '[tenants.small]\npeers = ["10.0.0.2"]\nrate = 1\nburst = 1\n'))
    state = TenantState(reg.spec("small"))
    assert state.admit(1, 10)          # burst token
    assert not state.admit(100, 10)    # over rate -> shed
    (ev,) = _events_of("tenant_shed")
    assert ev["tenant"] == "small"
    assert ev["cost"] == 100 and ev["cost_unit"] == "lines"


def test_breaker_trip_and_recover_rungs():
    from flowgger_tpu.tpu.breaker import DecodeBreaker

    clock = [100.0]
    b = DecodeBreaker(failures=2, cooldown_ms=1000,
                      clock=lambda: clock[0])
    for _ in range(2):
        b.record_failure(RuntimeError("xla dead"))
    (trip,) = _events_of("breaker_trip")
    assert trip["site"] == "breaker" and trip["detail"] == "errors"
    clock[0] += 2.0
    assert b.allow()          # half-open probe
    b.record_success()
    (rec,) = _events_of("breaker_recover")
    assert rec["site"] == "breaker"
    # exactly one event per occurrence: one trip, one recovery
    assert registry.get("events_breaker_trip") == 1
    assert registry.get("events_breaker_recover") == 1


def _isolated_watchdog(monkeypatch):
    from flowgger_tpu.tpu import device_common as dc

    monkeypatch.setattr(dc, "_compile_sema", threading.Semaphore(1))
    monkeypatch.setattr(dc, "_compile_active_box", {})
    monkeypatch.setattr(dc, "_compile_slots", {})
    monkeypatch.setattr(dc, "_compile_ready", set())
    return dc


def test_watchdog_and_busy_decline_rungs(monkeypatch):
    dc = _isolated_watchdog(monkeypatch)
    monkeypatch.setenv(dc.COMPILE_TIMEOUT_ENV, "50")
    started, gate = threading.Event(), threading.Event()

    def slow_compile():
        started.set()
        gate.wait(5.0)
        return 1

    try:
        with pytest.raises(dc.CompileTimeout):
            dc.guarded_compile_call("obs:slow", slow_compile)
        (wd,) = _events_of("watchdog_decline")
        assert wd["site"] == "compile" and "obs:slow" in wd["detail"]
        assert wd["cost_unit"] == "deadline_s"
        # the slow compile holds the single-flight semaphore: a FRESH
        # slot must busy-decline instantly with its own typed event
        assert started.wait(2.0)
        with pytest.raises(dc.CompileTimeout):
            dc.guarded_compile_call("obs:queued", lambda: 2)
        (busy,) = _events_of("busy_decline")
        assert busy["site"] == "compile" and "obs:queued" in busy["detail"]
    finally:
        gate.set()


def test_framing_decline_rung(monkeypatch):
    from flowgger_tpu.tpu import framing
    from flowgger_tpu.tpu.device_common import CompileTimeout

    def always_timeout(slot, fn):
        raise CompileTimeout(slot)

    monkeypatch.setattr(framing, "_watchdogged", always_timeout)
    with pytest.raises(framing.FramingDeclined):
        framing.device_frame_region(b"hello\nworld\n", "line", 64,
                                    n_records=2)
    (ev,) = _events_of("framing_decline")
    assert ev["route"] == "line" and "watchdog" in ev["detail"]
    assert registry.get("framing_declines") == 1


def test_economics_switch_rung():
    from flowgger_tpu.tpu.overlap import RouteEconomics

    econ = RouteEconomics(enabled=True, label="lane0")
    # device measures 100x slower than host -> steady winner flips
    econ.observe("device", 100, 1.0)
    econ.observe("host", 100, 0.001)
    (ev,) = _events_of("economics_switch")
    assert ev["route"] == "split" and "device -> host" in ev["detail"]
    assert ev["lane"] == 0 and ev["cost_unit"] == "s_per_row"
    # a recovered device wins the traffic back: the EWMA needs a few
    # fast samples to cross the margin, then exactly one more event
    for _ in range(25):
        econ.observe("device", 100, 0.0000001)
    assert len(_events_of("economics_switch")) == 2
    second = _events_of("economics_switch")[1]
    assert "host -> device" in second["detail"]


def test_framing_economics_switch_rung():
    from flowgger_tpu.tpu.framing import FramingEconomics

    econ = FramingEconomics(enabled=True)
    econ.observe("framing", 100, 1.0)
    econ.observe("hostpack", 100, 0.001)
    (ev,) = _events_of("economics_switch")
    assert ev["route"] == "framing"
    assert "framing -> hostpack" in ev["detail"]


def test_aot_reject_rung(tmp_path):
    from flowgger_tpu.tpu.aot import AotStore

    root = tmp_path / "artifacts"
    root.mkdir()
    (root / "manifest.json").write_text("{ not json")
    assert AotStore.load(str(root)) is None
    (ev,) = _events_of("aot_reject")
    assert ev["site"] == "aot" and "corrupt" in ev["detail"]
    assert registry.get("aot_rejects") == 1


def test_device_error_rung_via_fault_site():
    import io

    from flowgger_tpu.decoders import RFC5424Decoder
    from flowgger_tpu.encoders import GelfEncoder
    from flowgger_tpu.mergers import LineMerger
    from flowgger_tpu.tpu.batch import BatchHandler

    faultinject.configure_from(Config.from_string(
        '[faults]\ndevice_decode = "once:1"\n'))
    cfg = Config.from_string("")
    tx = queue.Queue()
    h = BatchHandler(tx, RFC5424Decoder(), GelfEncoder(cfg), cfg,
                     start_timer=False, merger=LineMerger(cfg))
    h.ingest_sep = b"\n"
    h.ingest_strip_cr = True
    h.ingest_chunk(b"<13>1 2015-08-05T15:53:45Z h a p m - ok\n")
    stderr = sys.stderr
    sys.stderr = io.StringIO()
    try:
        h.flush()
    finally:
        sys.stderr = stderr
    h.close()
    assert len(_events_of("device_error")) >= 1
    ev = _events_of("device_error")[0]
    assert ev["site"] == "batch" and ev["route"] == "rfc5424"
    # degradation boundary held: the line still emitted
    assert not tx.empty()


test_device_error_rung_via_fault_site = pytest.mark.faults(
    test_device_error_rung_via_fault_site)


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def _run_traced_batch(n=4):
    from flowgger_tpu.decoders import RFC5424Decoder
    from flowgger_tpu.encoders import GelfEncoder
    from flowgger_tpu.mergers import NulMerger
    from flowgger_tpu.tpu.batch import BatchHandler

    cfg = Config.from_string("")
    tx = queue.Queue()
    h = BatchHandler(tx, RFC5424Decoder(), GelfEncoder(cfg), cfg,
                     start_timer=False, merger=NulMerger(cfg))
    h.ingest_sep = b"\n"
    h.ingest_strip_cr = True
    for i in range(n):
        h.ingest_chunk(
            b"<13>1 2015-08-05T15:53:45Z h a p m - hello %d\n" % i)
    h.flush()
    h.close()
    return tx


def test_tracing_off_records_nothing():
    assert obs_trace.tracer.begin("x") is None
    _run_traced_batch()
    assert obs_trace.tracer.snapshot() == []
    assert obs_trace.tracer.stats()["completed"] == 0


def test_ring_mode_batch_spans():
    obs_trace.tracer.configure("ring")
    _run_traced_batch()
    snaps = obs_trace.tracer.snapshot()
    assert snaps, "no completed batch traces"
    trace = snaps[-1]
    stages = [sp["stage"] for sp in trace["spans"]]
    # the block route records the full ladder
    for stage in ("pack", "submit", "fetch", "encode", "sequence",
                  "emit"):
        assert stage in stages, f"missing {stage} in {stages}"
    assert trace["route"] == "rfc5424"
    assert trace.get("e2e_s", 0) > 0
    for sp in trace["spans"]:
        assert sp["t1"] >= sp["t0"]
        assert "thread" in sp
    # e2e histogram observed alongside
    assert registry.snapshot()["e2e_batch_seconds"]["count"] >= 1


def test_chrome_events_required_keys():
    obs_trace.tracer.configure("ring")
    _run_traced_batch()
    events = obs_trace.tracer.chrome_events()
    spans = [e for e in events if e.get("ph") == "X"]
    assert spans
    for e in spans:
        for key in ("ph", "ts", "dur", "pid", "tid", "name"):
            assert key in e, f"span missing {key}: {e}"
        assert e["dur"] >= 0
    # round-trips as JSON
    assert json.loads(json.dumps({"traceEvents": events}))


def test_trace_ring_is_bounded():
    obs_trace.tracer.configure("ring", ring=4)
    for _ in range(10):
        bid = obs_trace.tracer.begin("t")
        obs_trace.tracer.span(bid, "pack", 0.0, 0.1)
        obs_trace.tracer.end(bid)
    stats = obs_trace.tracer.stats()
    assert stats["ring"] == 4 and stats["completed"] == 10


def test_jsonl_mode_and_trace_dump_cli(tmp_path):
    path = tmp_path / "trace.jsonl"
    obs_trace.tracer.configure("jsonl", path=str(path))
    _run_traced_batch()
    obs_trace.tracer.close()
    lines = path.read_text().strip().splitlines()
    assert lines
    rec = json.loads(lines[-1])
    assert rec["spans"]
    out = tmp_path / "chrome.json"
    r = subprocess.run(
        [sys.executable, _TRACE_DUMP, "--jsonl", str(path),
         "-o", str(out)],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    doc = json.loads(out.read_text())
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert spans
    for e in spans:
        for key in ("ph", "ts", "dur", "pid", "tid"):
            assert key in e


# -- sub-spans, profiler annotations, link counters --------------------------

class _FakeAnnotation:
    """Stands where ``jax.profiler.TraceAnnotation`` would: records
    every open and close with the thread it happened on."""

    log = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        self.log.append(("open", self.name, self.kw.get("batch"),
                         threading.current_thread().name))
        return self

    def __exit__(self, *exc):
        self.log.append(("close", self.name, self.kw.get("batch"),
                         threading.current_thread().name))
        return False


@pytest.fixture
def annotations(monkeypatch):
    _FakeAnnotation.log = []
    monkeypatch.setattr(obs_trace, "_annotation_class",
                        lambda: _FakeAnnotation)
    monkeypatch.setattr(obs_trace.tracer, "_annotation", None)
    return _FakeAnnotation.log


@pytest.fixture
def host_route(monkeypatch):
    """The route ``backfill.drain`` serves 99.6% of its rows on: split
    decode on the device, block encode on the host."""
    monkeypatch.setenv("FLOWGGER_DEVICE_ENCODE", "0")
    return Config.from_string('[input]\ntpu_fuse = "off"\n')


def _run_batch(cfg, lines):
    from flowgger_tpu.decoders import RFC5424Decoder
    from flowgger_tpu.encoders import GelfEncoder
    from flowgger_tpu.mergers import NulMerger
    from flowgger_tpu.tpu.batch import BatchHandler

    tx = queue.Queue()
    h = BatchHandler(tx, RFC5424Decoder(), GelfEncoder(cfg), cfg,
                     start_timer=False, merger=NulMerger(cfg))
    h.ingest_sep = b"\n"
    h.ingest_strip_cr = True
    h.ingest_chunk(b"".join(lines))
    h.flush()
    h.close()
    out = []
    while not tx.empty():
        item = tx.get()
        out.append(bytes(getattr(item, "data", item)))
    return out


_LINES = [b"<13>1 2015-08-05T15:53:45Z h a p m - hello %d\n" % i
          for i in range(5)]


def test_tracing_off_opens_no_sub_span_and_no_annotation(annotations,
                                                         host_route):
    assert obs_trace.tracer.sub(1, "d2h", "fetch") is obs_trace._NO_SUB
    _run_batch(host_route, _LINES)
    assert annotations == []
    assert obs_trace.tracer._annotation is None   # jax.profiler untouched
    assert obs_trace.tracer.snapshot() == []


def test_sub_spans_carry_parent_thread_and_batch(annotations, host_route):
    obs_trace.tracer.configure("ring")
    _run_batch(host_route, _LINES)
    rec = obs_trace.tracer.snapshot()[-1]
    by_stage = {}
    for sp in rec["sub"]:
        assert sp["t1"] >= sp["t0"]
        by_stage.setdefault(sp["stage"], []).append(sp)
    assert set(by_stage) == {"h2d", "device_wait", "d2h", "ts_text"}
    ingest = {sp["thread"] for sp in rec["spans"] if sp["stage"] == "decode"}
    fetcher = {sp["thread"] for sp in rec["spans"] if sp["stage"] == "fetch"}
    assert ingest != fetcher
    assert {sp["parent"] for sp in by_stage["h2d"]} == {"decode"}
    assert {sp["thread"] for sp in by_stage["h2d"]} == ingest
    for stage in ("device_wait", "d2h"):
        assert {sp["parent"] for sp in by_stage[stage]} == {"fetch"}
        assert {sp["thread"] for sp in by_stage[stage]} == fetcher
    # the host block encoder's timestamp text, once a batch
    (ts_text,) = by_stage["ts_text"]
    assert (ts_text["parent"], ts_text["rows"]) == ("encode", len(_LINES))
    assert ts_text["thread"] in fetcher
    # every stage and sub-span held an annotation open for this batch,
    # on the thread that did the work, and every one was closed
    opened = [e[1:] for e in annotations if e[0] == "open"]
    closed = [e[1:] for e in annotations if e[0] == "close"]
    assert sorted(opened) == sorted(closed)
    assert {b for _n, b, _t in opened} == {rec["bid"]}
    names = {n for n, _b, _t in opened}
    assert names == {"flowgger." + s for s in
                     [sp["stage"] for sp in rec["spans"] + rec["sub"]]}
    assert {t for n, _b, t in opened if n == "flowgger.d2h"} == fetcher
    assert {t for n, _b, t in opened if n == "flowgger.h2d"} == ingest


def test_stage_spans_are_the_same_with_and_without_sub_spans(
        monkeypatch, host_route):
    obs_trace.tracer.configure("ring")
    _run_batch(host_route, _LINES)
    with_sub = obs_trace.tracer.snapshot()[-1]
    monkeypatch.setattr(obs_trace.tracer, "sub",
                        lambda *a, **kw: obs_trace._NO_SUB)
    _run_batch(host_route, _LINES)
    without = obs_trace.tracer.snapshot()[-1]
    assert with_sub["sub"] and not without["sub"]
    stages = [sp["stage"] for sp in with_sub["spans"]]
    assert stages == [sp["stage"] for sp in without["spans"]]
    assert stages == ["pack", "decode", "submit", "fetch", "encode",
                      "sequence", "emit"]
    assert set(stages) <= set(obs_trace.STAGES)


def test_chrome_events_nest_sub_spans_under_their_parent(host_route):
    obs_trace.tracer.configure("ring")
    _run_batch(host_route, _LINES)
    events = [e for e in obs_trace.tracer.chrome_events()
              if e.get("ph") == "X"]
    subs = [e for e in events if e["cat"] == "sub"]
    assert {e["name"] for e in subs} == {"h2d", "device_wait", "d2h",
                                         "ts_text"}
    for e in subs:
        parents = [p for p in events if p["cat"] == "batch"
                   and p["name"] == e["args"]["parent"]
                   and p["tid"] == e["tid"]
                   and p["args"]["batch"] == e["args"]["batch"]]
        assert len(parents) == 1
        p = parents[0]
        # epoch microseconds in a float resolve a quarter of one; and
        # the handler lays ``fetch`` from the pop's start with the
        # length block_fetch_encode measured from its own, some tens
        # of microseconds later
        assert p["ts"] <= e["ts"] + 2
        assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1000
        # the stage comes first in the document, its children after
        assert events.index(p) < events.index(e)
    d2h = [e for e in subs if e["name"] == "d2h"]
    assert all(e["args"]["note"] and e["args"]["bytes"] > 0 for e in d2h)


def test_link_counters_on_the_served_route(host_route):
    import numpy as np

    from flowgger_tpu.tpu import pack, rfc5424

    n, max_len = len(_LINES), 512
    rows = pack.bucket_rows(n)
    _run_batch(host_route, _LINES)
    snap = registry.snapshot()
    assert snap["batch_rows_real"] == n
    assert snap["batch_rows_padded"] == rows
    # a [rows, 512] u8 batch and its i32 lengths went up; the lines are
    # what is not padding
    line_bytes = sum(len(ln) - 1 for ln in _LINES)
    assert snap["h2d_bytes"] == rows * max_len + rows * 4
    assert snap["packed_line_bytes"] == line_bytes
    assert snap["h2d_bytes"] - snap["packed_line_bytes"] == \
        rows * (max_len + 4) - line_bytes
    # every output channel of the decode program came down: their
    # copies begun at its dispatch, the fetcher blocked for them once
    batch, lens = pack.pack_lines_2d([ln[:-1] for ln in _LINES],
                                     max_len)[:2]
    out = rfc5424.decode_rfc5424_submit(batch, lens)[0]
    assert snap["d2h_prefetched"] == len(out) == 31
    assert snap["d2h_calls"] == 1
    assert snap["d2h_bytes"] == sum(np.asarray(v).nbytes
                                    for v in out.values())
    # the fetcher's seconds went to the host route and to no other
    assert snap["route_pop_seconds_host"] > 0
    assert "route_pop_seconds_fused" not in snap
    assert "route_pop_seconds_device" not in snap
    # the device encoder's own count keeps its meaning: it never ran
    assert snap["device_encode_fetch_bytes"] == 0


def test_the_pair_rescue_is_a_second_round_over_the_link(host_route):
    """A row with 7..16 SD pairs sends the batch's overflow rows
    through the wider kernel: one more upload, one more wait for the
    program and one more for its 31 copies, begun at its dispatch like
    the first program's, all inside the fetch stage."""
    seven = (b'<13>1 2015-08-05T15:53:45Z h a p m [a@1 k1="1" k2="2" '
             b'k3="3" k4="4"][b@1 k5="5" k6="6" k7="7"] rescued\n')
    obs_trace.tracer.configure("ring")
    _run_batch(host_route, _LINES + [seven])
    snap = registry.snapshot()
    assert snap["d2h_prefetched"] == 62
    assert snap["d2h_calls"] == 2
    rows = 256                     # both the batch's bucket and the rescue's
    assert snap["h2d_bytes"] == 2 * rows * (512 + 4)
    # every channel of both programs, once: 113 B a row beside the six
    # pair channels, which are [256, 6] from the first and [256, 16]
    # from the wide one (five int32 and one bool)
    assert snap["d2h_bytes"] == rows * (2 * 113 + 21 * (6 + 16))
    rec = obs_trace.tracer.snapshot()[-1]
    count = {}
    for sp in rec["sub"]:
        key = (sp["stage"], sp["parent"])
        count[key] = count.get(key, 0) + 1
    assert count == {("h2d", "decode"): 1, ("h2d", "fetch"): 1,
                     ("device_wait", "fetch"): 2, ("d2h", "fetch"): 2,
                     ("ts_text", "encode"): 1}
    d2h = [sp for sp in rec["sub"] if sp["stage"] == "d2h"]
    assert [sp["note"] for sp in d2h] == ["31", "31"]
    assert sum(sp["bytes"] for sp in d2h) == snap["d2h_bytes"]
    fetch = next(sp for sp in rec["spans"] if sp["stage"] == "fetch")
    assert all(fetch["t0"] <= sp["t0"] and sp["t1"] <= fetch["t1"] + 1e-3
               for sp in rec["sub"] if sp["parent"] == "fetch")


def test_h2d_counts_host_arrays_only():
    import numpy as np

    from flowgger_tpu.tpu.device_common import h2d

    batch = np.zeros((256, 64), dtype=np.uint8)
    lens = np.zeros(256, dtype=np.int32)
    lens[:3] = (5, 7, 0)
    on_device = h2d(batch, lens)
    assert registry.get("h2d_bytes") == 256 * 64 + 256 * 4
    assert registry.get("packed_line_bytes") == 12
    # a lane's block_submit has uploaded already: the format's own
    # submit passes the device arrays through, and counts nothing
    again = h2d(*on_device)
    assert again[0] is on_device[0] and again[1] is on_device[1]
    assert registry.get("h2d_bytes") == 256 * 64 + 256 * 4


def test_window_wait_is_the_stall_the_counter_adds():
    from flowgger_tpu.tpu.overlap import InflightWindow

    obs_trace.tracer.configure("ring")
    release = threading.Event()

    def pop(entry):
        release.wait(10)
        time.sleep(0.03)

    win = InflightWindow(1, pop, name="t")
    bid = obs_trace.tracer.begin("t")
    try:
        win.submit(0)               # the fetcher takes it and holds it
        threading.Timer(0.05, release.set).start()
        for i in (1, 2, 3):         # each finds the window full
            win.submit(i)
    finally:
        release.set()
        win.close()
    rec = next(r for r in obs_trace.tracer._open.values()
               if r["bid"] == bid)
    waits = [sp for sp in rec["sub"] if sp["stage"] == "window_wait"]
    assert len(waits) == 3
    assert {sp["parent"] for sp in waits} == {"submit"}
    stalled = registry.snapshot()["overlap_stall_seconds"]
    assert stalled > 0.08
    assert abs(sum(sp["t1"] - sp["t0"] for sp in waits) - stalled) < 1e-3
    obs_trace.tracer.end(bid)


def test_sink_bytes_are_the_same_traced_and_untraced(host_route):
    lines = _LINES + [b"not a syslog line\n",
                      b"<13>1 2015-08-05T15:53:45Z h a p m "
                      b'[x@1 k="v"] tail\n']
    plain = _run_batch(host_route, lines)
    obs_trace.tracer.configure("ring")
    traced = _run_batch(host_route, lines)
    assert plain and traced == plain


def test_compile_sub_span_is_an_annotation_and_no_record(annotations,
                                                         monkeypatch):
    from flowgger_tpu.tpu import device_common

    # an earlier test's compile may still hold the single-flight slot
    monkeypatch.setattr(device_common, "_compile_sema",
                        threading.Semaphore(1))
    monkeypatch.setattr(device_common, "_compile_active_box", {})
    obs_trace.tracer.configure("ring")
    name = "test-obs:compile-span"
    assert device_common.guarded_compile_call(
        name, lambda x: x + 1, 1, timeout_s=10) == 2
    # a warm name calls inline: no worker, no span
    assert device_common.guarded_compile_call(
        name, lambda x: x + 1, 2, timeout_s=10) == 3
    mine = [e for e in annotations if e[3] == f"xla-compile:{name}"]
    assert [e[:3] for e in mine] == [("open", "flowgger.compile", None),
                                     ("close", "flowgger.compile", None)]
    assert obs_trace.tracer.stats()["open"] == 0


def test_trace_dump_cli_bad_source(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text("{not json\n")
    r = subprocess.run(
        [sys.executable, _TRACE_DUMP, "--jsonl", str(bad)],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 2


# ---------------------------------------------------------------------------
# queue-wait + e2e latency histograms
# ---------------------------------------------------------------------------

def test_queue_wait_histogram_policy_queue():
    from flowgger_tpu.utils.bounded_queue import PolicyQueue

    q = PolicyQueue(maxsize=0)
    for i in range(64):
        q.put(b"x%d" % i)
    for _ in range(64):
        q.get()
    snap = registry.snapshot()
    assert snap["queue_wait_seconds"]["count"] >= 1


def test_queue_wait_histogram_fair_queue():
    from flowgger_tpu.tenancy.fairqueue import WeightedFairQueue

    q = WeightedFairQueue(maxsize=0)
    for i in range(64):
        q.put(b"x%d" % i)
    for _ in range(64):
        q.get()
    snap = registry.snapshot()
    assert snap["queue_wait_seconds"]["count"] >= 1


def test_queue_wait_survives_sentinel_and_drop_oldest():
    from flowgger_tpu.utils.bounded_queue import PolicyQueue

    q = PolicyQueue(maxsize=2, policy="drop_oldest")
    q.put(b"a")
    q.put(None)   # sentinel: never stamped, never dropped
    q.put(b"b")   # full: a is dropped, b enters
    assert q.get() == None  # noqa: E711 - sentinel delivered in order
    assert q.get() == b"b"


# ---------------------------------------------------------------------------
# reporter / final_flush write race (satellite fix)
# ---------------------------------------------------------------------------

def test_final_flush_shares_reporter_handle(tmp_path):
    reg = Registry()
    path = tmp_path / "m.jsonl"
    reg.inc("input_lines", 5)
    reg.start_reporter(60.0, str(path))  # tick far in the future
    reg.final_flush()
    reg.stop_reporter()
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 1
    assert json.loads(lines[0])["input_lines"] == 5


def test_stop_reporter_clears_stale_path(tmp_path):
    reg = Registry()
    path = tmp_path / "m.jsonl"
    reg.start_reporter(60.0, str(path))
    reg.stop_reporter()
    assert reg._path is None
    before = path.read_text() if path.exists() else ""
    reg.final_flush()  # no reporter: no write, no re-open of the path
    after = path.read_text() if path.exists() else ""
    assert before == after


def test_concurrent_flush_and_reporter_never_interleave(tmp_path):
    reg = Registry()
    reg.inc("input_lines", 1)
    path = tmp_path / "m.jsonl"
    reg.start_reporter(0.005, str(path))
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            reg.final_flush()

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    reg.stop_reporter()
    lines = path.read_text().strip().splitlines()
    assert len(lines) > 10
    for line in lines:  # every line is intact JSON — no mid-line splice
        assert json.loads(line)["input_lines"] == 1


# ---------------------------------------------------------------------------
# standalone obs listener + profiler toggle
# ---------------------------------------------------------------------------

def _get(addr, path, method="GET"):
    req = urllib.request.Request(
        f"http://{addr}{path}", method=method,
        data=b"" if method == "POST" else None)
    with urllib.request.urlopen(req, timeout=5) as resp:
        return resp.status, resp.headers.get("Content-Type"), resp.read()


def test_obs_server_metrics_trace_healthz(tmp_path):
    registry.inc("input_lines", 9)
    obs_trace.tracer.configure("ring")
    bid = obs_trace.tracer.begin("probe")
    obs_trace.tracer.span(bid, "pack", 1.0, 1.5, rows=3)
    obs_trace.tracer.end(bid)
    obs_events.emit("queue", "queue_drop", detail="drop_newest")
    server = obs_prom.ObsServer("127.0.0.1", 0)
    server.start()
    try:
        status, ctype, body = _get(server.addr, "/metrics")
        assert status == 200 and ctype == obs_prom.PROM_CONTENT_TYPE
        samples, types, problems = parse_exposition(body.decode())
        assert problems == [], "\n".join(problems)
        assert samples["flowgger_input_lines_total"][0][1] == "9"
        status, _, body = _get(server.addr, "/trace")
        assert status == 200
        doc = json.loads(body)
        assert any(e.get("ph") == "X" for e in doc["traceEvents"])
        status, _, body = _get(server.addr, "/healthz")
        doc = json.loads(body)
        assert doc["events"]["counts"] == {"queue_drop": 1}
        assert doc["trace"]["mode"] == "ring"
        assert doc["metrics"]["input_lines"] == 9
    finally:
        server.stop()


def test_profile_toggle_via_post_and_function(monkeypatch, tmp_path):
    from flowgger_tpu.utils import metrics as m

    calls = []
    monkeypatch.setattr(m, "start_jax_profiler",
                        lambda d: (calls.append(("start", d)),
                                   setattr(m, "_profiling", True)))
    monkeypatch.setattr(m, "stop_jax_profiler",
                        lambda: (calls.append(("stop",)),
                                 setattr(m, "_profiling", False)))
    monkeypatch.setattr(m, "_profiling", False)
    monkeypatch.setattr(m, "_profile_dir", str(tmp_path / "prof"))
    server = obs_prom.ObsServer("127.0.0.1", 0)
    server.start()
    try:
        status, _, body = _get(server.addr, "/profile", method="POST")
        assert status == 200
        doc = json.loads(body)
        assert doc["profiling"] is True
        assert doc["log_dir"].endswith("prof")
        status, _, body = _get(server.addr, "/profile", method="POST")
        assert json.loads(body)["profiling"] is False
    finally:
        server.stop()
    assert [c[0] for c in calls] == ["start", "stop"]


def test_sigusr2_toggles_profiler(monkeypatch):
    import signal

    from flowgger_tpu.pipeline import Pipeline
    from flowgger_tpu.utils import metrics as m

    flips = []
    monkeypatch.setattr(m, "toggle_jax_profiler",
                        lambda: (flips.append(1), (True, "d"))[1])
    p = Pipeline(Config.from_string(
        '[input]\ntype = "stdin"\n[output]\ntype = "debug"\n'))
    old = signal.getsignal(signal.SIGUSR2)
    try:
        p._install_signal_handlers([])
        handler = signal.getsignal(signal.SIGUSR2)
        assert callable(handler) and handler is not old
        handler(signal.SIGUSR2, None)
        assert flips == [1]
    finally:
        signal.signal(signal.SIGUSR2, old)
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        signal.signal(signal.SIGINT, signal.SIG_DFL)


def test_pipeline_standalone_listener_config():
    from flowgger_tpu.pipeline import Pipeline

    p = Pipeline(Config.from_string(
        '[input]\ntype = "stdin"\n[output]\ntype = "debug"\n'
        '[metrics]\nprom_port = 0\n'))
    # constructed but not started until run(); maybe_start_from is the
    # run()-side hook — exercise it directly
    from flowgger_tpu.obs.prom import maybe_start_from

    server = maybe_start_from(p.config)
    assert server is not None
    try:
        status, ctype, _ = _get(server.addr, "/metrics")
        assert status == 200 and "version=0.0.4" in ctype
    finally:
        server.stop()


def test_prom_port_validation():
    from flowgger_tpu.config import ConfigError
    from flowgger_tpu.obs.prom import maybe_start_from

    with pytest.raises(ConfigError):
        maybe_start_from(Config.from_string(
            "[metrics]\nprom_port = 99999\n"))
    assert maybe_start_from(Config.from_string("")) is None


# ---------------------------------------------------------------------------
# [metrics] config validation
# ---------------------------------------------------------------------------

def test_trace_config_validation():
    from flowgger_tpu.config import ConfigError

    with pytest.raises(ConfigError):
        obs_trace.configure_from(Config.from_string(
            '[metrics]\ntrace = "sideways"\n'))
    with pytest.raises(ConfigError):
        obs_trace.configure_from(Config.from_string(
            '[metrics]\ntrace = "jsonl"\n'))  # jsonl needs trace_path


def test_configure_from_wires_trace_and_events(tmp_path):
    from flowgger_tpu.utils import metrics as m

    tp = tmp_path / "t.jsonl"
    m.configure_from(Config.from_string(
        f'[metrics]\ntrace = "jsonl"\ntrace_path = "{tp}"\n'
        "events_ring = 13\n"))
    assert obs_trace.tracer.mode == "jsonl"
    assert obs_events.journal._ring.maxlen == 13
