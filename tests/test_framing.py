"""Device-resident framing (tpu/framing.py): differential tests vs the
host splitters, the decline/breaker ladder, the raw-session ingest
path, and the AOT framing family.

The scalar oracle is the host splitter logic itself —
``pack.split_chunk``'s numpy separator scan for line/nul and
``splitters._scan_syslen_region`` for syslen — and the contract is
byte identity: same records, same order, across all three framings and
arbitrary chunk boundaries.
"""

import queue

import numpy as np
import pytest

from flowgger_tpu.block import EncodedBlock
from flowgger_tpu.config import Config, ConfigError
from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
from flowgger_tpu.encoders.gelf import GelfEncoder
from flowgger_tpu.encoders.ltsv import LTSVEncoder
from flowgger_tpu.splitters import (
    LineSplitter,
    NulSplitter,
    SyslenSplitter,
    _scan_syslen_region,
)
from flowgger_tpu.tpu import framing, pack
from flowgger_tpu.tpu.batch import BatchHandler
from flowgger_tpu.utils import faultinject
from flowgger_tpu.utils.metrics import registry

MAX_LEN = 128
CFG = Config.from_string("")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    registry.reset()
    faultinject.reset()
    # run the framing jits inline: an earlier test's never-finishing
    # device-encode compile may hold the single-flight semaphore, and
    # these tests assert the *engaged* tier (the busy-decline ladder
    # has its own test below, which restores the real watchdog)
    monkeypatch.setattr(framing, "_watchdogged", lambda slot, fn: fn())
    yield
    faultinject.reset()


def _cfg(framing_on="on", lanes=1, extra=""):
    return Config.from_string(
        "[input]\n"
        f'tpu_framing = "{framing_on}"\n'
        'tpu_fuse = "off"\n'
        f"tpu_max_line_len = {MAX_LEN}\n"
        + (f"tpu_lanes = {lanes}\n" if lanes > 1 else "")
        + extra)


class ChunkedStream:
    """A stream that returns scheduled chunk sizes, so records split
    mid-byte (and delimiters land exactly on chunk edges)."""

    def __init__(self, data, sizes):
        self.data, self.pos = data, 0
        self.sizes, self.i = sizes, 0

    def read(self, n):
        if self.pos >= len(self.data):
            return b""
        sz = max(1, self.sizes[self.i % len(self.sizes)])
        self.i += 1
        out = self.data[self.pos:self.pos + sz]
        self.pos += len(out)
        return out


def collect(tx):
    out = []
    while not tx.empty():
        item = tx.get_nowait()
        if isinstance(item, EncodedBlock):
            out.extend(item.iter_unframed())
        else:
            out.append(item)
    return out


CORPUS = [
    f"<34>1 2023-10-11T22:14:15.003Z host{i % 7} app {i} ID47 - msg "
    f"number {i}".encode()
    for i in range(180)
] + [b"", b"plain junk", b"\xff\xfebinary", b"x" * 300, b"ends cr\r"]


def _run(cfg, splitter_cls, stream, sizes, encoder_cls=LTSVEncoder):
    tx = queue.Queue()
    h = BatchHandler(tx, RFC5424Decoder(), encoder_cls(CFG), cfg,
                     fmt="rfc5424", start_timer=False, merger=None)
    splitter_cls().run(ChunkedStream(stream, sizes), h)
    h.close()
    return collect(tx)


# ---------------------------------------------------------------------------
# span kernels vs the host splitters (the FC03 differential contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sep,name,strip", [
    (b"\n", "line", True), (b"\0", "nul", False)], ids=["line", "nul"])
def test_frame_sep_spans_match_host_split(sep, name, strip):
    import random

    rng = random.Random(11)
    for trial in range(12):
        lines = []
        for _ in range(rng.randrange(0, 50)):
            body = bytes(rng.randrange(1, 256)
                         for _ in range(rng.randrange(0, 40)))
            lines.append(body.replace(sep, b"~"))
        if trial % 3 == 0:
            lines += [b"", b"cr tail\r", b"\r"]
        region = b"".join(ln + sep for ln in lines)
        if not region:
            continue
        hs, hl, hn, _carry = pack._split_np(region, strip_cr=strip,
                                            sep=sep[0])
        p, consumed, err = framing.device_frame_region(
            region, name, MAX_LEN, n_records=region.count(sep))
        assert not err and consumed == len(region)
        assert p[5] == hn
        assert np.array_equal(p[3][:hn], hs)
        assert np.array_equal(p[4], hl)


@pytest.mark.parametrize("case", ["split_record", "overflow"])
def test_frame_sep_spans_kernel_at_a_regions_end(case):
    """The span kernel itself, where ``device_frame_region``'s callers
    never take it: a region whose last record is split across its end
    (the tail stays unconsumed, as ``split_chunk``'s carry), bytes past
    ``rlen`` in the bucket, and more separators than ``ncap`` slots."""
    rng = np.random.default_rng(7)
    if case == "overflow":
        region = np.frombuffer((b"x\n" * 100).ljust(4096, b"\0"), np.uint8)
        out = framing.frame_sep_spans_jit(region, 200, sep=10,
                                          strip_cr=True, ncap=64)
        assert bool(out["overflow"]) and int(out["n"]) == 100
        return
    for t in range(10):
        lines = [bytes(rng.integers(32, 127, rng.integers(0, 60))
                       .astype(np.uint8))
                 for _ in range(rng.integers(1, 30))]
        blob = b"".join(ln + (b"\r\n" if t % 3 == 0 else b"\n")
                        for ln in lines)
        if t % 2 == 0:
            blob += b"partial-tail"
        # the bucket is longer than the region and not zero past it
        reg = np.full(len(blob) + int(rng.integers(0, 64)), 10, np.uint8)
        reg[:len(blob)] = np.frombuffer(blob, np.uint8)
        out = framing.frame_sep_spans_jit(reg, np.int32(len(blob)), sep=10,
                                          strip_cr=True, ncap=64)
        hs, hl, hn, carry = pack.split_chunk(blob, strip_cr=True)
        assert not bool(out["overflow"])
        assert int(out["n"]) == hn
        assert int(out["consumed"]) == len(blob) - len(carry)
        assert np.array_equal(np.asarray(out["starts"])[:hn], hs), t
        assert np.array_equal(np.asarray(out["lens"])[:hn], hl), t


def _syslen_cases(group):
    if group == "handpicked":
        return [
            b"5 hello0 14 hello world!!3 abc",
            b"".join(b"%d %s" % (len(m), m)
                     for m in [b"", b"x" * 200, b"mid dle"]),
            b"5 hello7 incomp",          # incomplete body -> carry
            b"5 helloxx junk",           # bad prefix -> err
            b" leading space",           # empty prefix -> err
            b"123",                      # no space yet -> carry, no err
            b"",
        ]
    if group == "edges":
        return [
            b"5 hello",                  # exactly one record
            b"0 " * 5,                   # empty records only
            b"3 abc12 nodigitspace",     # a chain, then garbage
            b"03 abc",                   # leading zero
            b"5 hello14 hello world!!3 abc12 trunc",
        ]
    rng = np.random.default_rng(1)
    cases = []
    for _ in range(12):
        recs = [bytes(rng.integers(33, 127, size=int(rng.integers(0, 50)))
                      .astype(np.uint8))
                for _ in range(int(rng.integers(0, 12)))]
        extra = [b"", b"12", b"12 abc", b"garbage no prefix",
                 b"0 "][int(rng.integers(0, 5))]
        cases.append(b"".join(b"%d " % len(r) + r for r in recs) + extra)
    return cases


@pytest.mark.parametrize("group", ["handpicked", "edges", "random"])
def test_frame_syslen_spans_match_host_scan(group):
    for region in _syslen_cases(group):
        hs, hl, hn, hcons, herr = _scan_syslen_region(region)
        p, c, e = framing.device_frame_region(
            region, "syslen", MAX_LEN,
            n_records=max(region.count(b" "), 1))
        assert (p[5], c, e) == (hn, hcons, herr), region
        assert np.array_equal(p[3][:hn], hs)
        assert np.array_equal(p[4], hl)


def test_frame_syslen_huge_prefix_declines_to_host():
    # a >9-digit length prefix exceeds the exact int32 parse: the
    # kernel must decline the whole region (the host scan owns the
    # val > 2^31-1 error semantics), never return a divergent answer
    with pytest.raises(framing.FramingDeclined):
        framing.device_frame_region(b"12345678901 x", "syslen",
                                    MAX_LEN, n_records=1)


def test_frame_gather_matches_host_pack_including_oversized():
    lines = [b"short", b"y" * 500, b"", b"mid \xff bytes"]
    region = b"".join(ln + b"\n" for ln in lines)
    p, _, _ = framing.device_frame_region(region, "line", MAX_LEN,
                                          n_records=len(lines))
    hp = pack.pack_region_2d(region, MAX_LEN, sep=10, strip_cr=True)
    assert np.array_equal(np.asarray(p[0]), hp[0])
    assert np.array_equal(np.asarray(p[1]), hp[1])
    assert np.array_equal(p[3], hp[3])
    assert np.array_equal(p[4], hp[4])
    assert p[5] == hp[5]


def test_frame_gather_of_syslen_spans_clamps_and_zero_fills():
    """The gather behind syslen spans (starts that follow a prefix, not
    a separator): every row is its record clipped to ``max_len``, zero
    from there to the row's end, rows past ``n`` empty."""
    rng = np.random.default_rng(3)
    recs = [b"x" * int(k) for k in rng.integers(0, 300, 30)]
    region = b"".join(b"%d " % len(r) + r for r in recs)
    p, consumed, err = framing.device_frame_region(
        region, "syslen", MAX_LEN, n_records=region.count(b" "))
    assert (p[5], consumed, err) == (len(recs), len(region), False)
    bat, lens_c = np.asarray(p[0]), np.asarray(p[1])
    assert any(len(r) > MAX_LEN for r in recs)
    for i, r in enumerate(recs):
        assert bytes(bat[i][:lens_c[i]]) == r[:MAX_LEN], i
        assert p[4][i] == len(r)
    assert not bat[np.arange(bat.shape[1])[None, :] >= lens_c[:, None]].any()
    assert not lens_c[len(recs):].any()


# ---------------------------------------------------------------------------
# raw-session ingest: end-to-end byte identity
# ---------------------------------------------------------------------------

def test_raw_ingest_byte_identity_all_framings():
    stream_line = b"".join(ln + b"\n" for ln in CORPUS)
    stream_nul = b"".join(ln.replace(b"\0", b"~") + b"\0"
                          for ln in CORPUS)
    stream_sys = b"".join(b"%d %s" % (len(ln), ln) for ln in CORPUS)
    for splitter_cls, stream in ((LineSplitter, stream_line),
                                 (NulSplitter, stream_nul),
                                 (SyslenSplitter, stream_sys)):
        for sizes in ([37], [1 << 14], [13, 1, 777]):
            registry.reset()
            want = _run(_cfg("off"), splitter_cls, stream, sizes)
            got = _run(_cfg("on"), splitter_cls, stream, sizes)
            assert want == got, (splitter_cls.__name__, sizes)
            assert len(want) >= 180
            assert registry.get("framing_rows") > 0, \
                splitter_cls.__name__


def test_raw_ingest_gelf_output_identity():
    # GELF output engages the device-encode probe downstream of the
    # framed batch — the framed packed tuple must ride that route (and
    # its declines) byte-identically too
    stream = b"".join(ln + b"\n" for ln in CORPUS[:60])
    want = _run(_cfg("off"), LineSplitter, stream, [101],
                encoder_cls=GelfEncoder)
    got = _run(_cfg("on"), LineSplitter, stream, [101],
               encoder_cls=GelfEncoder)
    assert want == got


def test_raw_ingest_fused_route_compat():
    # tpu_fuse = "auto" + GELF output: the device-framed packed tuple
    # (committed lane-device arrays, not numpy) must ride
    # fused_routes.submit — socket bytes → output bytes as chained
    # device programs — and every decline rung below it, byte-
    # identically.  On hosts whose XLA can't compile the fused program
    # this exercises the decline ladder with device-resident inputs.
    stream = b"".join(ln + b"\n" for ln in CORPUS[:60])
    cfg_off = Config.from_string(
        f"[input]\ntpu_framing = \"off\"\ntpu_max_line_len = {MAX_LEN}\n")
    cfg_on = Config.from_string(
        f"[input]\ntpu_framing = \"on\"\ntpu_max_line_len = {MAX_LEN}\n")
    want = _run(cfg_off, LineSplitter, stream, [101],
                encoder_cls=GelfEncoder)
    got = _run(cfg_on, LineSplitter, stream, [101],
               encoder_cls=GelfEncoder)
    assert want == got


def test_raw_ingest_2lane_byte_identity():
    stream = b"".join(ln + b"\n" for ln in CORPUS)
    want = _run(_cfg("off", lanes=2), LineSplitter, stream, [53])
    got = _run(_cfg("on", lanes=2), LineSplitter, stream, [53])
    assert want == got
    stream_sys = b"".join(b"%d %s" % (len(ln), ln)
                          for ln in CORPUS[:80])
    want = _run(_cfg("off", lanes=2), SyslenSplitter, stream_sys, [29])
    got = _run(_cfg("on", lanes=2), SyslenSplitter, stream_sys, [29])
    assert want == got


def test_trailing_partial_line_emitted_at_eof():
    # BufRead::lines parity: a final record without its separator (and
    # with a trailing CR) still comes out, through the carry path
    stream = (b"<34>1 2023-10-11T22:14:15Z h a 1 - - one\n"
              b"<34>1 2023-10-11T22:14:16Z h a 1 - - tail\r")
    want = _run(_cfg("off"), LineSplitter, stream, [9])
    got = _run(_cfg("on"), LineSplitter, stream, [9])
    assert want == got and len(want) == 2


def test_syslen_error_stream_parity(capsys):
    # records before the malformed prefix emit; the session dies with
    # the host scan's message and later pushes are refused
    ok = CORPUS[3]
    stream = b"%d %s" % (len(ok), ok) + b"bogus junk follows"
    want = _run(_cfg("off"), SyslenSplitter, stream, [11])
    err_host = capsys.readouterr().err
    got = _run(_cfg("on"), SyslenSplitter, stream, [11])
    err_dev = capsys.readouterr().err
    assert want == got and len(want) == 1
    assert "Can't read message's length" in err_host
    assert "Can't read message's length" in err_dev


def test_dead_syslen_session_unregisters(capsys):
    # a mid-stream framing error kills the session; the splitter's
    # early close must still unregister it from the handler (a shared
    # long-lived handler must not accumulate dead sessions)
    tx = queue.Queue()
    h = BatchHandler(tx, RFC5424Decoder(), LTSVEncoder(CFG), _cfg("on"),
                     fmt="rfc5424", start_timer=False, merger=None)
    SyslenSplitter().run(ChunkedStream(b"xx bad prefix then more", [5]),
                         h)
    assert h._raw_sessions == []
    h.close()
    assert "Can't read message's length" in capsys.readouterr().err


def test_syslen_idle_with_partial_prefix_closes_quietly(capsys):
    # host parity (_run_spans TimeoutError branch): an idle timeout
    # with a partial length PREFIX buffered (not mid-body) prints the
    # idle-close notice, not a bad-length error
    class IdleStream:
        def __init__(self):
            self.calls = 0

        def read(self, n):
            self.calls += 1
            if self.calls == 1:
                return b"12"
            raise TimeoutError

    tx = queue.Queue()
    h = BatchHandler(tx, RFC5424Decoder(), LTSVEncoder(CFG), _cfg("on"),
                     fmt="rfc5424", start_timer=False, merger=None)
    SyslenSplitter().run(IdleStream(), h)
    h.close()
    err = capsys.readouterr().err
    assert "Closing idle connection" in err
    assert "Can't read message's length" not in err


def test_syslen_short_read_message_at_eof(capsys):
    stream = b"500 only part of the body"
    got = _run(_cfg("on"), SyslenSplitter, stream, [7])
    assert got == []
    assert "failed to fill whole buffer" in capsys.readouterr().err


def test_carry_accumulates_without_separator():
    tx = queue.Queue()
    h = BatchHandler(tx, RFC5424Decoder(), LTSVEncoder(CFG), _cfg("on"),
                     fmt="rfc5424", start_timer=False, merger=None)
    sess = h.open_raw("line")
    assert sess.push(b"<34>1 2023-10-11T22:14:15Z h")
    h.flush()
    assert collect(tx) == []
    assert registry.get_gauge("framing_carry_bytes") == 28
    assert sess.push(b" a 1 - - the rest\n")
    h.flush()
    h.close()
    assert len(collect(tx)) == 1
    assert registry.get_gauge("framing_carry_bytes") == 0


# ---------------------------------------------------------------------------
# decline ladder / breaker / economics / config
# ---------------------------------------------------------------------------

def test_watchdog_decline_falls_back_to_host(monkeypatch):
    from flowgger_tpu.tpu.device_common import CompileTimeout

    def timed_out(slot, fn):
        raise CompileTimeout(slot)

    monkeypatch.setattr(framing, "_watchdogged", timed_out)
    stream = b"".join(ln + b"\n" for ln in CORPUS[:50])
    want = _run(_cfg("off"), LineSplitter, stream, [41])
    got = _run(_cfg("on"), LineSplitter, stream, [41])
    assert want == got
    assert registry.get("framing_declines") > 0
    assert registry.get("framing_rows") == 0


def test_decline_cooldown_hysteresis():
    state = {}
    st = framing.cooldown_state(state, "line")
    for _ in range(framing.DECLINE_LIMIT):
        framing.note_decline(st)
    assert st["cooldown"] == framing.COOLDOWN
    assert framing.in_cooldown(st)
    st["cooldown"] = 1
    assert framing.in_cooldown(st)
    assert not framing.in_cooldown(st)
    framing.note_success(st)
    assert st["declines"] == 0
    # its own namespace: never shares the fused/device decline budget
    assert set(state) == {"framing:line"}


@pytest.mark.faults
def test_device_error_degrades_through_breaker(capsys):
    # device_decode fault mid-framing: the breaker records the failure
    # and the flush re-frames on the host — zero records lost
    stream = b"".join(ln + b"\n" for ln in CORPUS[:40])
    want = _run(_cfg("off"), LineSplitter, stream, [33])
    capsys.readouterr()
    faultinject.configure({"device_decode": "every:1"})
    try:
        got = _run(_cfg("on"), LineSplitter, stream, [33])
    finally:
        faultinject.reset()
    assert want == got


def test_framing_economics_routes_to_cheaper_path():
    econ = framing.FramingEconomics(probe_every=4)
    assert econ.allow_framing()          # probe the device tier first
    econ.observe("framing", 100, 1.0)    # 10ms/row: terrible
    # a slow-measuring framing tier buys host comparison flushes
    assert not econ.allow_framing()
    econ.observe("hostpack", 100, 0.001)
    allowed = [econ.allow_framing() for _ in range(8)]
    assert not all(allowed)              # framing loses the traffic
    assert any(allowed)                  # but still re-probes
    snap = econ.snapshot()
    assert snap["framing_s_per_row"] > snap["hostpack_s_per_row"]
    # the operator's why-did-framing-stop signal in /healthz
    assert registry.get_gauge("framing_framing_spr") > \
        registry.get_gauge("framing_hostpack_spr") > 0


def test_framing_config_validation():
    with pytest.raises(ConfigError):
        BatchHandler(queue.Queue(), RFC5424Decoder(), LTSVEncoder(CFG),
                     Config.from_string(
                         '[input]\ntpu_framing = "maybe"\n'),
                     fmt="rfc5424", start_timer=False, merger=None)


def test_framing_auto_stays_off_on_cpu_backend():
    import jax

    h = BatchHandler(queue.Queue(), RFC5424Decoder(), LTSVEncoder(CFG),
                     Config.from_string(""), fmt="rfc5424",
                     start_timer=False, merger=None)
    if jax.default_backend() == "cpu":
        assert not h.wants_raw("line")
    h.close()


def test_framing_on_notice_when_route_cannot_engage(capsys):
    # Record-path config (no block merger route): "on" must say why
    from flowgger_tpu.encoders.rfc3164 import RFC3164Encoder

    h = BatchHandler(queue.Queue(), RFC5424Decoder(),
                     RFC3164Encoder(CFG), _cfg("on"), fmt="rfc5424",
                     start_timer=False, merger=None)
    assert not h.wants_raw("line")
    assert "cannot device-frame" in capsys.readouterr().err
    h.close()


def test_span_fetch_bytes_bounded_under_emitted():
    stream = b"".join(ln + b"\n" for ln in CORPUS)
    got = _run(_cfg("on"), LineSplitter, stream, [1 << 14])
    rows = registry.get("framing_rows")
    assert rows > 0
    fetch_per_row = registry.get("framing_span_fetch_bytes") / rows
    emit_per_row = sum(len(g) for g in got) / rows
    assert fetch_per_row < emit_per_row


# ---------------------------------------------------------------------------
# AOT framing family
# ---------------------------------------------------------------------------

def test_framing_aot_artifacts_round_trip(tmp_path):
    from flowgger_tpu.tpu import aot

    d = str(tmp_path / "aot")
    manifest = aot.build_artifacts(
        d, platforms=("cpu",), families=("framing",),
        rows_grid=(256,), max_len=MAX_LEN, quiet=True)
    kinds = {e["family"] for e in manifest["entries"].values()}
    assert kinds == {"framing_line", "framing_nul", "framing_syslen",
                     "framing_gather"}
    cfg = Config.from_string(f'[input]\ntpu_aot_dir = "{d}"\n')
    try:
        aot.setup_aot(cfg, max_len=MAX_LEN, grid=None)
        assert aot.active_store() is not None
        # a region at the artifact's byte bucket (256 rows x ~128 B)
        lines = [b"z" * 120 for _ in range(200)]
        region = b"".join(ln + b"\n" for ln in lines)
        registry.reset()
        p, _, _ = framing.device_frame_region(region, "line", MAX_LEN,
                                              n_records=200)
        assert registry.get("aot_hits") >= 2  # stage A + gather
        hp = pack.pack_region_2d(region, MAX_LEN, sep=10, strip_cr=True)
        assert np.array_equal(np.asarray(p[0]), hp[0])
        assert np.array_equal(np.asarray(p[1]), hp[1])
    finally:
        aot.activate_store(None)
