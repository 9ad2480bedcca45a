"""The timestamps' text of the host block encoders
(``block_common.vals_scratch``): json_f64's notation comes from the
threaded native formatter, one dense slot a row; every other notation
(display_f64, unix_to_rfc3339_ms) and a process without the library
keep the dedup path, one Python call per distinct value.

Held here, for each of the four ``ts_scratch(..., json_f64)`` call
sites (rfc5424 -> GELF on the native row assembler, the same on the
numpy engine, rfc3164 -> GELF, ltsv -> GELF) and every shape of stamp a
format can write: the block's bytes with the native formatter equal
its bytes with the formatter gone, and both equal the scalar pipeline's;
the two counters; the ``ts_text`` sub-span under ``encode``.  The device
encode tiers are skipped (``allow_device=False``): nothing here waits
for a device-encode compile.  On the CPU this proves bytes and counts,
never a rate.
"""

import numpy as np
import pytest

from flowgger_tpu import native
from flowgger_tpu.config import Config
from flowgger_tpu.decoders import DecodeError
from flowgger_tpu.decoders.ltsv import LTSVDecoder
from flowgger_tpu.decoders.rfc3164 import RFC3164Decoder
from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
from flowgger_tpu.encoders.gelf import GelfEncoder
from flowgger_tpu.encoders.ltsv import LTSVEncoder
from flowgger_tpu.encoders.rfc5424 import RFC5424Encoder
from flowgger_tpu.mergers import NulMerger
from flowgger_tpu.obs import trace as obs_trace
from flowgger_tpu.tpu import block_common, pack
from flowgger_tpu.tpu.batch import block_fetch_encode, block_submit
from flowgger_tpu.tpu.device_common import TS_W
from flowgger_tpu.utils.metrics import registry
from flowgger_tpu.utils.rustfmt import display_f64, json_f64
from flowgger_tpu.utils.timeparse import unix_to_rfc3339_ms

from test_applog_longlines import run as handler_run

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native library not built")

ROWS = 48
CFG = Config.from_string("")
GELF = GelfEncoder(CFG)
GELF_EXTRA = GelfEncoder(Config.from_string(
    '[output.gelf_extra]\nZone = "eu"\nkind = "syslog"\n'))
MERGER = NulMerger(CFG)


@pytest.fixture(autouse=True)
def _clean():
    registry.reset()
    obs_trace.tracer.configure("off")
    yield
    obs_trace.tracer.configure("off")
    registry.reset()


# ---- the stamps a format can write ------------------------------------------

def _rfc3339(shape, i):
    """The i-th row's RFC 3339 stamp of a batch of ``shape``."""
    sec = f"2023-11-14T22:13:{i % 60:02d}"
    return {
        "one": "2023-11-14T22:13:20.5Z",
        "ms": f"{sec}.{i * 37 % 1000:03d}Z",
        "us": f"{sec}.{i * 7919 % 10**6:06d}+02:00",
        "ns": f"{sec}.{(i * 104729 + 1) % 10**9:09d}Z",
        "integral": "2023-11-14T22:13:20Z",           # 1700000000.0
        "pre1970": f"19{i % 70:02d}-12-31T23:59:{i % 60:02d}.25Z",
        "past2262": f"2{3 + i % 7}00-01-01T00:00:{i % 60:02d}.5-07:00",
    }[shape]


_RFC3339_SHAPES = ("one", "ms", "us", "ns", "integral", "pre1970",
                   "past2262")


def _rfc5424_lines(shape):
    return [f'<{i % 192}>1 {_rfc3339(shape, i)} host-{i} app {i} mid '
            f'[sd@32473 k="v{i}"] message {i}'.encode()
            for i in range(ROWS)]


def _ltsv_lines(shape):
    if shape == "unix_literal":
        # unix-literal rows format their own span; the rfc3339 rows
        # beside them share the batch's scratch
        return [(f"time:15119630{i:02d}.{i * 7919 % 10**6:06d}" if i % 3
                 else f"time:{_rfc3339('ms', i)}").encode()
                + f"\thost:h{i}\tk:v{i}\tmessage:m {i}".encode()
                for i in range(ROWS)]
    return [f"time:{_rfc3339(shape, i)}\thost:h{i}\tk:v{i}\t"
            f"message:m {i}".encode() for i in range(ROWS)]


def _rfc3164_lines(shape):
    """Whole seconds only: the format has no fraction.  A stamp that
    names its year (the only way to write one before 1970 or past 2262)
    is the scalar oracle's: the kernel declines it."""
    stamp = {
        "one": lambda i: "Aug  5 15:53:45",
        "seconds": lambda i: f"Aug {1 + i % 28:2d} 15:{i % 60:02d}:45",
        "with_year": lambda i: (
            f"Aug {1 + i % 28:2d} 15:{i % 60:02d}:45",
            "2023 Nov 14 22:13:20",                    # 1700000000.0
            f"19{i % 70:02d} Dec 31 23:59:{i % 60:02d}",
            f"2{3 + i % 7}00 Jan  1 00:00:{i % 60:02d}")[i % 4],
    }[shape]
    return [f"<{i % 192}>{stamp(i)} host{i} app[{i}]: message {i}".encode()
            for i in range(ROWS)]


_SITES = {
    # site: (format, lines of a shape, decoder, encoder)
    "rfc5424_gelf_native": ("rfc5424", _rfc5424_lines, RFC5424Decoder(),
                            GELF),
    "rfc5424_gelf_numpy": ("rfc5424", _rfc5424_lines, RFC5424Decoder(),
                           GELF_EXTRA),
    "rfc3164_gelf": ("rfc3164", _rfc3164_lines, RFC3164Decoder(CFG), GELF),
    "ltsv_gelf": ("ltsv", _ltsv_lines, LTSVDecoder(CFG), GELF),
}

_CASES = (
    [(site, shape) for site in ("rfc5424_gelf_native", "rfc5424_gelf_numpy")
     for shape in _RFC3339_SHAPES]
    + [("rfc3164_gelf", shape)
       for shape in ("one", "seconds", "with_year")]
    + [("ltsv_gelf", shape) for shape in _RFC3339_SHAPES + ("unix_literal",)]
)


def scalar_bytes(decoder, encoder, lines):
    """What the scalar pipeline writes: the record path, a line at a
    time."""
    out = []
    for ln in lines:
        try:
            rec = decoder.decode(ln.decode("utf-8"))
        except DecodeError:
            continue
        out.append(MERGER.frame(encoder.encode(rec)))
    return b"".join(out)


def block_bytes(fmt, lines, encoder, decoder):
    """The host block encoder's bytes for one batch, and how many of
    its rows took the scalar oracle."""
    packed = pack.pack_lines_2d(lines, 256)
    handle = block_submit(fmt, packed)
    res, _, _ = block_fetch_encode(fmt, handle, packed, encoder, MERGER,
                                   decoder, allow_device=False)
    assert res is not None
    return bytes(res.block.data), res.fallback_rows


# ---- the four call sites x the stamps ---------------------------------------

@needs_native
@pytest.mark.parametrize("site,shape", _CASES,
                         ids=[f"{s}-{sh}" for s, sh in _CASES])
def test_the_blocks_bytes_with_and_without_the_native_formatter(
        site, shape, monkeypatch):
    fmt, lines_of, decoder, encoder = _SITES[site]
    lines = lines_of(shape)
    want = scalar_bytes(decoder, encoder, lines)
    assert want.count(b"\0") == ROWS        # the oracle takes every line

    got_native, fell_back = block_bytes(fmt, lines, encoder, decoder)
    # every row stayed columnar (but rfc3164's stamps with a year), and
    # the native formatter wrote every kept row's stamp (ltsv's
    # unix-literal rows format their span besides)
    assert fell_back == (ROWS - ROWS // 4 if shape == "with_year" else 0)
    assert registry.get("ts_text_native_rows") == ROWS - fell_back
    assert registry.get("ts_text_python_values") == 0

    registry.reset()
    monkeypatch.setattr(native, "format_f64_json_native",
                        lambda *a, **k: None)
    got_python, _ = block_bytes(fmt, lines, encoder, decoder)
    assert registry.get("ts_text_native_rows") == 0
    distinct = 1 if shape in ("one", "integral") else 2
    assert registry.get("ts_text_python_values") >= distinct

    assert got_native == got_python == want
    if shape == "integral":
        assert got_native.count(b'"timestamp":1700000000.0') == ROWS
    if shape == "with_year":
        assert got_native.count(b'"timestamp":1700000000.0') == ROWS // 4


# ---- vals_scratch alone -----------------------------------------------------

def _rows(scratch, off, ln):
    return [scratch[a:a + n] for a, n in zip(off.tolist(), ln.tolist())]


_STAMPS = np.array([1700000000.0, 1700000000.123, 1700000000.123,
                    -86399.75, 10413792000.5, 0.0, 1511963055.637824])


@needs_native
def test_the_native_scratch_is_one_dense_slot_a_row():
    scratch, off, ln = block_common.vals_scratch(_STAMPS, json_f64)
    assert isinstance(scratch, bytes) and len(scratch) == _STAMPS.size * TS_W
    assert off.dtype == ln.dtype == np.int64
    assert off.tolist() == [i * TS_W for i in range(_STAMPS.size)]
    assert _rows(scratch, off, ln) == [
        json_f64(float(v)).encode() for v in _STAMPS]
    assert registry.get("ts_text_native_rows") == _STAMPS.size
    assert registry.get("ts_text_python_values") == 0


@needs_native
def test_an_empty_batch_has_an_empty_scratch():
    scratch, off, ln = block_common.vals_scratch(np.zeros(0), json_f64)
    assert scratch == b"" and off.size == 0 and ln.size == 0
    assert registry.get("ts_text_native_rows") == 0


@pytest.mark.parametrize("fmt_fn", [display_f64, unix_to_rfc3339_ms],
                         ids=lambda f: f.__name__)
def test_another_notation_keeps_the_dedup_path(fmt_fn):
    scratch, off, ln = block_common.vals_scratch(_STAMPS, fmt_fn)
    assert _rows(scratch, off, ln) == [
        fmt_fn(float(v)).encode() for v in _STAMPS]
    # one text for each distinct value, shared by the rows that hold it
    distinct = np.unique(_STAMPS).size
    assert registry.get("ts_text_python_values") == distinct
    assert registry.get("ts_text_native_rows") == 0
    assert len(scratch) == sum(
        len(fmt_fn(float(v))) for v in np.unique(_STAMPS))
    assert off[1] == off[2]


def test_without_the_library_the_old_triple_comes_back(monkeypatch):
    monkeypatch.setattr(native, "_load", lambda: None)
    scratch, off, ln = block_common.vals_scratch(_STAMPS, json_f64)
    uniq, inv = np.unique(_STAMPS, return_inverse=True)
    strs = [json_f64(float(u)).encode("ascii") for u in uniq]
    ulen = np.array([len(s) for s in strs], dtype=np.int64)
    uoff = np.concatenate([[0], np.cumsum(ulen)[:-1]])
    assert scratch == b"".join(strs)
    assert off.tolist() == uoff[inv].tolist()
    assert ln.tolist() == ulen[inv].tolist()
    assert registry.get("ts_text_python_values") == uniq.size
    assert registry.get("ts_text_native_rows") == 0


@pytest.mark.parametrize("enc_cls", [LTSVEncoder, RFC5424Encoder],
                         ids=["display_f64", "unix_to_rfc3339_ms"])
def test_the_other_output_formats_count_python_values(enc_cls):
    """rfc5424 -> LTSV renders with display_f64 and rfc5424 -> rfc5424
    with unix_to_rfc3339_ms: no native twin, so the dedup path."""
    lines = _rfc5424_lines("ms")
    encoder = enc_cls(CFG)
    decoder = RFC5424Decoder()
    got, fell_back = block_bytes("rfc5424", lines, encoder, decoder)
    assert got == scalar_bytes(decoder, encoder, lines)
    assert fell_back == 0
    assert registry.get("ts_text_python_values") == ROWS
    assert registry.get("ts_text_native_rows") == 0


# ---- the counters and the sub-span, through the handler ---------------------

def _handler_run(batches):
    """Each entry flushed as a batch of its own through the host block
    route (device decode, no device encoder); the sink's bytes."""
    _framed, data = handler_run(batches, extra='tpu_fuse = "off"\n')
    return data


def _ts_texts(rec):
    return [sp for sp in rec["sub"] if sp["stage"] == "ts_text"]


@needs_native
def test_a_batch_of_n_kept_rows_adds_n_once(monkeypatch):
    monkeypatch.setenv("FLOWGGER_DEVICE_ENCODE", "0")
    junk = b"-- MARK -- not a syslog line"
    batches = [_rfc5424_lines("us"), _rfc5424_lines("one")[:7] + [junk]]
    calls = []
    inc = registry.inc

    def spy(name, n=1):
        calls.append((name, n))
        return inc(name, n)

    monkeypatch.setattr(registry, "inc", spy)
    got = _handler_run(batches)
    assert got == scalar_bytes(RFC5424Decoder(), GELF,
                               [ln for b in batches for ln in b])
    # one increment a batch, of the rows the columnar encoder kept
    assert [n for name, n in calls if name == "ts_text_native_rows"] == [
        ROWS, 7]
    assert not [n for name, n in calls if name == "ts_text_python_values"]
    assert registry.get("input_lines") == ROWS + 8


def test_the_counters_are_in_the_registrys_snapshot_from_the_start():
    from flowgger_tpu.utils import metrics

    snap = registry.snapshot()
    for name in ("ts_text_native_rows", "ts_text_python_values"):
        assert snap[name] == 0
        assert metrics.classify_metric(name) == "counter"


@needs_native
def test_one_ts_text_sub_span_a_batch_under_encode(monkeypatch):
    monkeypatch.setenv("FLOWGGER_DEVICE_ENCODE", "0")
    batches = [_rfc5424_lines("ms"), _rfc5424_lines("ns")[:5]]
    obs_trace.tracer.configure("ring", ring=8)
    _handler_run(batches)
    recs = sorted(obs_trace.tracer.snapshot(), key=lambda r: r["bid"])
    assert len(recs) == len(batches)
    assert [[(sp["parent"], sp["rows"]) for sp in _ts_texts(r)]
            for r in recs] == [[("encode", ROWS)], [("encode", 5)]]
    for rec in recs:
        (sp,) = _ts_texts(rec)
        # on the fetcher's thread, inside the batch's encode stage
        enc = next(s for s in rec["spans"] if s["stage"] == "encode")
        assert sp["thread"] == enc["thread"]
        assert enc["t0"] <= sp["t0"] <= sp["t1"] <= enc["t1"]
        # and a small part of it: the text of 48 stamps
        assert sp["t1"] - sp["t0"] == pytest.approx(
            0, abs=max(2e-3, enc["t1"] - enc["t0"]))


def test_no_sub_span_is_recorded_without_a_batch_or_a_tracer():
    for mode in ("off", "ring"):
        obs_trace.tracer.configure(mode)
        obs_trace.tracer.bind(None)
        block_common.vals_scratch(_STAMPS, display_f64)
        assert obs_trace.tracer.snapshot() == []
        assert obs_trace.tracer.stats()["open"] == 0
