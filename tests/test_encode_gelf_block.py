"""Differential tests for the columnar block encoder: the vectorized
segment-gather GELF route (tpu/encode_gelf_block.py) must produce byte-
identical output to the scalar path (RFC5424Decoder → GelfEncoder →
merger.frame) for every line, in order — including fallback rows spliced
between vectorized runs and every framing mode."""

import queue

import numpy as np
import pytest

from flowgger_tpu.config import Config
from flowgger_tpu.block import EncodedBlock
from flowgger_tpu.decoders import DecodeError
from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
from flowgger_tpu.encoders.gelf import GelfEncoder
from flowgger_tpu.mergers import LineMerger, NulMerger, SyslenMerger
from flowgger_tpu.splitters import ScalarHandler
from flowgger_tpu.tpu import pack
from flowgger_tpu.tpu.batch import BatchHandler

from test_tpu_rfc5424 import CORPUS

ORACLE = RFC5424Decoder()
CFG_EMPTY = Config.from_string("")
ENC = GelfEncoder(CFG_EMPTY)


def scalar_frames(lines, merger):
    """Expected framed bytes per line via the scalar oracle."""
    out = []
    for ln in lines:
        try:
            line = ln.decode("utf-8")
        except UnicodeDecodeError:
            continue
        try:
            rec = ORACLE.decode(line)
        except DecodeError:
            continue
        payload = ENC.encode(rec)
        out.append(merger.frame(payload) if merger is not None else payload)
    return out


def block_output(lines, merger):
    """Run lines through a block-mode BatchHandler; returns the queue
    items (EncodedBlocks and/or bytes)."""
    tx = queue.Queue()
    h = BatchHandler(tx, ORACLE, ENC, Config.from_string(""),
                     fmt="rfc5424", start_timer=False, merger=merger)
    for ln in lines:
        h.handle_bytes(ln)
    h.flush()
    items = []
    while not tx.empty():
        items.append(tx.get_nowait())
    return items


@pytest.mark.parametrize("merger", [None, LineMerger(), NulMerger(),
                                    SyslenMerger()],
                         ids=["noop", "line", "nul", "syslen"])
def test_block_matches_scalar_corpus(merger):
    lines = [ln.encode("utf-8") for ln in CORPUS]
    want = b"".join(scalar_frames(lines, merger))
    items = block_output(lines, merger)
    got = b"".join(i.data if isinstance(i, EncodedBlock) else i for i in items)
    assert got == want


@pytest.mark.parametrize("merger", [LineMerger(), SyslenMerger()],
                         ids=["line", "syslen"])
def test_block_unframed_iteration(merger):
    lines = [ln.encode("utf-8") for ln in CORPUS]
    want = scalar_frames(lines, None)
    items = block_output(lines, merger)
    got = []
    for i in items:
        assert isinstance(i, EncodedBlock)
        got.extend(i.iter_unframed())
    assert got == want


def test_block_framed_bounds(merger=LineMerger()):
    lines = [ln.encode("utf-8") for ln in CORPUS]
    want = scalar_frames(lines, merger)
    items = block_output(lines, merger)
    got = []
    for i in items:
        got.extend(i.iter_framed())
    assert got == want


def test_all_tier_a_single_slice():
    """A clean batch (no fallbacks) must come out as one block whose
    data equals the scalar bytes."""
    lines = [
        f'<13>1 2015-08-05T15:53:45.{i:03d}Z host-{i} app{i} {i} mid '
        f'[sd@32473 iut="{i}" event="ev{i}"] message number {i}'.encode()
        for i in range(64)
    ]
    merger = NulMerger()
    items = block_output(lines, merger)
    assert len(items) == 1 and isinstance(items[0], EncodedBlock)
    assert items[0].data == b"".join(scalar_frames(lines, merger))
    assert len(items[0]) == 64


def test_dup_sd_names_fall_back():
    """Duplicate SD keys take last-wins dict semantics via the oracle."""
    lines = [
        b'<13>1 2015-08-05T15:53:45Z h a p m [id k="first" k="second"] m',
        b'<13>1 2015-08-05T15:53:45Z h a p m [id k="only"] m',
    ]
    merger = LineMerger()
    items = block_output(lines, merger)
    got = b"".join(i.data if isinstance(i, EncodedBlock) else i for i in items)
    assert got == b"".join(scalar_frames(lines, merger))
    assert b'"_k":"second"' in got


def test_sorted_sd_keys_vectorized():
    """Multi-pair rows must emit keys in sorted order from the
    vectorized tier (no fallback involved)."""
    lines = [
        b'<13>1 2015-08-05T15:53:45Z h a p m '
        b'[id zeta="z" alpha="a" mid="m"] m',
    ]
    merger = LineMerger()
    items = block_output(lines, merger)
    got = b"".join(i.data if isinstance(i, EncodedBlock) else i for i in items)
    assert got == b"".join(scalar_frames(lines, merger))
    assert got.index(b'"_alpha"') < got.index(b'"_mid"') < got.index(b'"_zeta"')


def test_control_chars_and_escapes():
    lines = [
        b"<13>1 2015-08-05T15:53:45Z h a p m - tab\there",
        b"<13>1 2015-08-05T15:53:45Z h a p m - quote\"back\\slash",
        b"<13>1 2015-08-05T15:53:45Z h a p m - ctrl\x01\x1fchars",
        b"<13>1 2015-08-05T15:53:45Z h a p m - trailing ws \x1c\x1d ",
    ]
    merger = LineMerger()
    items = block_output(lines, merger)
    got = b"".join(i.data if isinstance(i, EncodedBlock) else i for i in items)
    assert got == b"".join(scalar_frames(lines, merger))


@pytest.mark.parametrize("merger", [None, LineMerger(), SyslenMerger()],
                         ids=["noop", "line", "syslen"])
def test_numpy_fallback_engine_matches(merger, monkeypatch):
    """With the native assembler disabled, the numpy segment engine must
    produce the same bytes."""
    from flowgger_tpu import native

    monkeypatch.setattr(native, "gelf_rows_available", lambda: False)
    lines = [ln.encode("utf-8") for ln in CORPUS]
    want = b"".join(scalar_frames(lines, merger))
    items = block_output(lines, merger)
    got = b"".join(i.data if isinstance(i, EncodedBlock) else i for i in items)
    assert got == want


@pytest.mark.parametrize("merger", [None, LineMerger(), NulMerger(),
                                    SyslenMerger()],
                         ids=["noop", "line", "nul", "syslen"])
def test_passthrough_block_matches_scalar(merger):
    from flowgger_tpu.encoders.passthrough import PassthroughEncoder

    enc = PassthroughEncoder(Config.from_string(""))
    lines = [ln.encode("utf-8") for ln in CORPUS]
    want = []
    for ln in lines:
        try:
            line = ln.decode("utf-8")
            rec = ORACLE.decode(line)
            payload = enc.encode(rec)
        except Exception:
            continue
        want.append(merger.frame(payload) if merger is not None else payload)
    tx = queue.Queue()
    h = BatchHandler(tx, ORACLE, enc, Config.from_string(""),
                     fmt="rfc5424", start_timer=False, merger=merger)
    for ln in lines:
        h.handle_bytes(ln)
    h.flush()
    got = []
    while not tx.empty():
        item = tx.get_nowait()
        if isinstance(item, EncodedBlock):
            got.extend(item.iter_framed())
        else:
            got.append(merger.frame(item) if merger is not None else item)
    assert got == want


def test_fuzz_block_vs_scalar():
    """Random mutations of valid lines through both paths."""
    import random

    rng = random.Random(7)
    base = [ln for ln in CORPUS if ln]
    lines = []
    for _ in range(400):
        ln = rng.choice(base)
        b = bytearray(ln.encode("utf-8"))
        for _ in range(rng.randrange(3)):
            if not b:
                break
            op = rng.randrange(3)
            pos = rng.randrange(len(b))
            if op == 0:
                b[pos] = rng.randrange(256)
            elif op == 1:
                del b[pos]
            else:
                b.insert(pos, rng.randrange(256))
        lines.append(bytes(b))
    merger = LineMerger()
    items = block_output(lines, merger)
    got = b"".join(i.data if isinstance(i, EncodedBlock) else i for i in items)
    assert got == b"".join(scalar_frames(lines, merger))


# -- rfc5424 and ltsv block routes ------------------------------------------

def _route_check(encoder_cls, cfg_text, merger, extra_lines=()):
    cfg = Config.from_string(cfg_text)
    enc = encoder_cls(cfg)
    lines = [ln.encode("utf-8") for ln in CORPUS]
    lines += [ln for ln in extra_lines]
    want = []
    for ln in lines:
        try:
            line = ln.decode("utf-8")
            rec = ORACLE.decode(line)
            payload = enc.encode(rec)
        except Exception:
            continue
        want.append(merger.frame(payload) if merger is not None else payload)
    tx = queue.Queue()
    h = BatchHandler(tx, ORACLE, enc, cfg,
                     fmt="rfc5424", start_timer=False, merger=merger)
    for ln in lines:
        h.handle_bytes(ln)
    h.flush()
    got = []
    saw_block = False
    while not tx.empty():
        item = tx.get_nowait()
        if isinstance(item, EncodedBlock):
            saw_block = True
            got.extend(item.iter_framed())
        else:
            got.append(merger.frame(item) if merger is not None else item)
    assert saw_block
    assert got == want


@pytest.mark.parametrize("merger", [None, LineMerger(), NulMerger(),
                                    SyslenMerger()],
                         ids=["noop", "line", "nul", "syslen"])
def test_rfc5424_block_route_matches_scalar(merger):
    from flowgger_tpu.encoders.rfc5424 import RFC5424Encoder

    _route_check(RFC5424Encoder, "", merger)


@pytest.mark.parametrize("merger", [None, LineMerger(), SyslenMerger()],
                         ids=["noop", "line", "syslen"])
def test_ltsv_block_route_matches_scalar(merger):
    from flowgger_tpu.encoders.ltsv import LTSVEncoder

    _route_check(LTSVEncoder, "", merger, extra_lines=[
        b"<13>1 2015-08-05T15:53:45Z h a p m - msg\twith tab",
        b'<13>1 2015-08-05T15:53:45Z h a p m [id "co:lon"="v"] m',
    ])


def test_ltsv_block_route_with_extra():
    from flowgger_tpu.encoders.ltsv import LTSVEncoder

    _route_check(
        LTSVEncoder,
        '[output.ltsv_extra]\ncluster = "prod"\n"we:ird" = "v"\n',
        LineMerger())


def test_ltsv_block_newline_escaping():
    """Messages containing raw newlines (reachable via nul/syslen
    framing) must take the oracle path so LTSV's newline-to-space value
    escape applies."""
    from flowgger_tpu.encoders.ltsv import LTSVEncoder

    enc = LTSVEncoder(Config.from_string(""))
    lines = [b"<13>1 2015-08-05T15:53:45Z host app p m - msg with\nnewline",
             b"<13>1 2015-08-05T15:53:45Z host app p m - clean"]
    want = [enc.encode(ORACLE.decode(ln.decode())) for ln in lines]
    assert b"message:msg with newline" in want[0]
    tx = queue.Queue()
    h = BatchHandler(tx, ORACLE, enc, Config.from_string(""),
                     fmt="rfc5424", start_timer=False, merger=None)
    for ln in lines:
        h.handle_bytes(ln)
    h.flush()
    got = []
    while not tx.empty():
        item = tx.get_nowait()
        got.extend(item.iter_unframed() if isinstance(item, EncodedBlock)
                   else [item])
    assert got == want


def test_pipelined_flushes_preserve_order_and_drain():
    """Size-triggered flushes submit batches into the in-flight window
    (the fetcher thread fetches/encodes behind the ingest thread); order
    across batches is preserved and a final flush fences the window."""
    lines = [
        f'<13>1 2015-08-05T15:53:45.{i:03d}Z host{i} app {i} m '
        f'[sd@1 k="{i}"] message {i}'.encode()
        for i in range(40)
    ]
    merger = LineMerger()
    cfg = Config.from_string("[input]\ntpu_batch_size = 8\n")
    tx = queue.Queue()
    h = BatchHandler(tx, ORACLE, ENC, cfg, fmt="rfc5424",
                     start_timer=False, merger=merger)
    for ln in lines:
        h.handle_bytes(ln)  # triggers drain=False flushes every 8 lines
    h.flush()                      # EOF drain: fences the window
    assert h._window.pending() == 0
    got = []
    while not tx.empty():
        got.extend(tx.get_nowait().iter_framed())
    assert got == scalar_frames(lines, merger)


def test_inflight_batch_drains_on_timer():
    """A stream pausing exactly at a batch boundary must still emit the
    held batch within the flush window (the size flush re-arms the
    timer when it leaves a batch in flight)."""
    import time

    lines = [
        f'<13>1 2015-08-05T15:53:45Z host app {i} m - boundary {i}'.encode()
        for i in range(8)
    ]
    cfg = Config.from_string(
        "[input]\ntpu_batch_size = 8\ntpu_flush_ms = 50\n")
    tx = queue.Queue()
    h = BatchHandler(tx, ORACLE, ENC, cfg, fmt="rfc5424",
                     start_timer=True, merger=LineMerger())
    for ln in lines:
        h.handle_bytes(ln)  # exactly one full batch: flush(drain=False)
    deadline = time.time() + 5
    got = []
    while len(got) < 8 and time.time() < deadline:
        try:
            item = tx.get(timeout=0.2)
            got.extend(item.iter_framed())
        except queue.Empty:
            pass
    assert len(got) == 8  # arrived via the re-armed timer, no EOF flush


def test_rfc3164_gelf_block_route_matches_scalar():
    """rfc3164_tpu -> GELF block route: byte-identical to the scalar
    decoder+encoder across standard-layout, custom-layout (fallback),
    no-PRI, unicode and invalid lines."""
    from flowgger_tpu.decoders.rfc3164 import RFC3164Decoder

    dec = RFC3164Decoder(CFG_EMPTY)
    lines = [
        b"<34>Aug  5 15:53:45 testhost app[123]: standard layout line",
        b"<13>Oct 11 22:14:15 mymachine su: 'su root' failed",
        b"Aug  5 15:53:45 host prog: no pri line",
        b"<34>testhost: Aug 5 15:53:45: custom layout line",
        b"<34>Aug  5 15:53:45 host app: unicode m\xc3\xa9ssage",
        b"<34>Aug  5 15:53:45 host app: quote\"and\\backslash",
        b"completely invalid",
        b"",
        b"<34>Aug  5 15:53:45 emptyhost ",
    ]
    for merger in (None, LineMerger(), SyslenMerger()):
        want = []
        for ln in lines:
            try:
                rec = dec.decode(ln.decode("utf-8"))
                payload = ENC.encode(rec)
            except Exception:
                continue
            want.append(merger.frame(payload) if merger is not None
                        else payload)
        tx = queue.Queue()
        h = BatchHandler(tx, dec, ENC, CFG_EMPTY, fmt="rfc3164",
                         start_timer=False, merger=merger)
        for ln in lines:
            h.handle_bytes(ln)
        h.flush()
        got = []
        saw_block = False
        while not tx.empty():
            item = tx.get_nowait()
            if isinstance(item, EncodedBlock):
                saw_block = True
                got.extend(item.iter_framed())
            else:
                got.append(merger.frame(item) if merger is not None
                           else item)
        assert saw_block
        assert got == want, merger


def test_rfc3164_gelf_block_fuzz():
    from flowgger_tpu.decoders.rfc3164 import RFC3164Decoder
    import random

    dec = RFC3164Decoder(CFG_EMPTY)
    rng = random.Random(11)
    base = [
        b"<34>Aug  5 15:53:45 testhost app[123]: a valid legacy message",
        b"<13>Oct 11 22:14:15 mymachine su: 'su root' failed for lonvick",
        b"Aug  5 15:53:45 host prog: no pri either",
    ]
    lines = []
    for _ in range(300):
        b = bytearray(rng.choice(base))
        for _ in range(rng.randrange(4)):
            if b:
                b[rng.randrange(len(b))] = rng.randrange(256)
        lines.append(bytes(b))
    merger = LineMerger()
    want = []
    for ln in lines:
        try:
            rec = dec.decode(ln.decode("utf-8"))
            want.append(merger.frame(ENC.encode(rec)))
        except Exception:
            continue
    tx = queue.Queue()
    h = BatchHandler(tx, dec, ENC, CFG_EMPTY, fmt="rfc3164",
                     start_timer=False, merger=merger)
    for ln in lines:
        h.handle_bytes(ln)
    h.flush()
    got = []
    while not tx.empty():
        item = tx.get_nowait()
        got.extend(item.iter_framed() if isinstance(item, EncodedBlock)
                   else [merger.frame(item)])
    assert got == want


def test_block_routes_survive_all_empty_batch():
    """A batch of only empty messages (keep-alive newlines) must not
    crash any block route — empty chunks have zero-length prefix-count
    arrays."""
    from flowgger_tpu.decoders.rfc3164 import RFC3164Decoder
    from flowgger_tpu.encoders.ltsv import LTSVEncoder

    for fmt, dec, enc in (
        ("rfc5424", ORACLE, ENC),
        ("rfc5424", ORACLE, LTSVEncoder(CFG_EMPTY)),
        ("rfc3164", RFC3164Decoder(CFG_EMPTY), ENC),
    ):
        tx = queue.Queue()
        h = BatchHandler(tx, dec, enc, CFG_EMPTY, fmt=fmt,
                         start_timer=False, merger=LineMerger())
        for _ in range(4):
            h.handle_bytes(b"")
        h.flush()
        emitted = []
        while not tx.empty():
            item = tx.get_nowait()
            emitted.extend(item.iter_framed()
                           if isinstance(item, EncodedBlock) else [item])
        # every empty line is a decode error in all three configs
        assert emitted == [], (fmt, type(enc).__name__)


def test_ltsv_gelf_block_route_matches_scalar():
    """ltsv_tpu -> GELF block route: byte-identical to the scalar
    decoder+encoder for untyped LTSV, covering pairs, sorted keys,
    unix-literal and rfc3339 timestamps, missing message/level,
    escaping, and fallback rows."""
    from flowgger_tpu.decoders.ltsv import LTSVDecoder

    dec = LTSVDecoder(CFG_EMPTY)
    lines = [
        b"host:web1\ttime:2015-08-05T15:53:45Z\tmessage:hello ltsv",
        b"host:web2\ttime:1438790025.42\tzeta:z\talpha:a\tmessage:sorted",
        b"host:w\ttime:1438790025\tlevel:3\tuser:bob\tmessage:lvl",
        b"host:w\ttime:2015-08-05T15:53:45.25Z",     # no message
        b"host:w\ttime:1438790025\tk:v with \"quote\"\tmessage:esc",
        b"time:2015-08-05T15:53:45Z\tmessage:no host",      # error row
        b"host:w\ttime:1438790025\tnovalue\tmessage:notice",  # fallback
        b"host:w\ttime:1438790025\tdup:a\tdup:b\tmessage:dups",
        "host:w\ttime:1438790025\tmessage:unicodé".encode(),
        b"plain not ltsv at all",
    ]
    for merger in (None, LineMerger(), SyslenMerger()):
        want = []
        for ln in lines:
            try:
                rec = dec.decode(ln.decode("utf-8"))
                payload = ENC.encode(rec)
            except Exception:
                continue
            want.append(merger.frame(payload) if merger is not None
                        else payload)
        tx = queue.Queue()
        h = BatchHandler(tx, dec, ENC, CFG_EMPTY, fmt="ltsv",
                         start_timer=False, merger=merger)
        for ln in lines:
            h.handle_bytes(ln)
        h.flush()
        got = []
        saw_block = False
        while not tx.empty():
            item = tx.get_nowait()
            if isinstance(item, EncodedBlock):
                saw_block = True
                got.extend(item.iter_framed())
            else:
                got.append(merger.frame(item) if merger is not None
                           else item)
        assert saw_block
        assert got == want, merger


def test_ltsv_gelf_block_typed_schema_uses_record_path():
    """A typed ltsv_schema disables the block route (values need Python
    conversion) but output must still match the scalar path."""
    from flowgger_tpu.decoders.ltsv import LTSVDecoder

    cfg = Config.from_string('[input.ltsv_schema]\ncounter = "u64"\n')
    dec = LTSVDecoder(cfg)
    lines = [b"host:w\ttime:1438790025\tcounter:42\tmessage:typed"]
    want = [ENC.encode(dec.decode(lines[0].decode()))]
    tx = queue.Queue()
    h = BatchHandler(tx, dec, ENC, cfg, fmt="ltsv",
                     start_timer=False, merger=None)
    h.handle_bytes(lines[0])
    h.flush()
    got = []
    while not tx.empty():
        item = tx.get_nowait()
        got.extend(item.iter_unframed() if isinstance(item, EncodedBlock)
                   else [item])
    assert got == want
    assert b'"_counter":42' in got[0]


def test_ltsv_gelf_block_repeated_special_keys():
    """Repeated special keys: earlier occurrences must not leak into the
    pair table, and a bad earlier occurrence must error like the scalar
    path (both via oracle fallback)."""
    from flowgger_tpu.decoders.ltsv import LTSVDecoder

    dec = LTSVDecoder(CFG_EMPTY)
    lines = [
        b"host:a\thost:b\ttime:1438790025\tmessage:x",
        b"time:junk\ttime:1438790025\thost:w\tmessage:y",
        b"host:w\ttime:1438790025\tmessage:clean",
    ]
    want = []
    for ln in lines:
        try:
            want.append(ENC.encode(dec.decode(ln.decode())))
        except Exception:
            continue
    tx = queue.Queue()
    h = BatchHandler(tx, dec, ENC, CFG_EMPTY, fmt="ltsv",
                     start_timer=False, merger=None)
    for ln in lines:
        h.handle_bytes(ln)
    h.flush()
    got = []
    while not tx.empty():
        item = tx.get_nowait()
        got.extend(item.iter_unframed() if isinstance(item, EncodedBlock)
                   else [item])
    assert got == want
    assert not any(b'"_host"' in g for g in got)


def test_gelf_gelf_block_route_matches_scalar():
    """gelf_tpu -> GELF re-encode block route: byte-identical to the
    scalar decoder+encoder for canonical inputs, with every exotic case
    (escapes, floats, version variants, missing timestamp, dup keys,
    control chars) through the oracle."""
    from flowgger_tpu.decoders.gelf import GelfDecoder

    dec = GelfDecoder(CFG_EMPTY)
    lines = [
        b'{"version":"1.1","host":"h1","short_message":"msg one",'
        b'"timestamp":1438790025.42,"level":5,"_extra":"kept"}',
        b'{"host":"h2","timestamp":1438790026,"zeta":"z","alpha":"a",'
        b'"num":42,"neg":-7,"flag":true,"off":false,"nil":null}',
        b'{"host":"h3","timestamp":1438790027,"full_message":"full text",'
        b'"short_message":""}',
        b'{"host":"","timestamp":1438790028}',            # unknown host
        b'{"host":"h5","timestamp":1438790029,"f":3.25}',  # float: oracle
        b'{"host":"h6","timestamp":1438790030,"e":"with \\"esc\\""}',
        b'{"host":"h7"}',                         # no ts: oracle (now())
        b'{"timestamp":1438790031}',              # missing host: error
        b'{"host":"h8","timestamp":1438790032,"version":"2.0"}',  # error
        b'{"host":"h9","timestamp":1438790033,"k":"v","_k":"dup"}',
        b'not json',
        '{"host":"hü","timestamp":1438790034}'.encode(),
    ]
    for merger in (None, LineMerger(), SyslenMerger()):
        want = []
        for ln in lines:
            try:
                rec = dec.decode(ln.decode("utf-8"))
                payload = ENC.encode(rec)
            except Exception:
                continue
            want.append(merger.frame(payload) if merger is not None
                        else payload)
        tx = queue.Queue()
        h = BatchHandler(tx, dec, ENC, CFG_EMPTY, fmt="gelf",
                         start_timer=False, merger=merger)
        for ln in lines:
            h.handle_bytes(ln)
        h.flush()
        got = []
        saw_block = False
        while not tx.empty():
            item = tx.get_nowait()
            if isinstance(item, EncodedBlock):
                saw_block = True
                got.extend(item.iter_framed())
            else:
                got.append(merger.frame(item) if merger is not None
                           else item)
        assert saw_block
        # rows with now() timestamps differ per call: compare only the
        # deterministic rows (drop the no-ts row from both sides)
        got2 = [g for g in got if b'"host":"h7"' not in g]
        want2 = [w for w in want if b'"host":"h7"' not in w]
        assert got2 == want2, merger
        assert len(got) == len(want)


def test_gelf_gelf_block_malformed_numbers_and_versions():
    """Tokenizer-accepted junk the JSON oracle rejects (or parses
    differently) must take the oracle path, never crash a batch or emit
    diverging bytes."""
    from flowgger_tpu.decoders.gelf import GelfDecoder

    dec = GelfDecoder(CFG_EMPTY)
    lines = [
        b'{"host":"h","timestamp":0x10}',
        b'{"host":"h","timestamp":1.2.3}',
        b'{"host":"h","timestamp":01}',
        b'{"host":"h","timestamp":1.}',
        b'{"host":"h","timestamp":1_0}',
        b'{"host":"h","timestamp":-0}',
        b'{"host":"h","timestamp":1,"k":12x3}',
        b'{"host":"h","timestamp":1,"k":-}',
        b'{"host":"h","timestamp":1,"k":-0}',
        b'{"host":"h","timestamp":1,"version":"1x1"}',
        b'{"host":"h","timestamp":1,"good":"row"}',
    ]
    want = []
    for ln in lines:
        try:
            want.append(ENC.encode(dec.decode(ln.decode())))
        except Exception:
            continue
    tx = queue.Queue()
    h = BatchHandler(tx, dec, ENC, CFG_EMPTY, fmt="gelf",
                     start_timer=False, merger=None)
    for ln in lines:
        h.handle_bytes(ln)
    h.flush()
    got = []
    while not tx.empty():
        item = tx.get_nowait()
        got.extend(item.iter_unframed() if isinstance(item, EncodedBlock)
                   else [item])
    assert got == want


def test_auto_gelf_block_merges_classes_in_order():
    """auto_tpu with a GELF sink block-encodes every class and merges
    the buffers back into input order, byte-identical to routing each
    line through its scalar decoder."""
    from flowgger_tpu.decoders.gelf import GelfDecoder
    from flowgger_tpu.decoders.ltsv import LTSVDecoder
    from flowgger_tpu.decoders.rfc3164 import RFC3164Decoder
    from flowgger_tpu.tpu.autodetect import (
        F_GELF, F_LTSV, F_RFC3164, F_RFC5424, classify,
    )

    decoders = {F_RFC5424: ORACLE, F_RFC3164: RFC3164Decoder(CFG_EMPTY),
                F_LTSV: LTSVDecoder(CFG_EMPTY), F_GELF: GelfDecoder(CFG_EMPTY)}
    lines = [
        b"<13>1 2015-08-05T15:53:45Z h5424 app 1 2 - rfc5424 one",
        b'{"host":"hg","timestamp":1438790025,"k":"v"}',
        b"host:hl\ttime:2015-08-05T15:53:45Z\tmessage:ltsv here",
        b"<34>Aug  5 15:53:45 h3164 app: legacy line",
        b"<13>1 2015-08-05T15:53:45Z h5424b app 1 2 - rfc5424 two",
        b"plain text goes legacy",
        b"completely { broken ] line <",
        b'{"host":"hg2","timestamp":1438790026,"level":2}',
    ]
    for merger in (None, LineMerger(), SyslenMerger()):
        want = []
        for ln in lines:
            try:
                rec = decoders[classify(ln)].decode(ln.decode())
                payload = ENC.encode(rec)
            except Exception:
                continue
            want.append(merger.frame(payload) if merger is not None
                        else payload)
        tx = queue.Queue()
        h = BatchHandler(tx, ORACLE, ENC, CFG_EMPTY, fmt="auto",
                         start_timer=False, merger=merger)
        for ln in lines:
            h.handle_bytes(ln)
        h.flush()
        got = []
        saw_block = False
        while not tx.empty():
            item = tx.get_nowait()
            if isinstance(item, EncodedBlock):
                saw_block = True
                got.extend(item.iter_framed())
            else:
                got.append(merger.frame(item) if merger is not None
                           else item)
        assert saw_block
        assert got == want, merger


def test_rfc3164_passthrough_block_route_matches_scalar():
    from flowgger_tpu.decoders.rfc3164 import RFC3164Decoder
    from flowgger_tpu.encoders.passthrough import PassthroughEncoder

    dec = RFC3164Decoder(CFG_EMPTY)
    enc = PassthroughEncoder(CFG_EMPTY)
    lines = [
        b"<34>Aug  5 15:53:45 testhost app[123]: standard layout line",
        b"Aug  5 15:53:45 host prog: no pri line  ",
        b"<34>testhost: Aug 5 15:53:45: custom layout line",
        b"<34>Aug  5 15:53:45 host app: unicode m\xc3\xa9ssage",
        b"completely invalid",
    ]
    for merger in (None, LineMerger(), SyslenMerger()):
        want = []
        for ln in lines:
            try:
                payload = enc.encode(dec.decode(ln.decode("utf-8")))
            except Exception:
                continue
            want.append(merger.frame(payload) if merger is not None
                        else payload)
        tx = queue.Queue()
        h = BatchHandler(tx, dec, enc, CFG_EMPTY, fmt="rfc3164",
                         start_timer=False, merger=merger)
        for ln in lines:
            h.handle_bytes(ln)
        h.flush()
        got = []
        saw_block = False
        while not tx.empty():
            item = tx.get_nowait()
            if isinstance(item, EncodedBlock):
                saw_block = True
                got.extend(item.iter_framed())
            else:
                got.append(merger.frame(item) if merger is not None
                           else item)
        assert saw_block
        assert got == want, merger


def test_ltsv_gelf_block_typed_schema_fast_tier():
    """bool/u64/i64-typed ltsv_schema keys stay on the fast tier when
    canonical (bare literals in the GELF output); f64 and non-canonical
    values drop to the oracle — all byte-identical to the scalar path."""
    from flowgger_tpu.decoders.ltsv import LTSVDecoder
    from flowgger_tpu.utils.metrics import registry

    base_fallbacks = registry.get("fallback_rows")

    cfg = Config.from_string(
        '[input.ltsv_schema]\ncounter = "u64"\ndelta = "i64"\n'
        'flag = "bool"\nratio = "f64"\nname = "string"\n')
    dec = LTSVDecoder(cfg)
    lines = [
        b"host:h\ttime:1438790025\tcounter:42\tflag:true\tmessage:m1",
        b"host:h\ttime:1438790025\tdelta:-7\tname:xyz\tmessage:m2",
        b"host:h\ttime:1438790025\tcounter:007\tmessage:bad int",
        b"host:h\ttime:1438790025\tflag:TRUE\tmessage:bad bool",
        b"host:h\ttime:1438790025\tratio:2.5\tmessage:canonical f64",
        b"host:h\ttime:1438790025\tratio:-0.125\tmessage:negative f64",
        b"host:h\ttime:1438790025\tratio:2.50\tmessage:padded f64 oracle",
        b"host:h\ttime:1438790025\tratio:1e1\tmessage:exp f64 oracle",
        b"host:h\ttime:1438790025\tratio:inf\tmessage:inf via oracle",
        b"host:h\ttime:1438790025\tratio:x\tmessage:bad f64 dropped",
        b"host:h\ttime:1438790025\tdelta:-0\tmessage:minus zero",
        b"host:h\ttime:1438790025\tcounter:+5\tmessage:plus sign",
    ]
    want = []
    for ln in lines:
        try:
            want.append(ENC.encode(dec.decode(ln.decode())))
        except Exception:
            continue
    tx = queue.Queue()
    h = BatchHandler(tx, dec, ENC, cfg, fmt="ltsv",
                     start_timer=False, merger=None)
    for ln in lines:
        h.handle_bytes(ln)
    h.flush()
    got = []
    saw_block = False
    while not tx.empty():
        item = tx.get_nowait()
        if isinstance(item, EncodedBlock):
            saw_block = True
            got.extend(item.iter_unframed())
        else:
            got.append(item)
    assert saw_block
    assert got == want
    joined = b"|".join(got)
    assert b'"_counter":42' in joined      # bare number
    assert b'"_flag":true' in joined       # bare bool
    assert b'"_delta":-7' in joined
    assert b'"_ratio":2.5,' in joined      # bare canonical f64
    assert b'"_ratio":-0.125,' in joined
    # the two canonical-f64 lines (plus m1/m2) stayed on the fast tier;
    # every other line re-ran the oracle
    assert registry.get("fallback_rows") - base_fallbacks == len(lines) - 4


def test_ltsv_big_schema_declines_to_record_path():
    """A >8-key schema makes the block route decline after submit; the
    handler must fall back to the Record path, not crash."""
    from flowgger_tpu.decoders.ltsv import LTSVDecoder

    keys = "\n".join(f'k{i} = "u64"' for i in range(9))
    cfg = Config.from_string(f"[input.ltsv_schema]\n{keys}\n")
    dec = LTSVDecoder(cfg)
    lines = [b"host:h\ttime:1438790025\tk0:1\tmessage:big schema"]
    want = [ENC.encode(dec.decode(lines[0].decode()))]
    tx = queue.Queue()
    h = BatchHandler(tx, dec, ENC, cfg, fmt="ltsv",
                     start_timer=False, merger=None)
    h.handle_bytes(lines[0])
    h.flush()
    got = []
    while not tx.empty():
        item = tx.get_nowait()
        got.extend(item.iter_unframed() if isinstance(item, EncodedBlock)
                   else [item])
    assert got == want


@pytest.mark.parametrize("merger", [None, SyslenMerger()],
                         ids=["noop", "syslen"])
def test_rfc5424_block_numpy_fallback_engine(merger, monkeypatch):
    """With the native r5 assembler disabled, the numpy segment engine
    must produce the same bytes (it is the production path on
    toolchain-less deployments)."""
    from flowgger_tpu import native
    from flowgger_tpu.encoders.rfc5424 import RFC5424Encoder

    monkeypatch.setattr(native, "r5_rows_available", lambda: False)
    _route_check(RFC5424Encoder, "", merger)


# -- rows longer than the device's row ---------------------------------------
# An over-length row is clipped for the device (input.tpu_max_line_len,
# 512) and decoded as clipped.  It stays on the columnar encoder where
# the clip holds the whole header and a non-blank byte of MSG; MSG's end
# and the tail's purity are read on the host.  Every case holds the
# block's bytes against the scalar oracle's and says which way the row
# went (overlen_rows_kept against splice_rows_overlen).

_OL_HEAD = (b"<131>1 2026-09-21T10:00:00.000001Z dn01.ams.example.net "
            b"hadoop-datanode 4242 - ")
_OL_SHORT = _OL_HEAD + b"- a short line"
_OL_WIDTH = 512


def _ol(head, length, tail=b"", fill=b"x"):
    """``head``, filled to ``length`` bytes, ending in ``tail``."""
    pad = length - len(head) - len(tail)
    assert pad >= 0
    line = head + fill * pad + tail
    assert len(line) == length
    return line


def _ol_sd_closing_at(pos):
    """A line whose structured data's ``]`` is byte ``pos`` (from 0),
    followed by a blank and MSG."""
    line = _ol(_OL_HEAD + b'[mdc@18060 thread="', pos + 1, b'"]') \
        + b" msg begins" + b"z" * 200
    assert line[pos:pos + 1] == b"]"
    return line


_TRACE = (b"- ERROR java.io.IOException: Broken pipe" +
          b"".join(b"#012#011at org.apache.hadoop.hdfs.Frame%d.run"
                   b"(Frame%d.java:%d)" % (i, i, 100 + i)
                   for i in range(40)))


def _ol_trace_clipped_inside_012():
    """A folded stack trace with the clip between ``#0`` and ``12``."""
    head = _OL_HEAD + b"- "
    at = _TRACE.index(b"#012", _OL_WIDTH - len(head) - 60)
    line = _ol(head, _OL_WIDTH - 2 - at, fill=b"y") + _TRACE
    assert line[_OL_WIDTH - 2:_OL_WIDTH + 2] == b"#012"
    return line


# name -> (line, the way it goes: "kept", "spliced", or None where the
# row is not over-length)
_OL_CASES = {
    "512-bytes": (_ol(_OL_HEAD + b"- m", 512, b" end"), None),
    "513-bytes": (_ol(_OL_HEAD + b"- m", 513, b" end"), "kept"),
    "4800-bytes": (_ol(_OL_HEAD + b"- m", 4800, b" end"), "kept"),
    "clip-in-the-header": (
        b"<131>1 2026-09-21T10:00:00.000001Z " + b"h" * 600
        + b" app 1 - - msg", "spliced"),
    "clip-in-an-sd-value": (
        _ol(_OL_HEAD + b'[mdc@18060 thread="', 700, b'"] msg'), "spliced"),
    "clip-on-the-sds-closing-bracket": (_ol_sd_closing_at(511), "spliced"),
    "clip-before-the-sds-closing-bracket": (_ol_sd_closing_at(512),
                                            "spliced"),
    "clip-on-the-blank-after-the-sd": (_ol_sd_closing_at(510), "spliced"),
    "msg-begins-on-the-clips-last-byte": (_ol_sd_closing_at(509), "kept"),
    "nil-sd-on-the-clips-last-byte": (
        _ol(_OL_HEAD[:-3], 512, b" - -", fill=b"4") + b" msg" + b"z" * 90,
        "spliced"),
    "clip-inside-a-folded-newline": (_ol_trace_clipped_inside_012(), "kept"),
    "tail-all-blanks": (_ol(_OL_HEAD + b"- m", 512) + b" " * 300, "kept"),
    "blanks-on-both-sides-of-the-clip": (
        _OL_HEAD + b"- only this" + b" " * 800, "kept"),
    "tail-ends-in-blanks-and-controls": (
        _ol(_OL_HEAD + b"- m", 700, b"end \x1c\x1d\x1e\x1f \t\r"), "kept"),
    "tail-all-controls-28-31": (
        _ol(_OL_HEAD + b"- m", 512) + b"\x1c\x1d\x1e\x1f" * 9, "kept"),
    "msg-begins-past-the-clip": (
        _OL_HEAD + b"-" + b" " * 600 + b"hello", "spliced"),
    "one-utf8-character-in-the-tail": (
        _ol(_OL_HEAD + b"- m", 700, "café au lait".encode()),
        "spliced"),
    "one-utf8-character-in-the-clip": (
        _ol(_OL_HEAD + "- café ".encode(), 700), "spliced"),
    "json-escapes-in-the-tail": (
        _ol(_OL_HEAD + b"- m", 900,
            b'q"uo\\te \x01\x08\x0c\x0b\t\x7f "\\" end'), "kept"),
    "seven-pairs": (
        _ol(_OL_HEAD + b'[mdc@18060 g="7" f="6" e="5" d="4" c="3" '
            b'b="2" a="1"] m', 1300, b" end"), "kept"),
    "escaped-sd-value": (
        _ol(_OL_HEAD + b'[mdc@18060 thread="main" '
            b'class="te\\st sc\\"ript \\] x"] m', 900, b" tail"),
        "kept-native"),
    "refused-by-the-decoder": (
        _ol(_OL_HEAD + b"- m", 700).replace(b"2026-09-21", b"2026-13-21"),
        "spliced"),
}

_OL_MERGERS = {"line": LineMerger, "nul": NulMerger, "syslen": SyslenMerger}


@pytest.fixture
def host_block_route(monkeypatch):
    """No device encoder in the host block encoder's way (with
    ``tpu_fuse = "off"`` in the handler's config)."""
    monkeypatch.setenv("FLOWGGER_DEVICE_ENCODE", "0")


def _ol_block_bytes(lines, merger):
    tx = queue.Queue()
    h = BatchHandler(tx, ORACLE, ENC,
                     Config.from_string('[input]\ntpu_fuse = "off"\n'),
                     fmt="rfc5424", start_timer=False, merger=merger)
    for ln in lines:
        h.handle_bytes(ln)
    h.flush()
    h.close()
    got = []
    while not tx.empty():
        item = tx.get_nowait()
        got.append(item.data if isinstance(item, EncodedBlock) else item)
    return b"".join(got)


def _ol_way(way, engine):
    if way == "kept-native":
        return "kept" if engine == "native" else "spliced"
    return way


@pytest.mark.parametrize("frame", sorted(_OL_MERGERS))
@pytest.mark.parametrize("case", sorted(_OL_CASES))
def test_over_length_row_matches_scalar(case, frame, gelf_engine,
                                        host_block_route):
    from flowgger_tpu.utils.metrics import registry

    line, way = _OL_CASES[case]
    way = _ol_way(way, gelf_engine)
    assert (len(line) > _OL_WIDTH) == (way is not None)
    merger = _OL_MERGERS[frame]()
    lines = [_OL_SHORT, line, _OL_SHORT]
    want = b"".join(scalar_frames(lines, merger))
    registry.reset()
    assert _ol_block_bytes(lines, merger) == want
    assert registry.get("overlen_rows") == int(way is not None)
    assert registry.get("overlen_rows_kept") == int(way == "kept")
    assert registry.get("splice_rows_overlen") == int(way == "spliced")
    assert registry.get("splice_rows") == int(way == "spliced")


@pytest.mark.parametrize("frame", sorted(_OL_MERGERS))
def test_every_over_length_case_in_one_batch(frame, gelf_engine,
                                             host_block_route):
    """Adjacent over-length rows: each tail is read as its own, also
    where it is all blanks and its neighbour's is not."""
    from flowgger_tpu.utils.metrics import registry

    merger = _OL_MERGERS[frame]()
    names = sorted(_OL_CASES)
    lines = [_OL_CASES[k][0] for k in names] + [_OL_SHORT] \
        + [_OL_CASES[k][0] for k in reversed(names)]
    ways = [_ol_way(_OL_CASES[k][1], gelf_engine) for k in names]
    registry.reset()
    assert _ol_block_bytes(lines, merger) == b"".join(
        scalar_frames(lines, merger))
    assert registry.get("overlen_rows_kept") == 2 * ways.count("kept")
    assert registry.get("splice_rows_overlen") == 2 * ways.count("spliced")


def test_the_tail_scan_reads_each_rows_own_tail():
    """``_overlen_tails`` alone, against a loop over the rows."""
    from flowgger_tpu.tpu.encode_gelf_block import _overlen_tails

    rng = np.random.default_rng(34)
    blanks = np.array([9, 10, 11, 12, 13, 28, 29, 30, 31, 32], np.uint8)
    rows, lens = [], []
    for k in range(200):
        n = int(rng.integers(17, 400))
        row = rng.integers(33, 127, n).astype(np.uint8)
        if k % 3 == 0:      # trailing blanks, sometimes the whole tail
            cut = int(rng.integers(0, n - 16)) if k % 2 else 16
            row[cut:] = rng.choice(blanks, n - cut)
        if k % 7 == 0:
            row[int(rng.integers(16, n))] = int(rng.integers(128, 256))
        rows.append(row)
        lens.append(n)
    chunk = np.concatenate(rows)
    lens = np.array(lens, dtype=np.int64)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    pick = np.flatnonzero(np.arange(200) % 2 == 0)
    clip_end = np.full(pick.size, 7)
    ascii_tail, end = _overlen_tails(chunk, starts, lens, 16, pick, clip_end)
    for j, i in enumerate(pick.tolist()):
        tail = bytes(rows[i][16:])
        assert bool(ascii_tail[j]) == tail.isascii()
        if tail.isascii():
            kept = tail.decode().rstrip()
            assert int(end[j]) == (16 + len(kept) if kept else 7)


@pytest.mark.parametrize("frame", sorted(_OL_MERGERS))
def test_over_length_rows_with_gelf_extra_take_the_same_extension(frame):
    """``[output.gelf_extra]`` runs on the numpy engine, which reads the
    same spans: its over-length rows stay columnar too."""
    from flowgger_tpu.tpu import rfc5424
    from flowgger_tpu.tpu.encode_gelf_block import encode_rfc5424_gelf_block
    from flowgger_tpu.utils.metrics import registry

    enc = GelfEncoder(Config.from_string(
        '[output.gelf_extra]\nZone = "eu"\nkind = "syslog"\nzzz = "last"\n'))
    merger = _OL_MERGERS[frame]()
    names = sorted(_OL_CASES)
    lines = [_OL_SHORT] + [_OL_CASES[k][0] for k in names]
    want = []
    for ln in lines:
        try:
            want.append(merger.frame(enc.encode(ORACLE.decode(ln.decode()))))
        except DecodeError:
            pass
    packed = pack.pack_lines_2d(lines, _OL_WIDTH)
    host_out = rfc5424.decode_rfc5424_host(packed[0], packed[1])
    registry.reset()
    res = encode_rfc5424_gelf_block(packed[2], packed[3], packed[4],
                                    host_out, packed[5], _OL_WIDTH, enc,
                                    merger)
    assert res.block.data == b"".join(want)
    ways = [_ol_way(_OL_CASES[k][1], "numpy") for k in names]
    assert registry.get("overlen_rows_kept") == ways.count("kept")
    assert registry.get("splice_rows_overlen") == ways.count("spliced")
