"""Pallas structural-pass kernels (tpu/pallas_kernels.py): interpret-mode
byte-identity differentials against the jnp tiers and the host scalar
oracles, the watchdog-decline fallback ladder, the AOT ``pallas``
artifact family, and the end-to-end framing × format × lane matrix.

Every kernel runs under ``interpret=True`` here — this container has no
TPU, and the Pallas interpreter executes the *same kernel bodies* that
Mosaic lowers on hardware, so byte identity in interpret mode is the
honest CPU-box proxy for the VMEM kernels (the FC03 contract declared
in pallas_kernels.py points at the four ``test_*_match*`` ids below).
The oracles are the ones the rest of the tree already trusts:
``pack.split_chunk`` / ``splitters._scan_syslen_region`` for framing,
the lax/sum ``structural_index`` for the stage-1 classifier, and the
``decode_*_jit`` kernels (themselves FC03-bound to the scalar
decoders) for the decode passes.

Interpreting a kernel costs minutes-per-geometry, so the heavyweight
differentials (structural classifier, decode, raw ingest, fused
entries, e2e matrix, AOT round trip) are slow-marked: tier-1 keeps the
span kernels and the decline/hysteresis ladders, and ci.sh runs the
slow half in its own capped Pallas step.
"""

import queue

import numpy as np
import pytest

from flowgger_tpu.block import EncodedBlock
from flowgger_tpu.config import Config, ConfigError
from flowgger_tpu.decoders.jsonl import JSONLDecoder
from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
from flowgger_tpu.encoders.gelf import GelfEncoder
from flowgger_tpu.encoders.ltsv import LTSVEncoder
from flowgger_tpu.obs import events
from flowgger_tpu.splitters import (
    LineSplitter,
    NulSplitter,
    SyslenSplitter,
    _scan_syslen_region,
)
from flowgger_tpu.tpu import framing, pack
from flowgger_tpu.tpu import jsonidx as JI
from flowgger_tpu.tpu import jsonl as TJ
from flowgger_tpu.tpu import pallas_kernels as PK
from flowgger_tpu.tpu import rfc5424 as R
from flowgger_tpu.tpu.batch import BatchHandler
from flowgger_tpu.utils.metrics import registry

MAX_LEN = 128
CFG = Config.from_string("")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    from flowgger_tpu.tpu import device_common

    registry.reset()
    events.journal.reset()
    # run the framing probes inline (test_framing.py precedent: an
    # earlier test's hung compile may hold the watchdog semaphore),
    # and the decode tier's pallas slots too — interpret-mode compiles
    # exceed the 15s first-compile deadline on small CI boxes, and
    # these tests assert the ENGAGED tier (the decline ladder has its
    # own tests).  Non-pallas slots keep the real watchdog.
    monkeypatch.setattr(framing, "_watchdogged", lambda slot, fn: fn())
    orig_gcc = device_common.guarded_compile_call

    def _gcc(name, fn, *args, **kw):
        if name.startswith("pallas/"):
            return fn(*args)
        return orig_gcc(name, fn, *args, **kw)

    monkeypatch.setattr(device_common, "guarded_compile_call", _gcc)
    framing._PALLAS_STATE.clear()
    PK._DECODE_STATE.clear()
    yield
    PK.set_mode("off")
    framing._PALLAS_STATE.clear()
    PK._DECODE_STATE.clear()


# ---------------------------------------------------------------------------
# framing span kernels vs the jnp tier and the host splitters
# (FC03 DIFF_TESTs)
# ---------------------------------------------------------------------------

def test_sep_spans_match_jnp_and_host():
    rng = np.random.default_rng(7)
    for t in range(10):
        n = rng.integers(1, 30)
        lines = [bytes(rng.integers(32, 127, rng.integers(0, 60))
                       .astype(np.uint8)) for _ in range(n)]
        crlf = t % 3 == 0
        blob = b"".join(ln + (b"\r\n" if crlf else b"\n")
                        for ln in lines)
        if t % 5 == 0:
            blob += b"partial-tail"
        B = len(blob) + int(rng.integers(0, 64))
        reg = np.zeros(B, np.uint8)
        reg[:len(blob)] = np.frombuffer(blob, np.uint8)
        out = PK.frame_sep_spans_pallas(
            reg, np.int32(len(blob)), sep=10, strip_cr=True, ncap=64,
            interpret=True)
        # host oracle: the numpy separator scan behind split_chunk
        hs, hl, hn, carry = pack.split_chunk(blob, strip_cr=True)
        consumed = len(blob) - len(carry)
        assert int(out["n"]) == hn
        assert int(out["consumed"]) == consumed
        for i in range(hn):
            assert int(out["starts"][i]) == int(hs[i]), (t, i)
            assert int(out["lens"][i]) == int(hl[i]), (t, i)
    # jnp-tier full-key identity including the overflow flag
    B = 4096
    region = np.frombuffer((b"x\n" * 100).ljust(B, b"\0"), np.uint8)
    a = framing.frame_sep_spans_jit(region, 200, sep=10, strip_cr=True,
                                    ncap=64)
    b = PK.frame_sep_spans_pallas(region, 200, sep=10, strip_cr=True,
                                  ncap=64, interpret=True)
    for k in ("starts", "lens", "n", "consumed", "overflow"):
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


def test_syslen_spans_match_jnp_and_host():
    rng = np.random.default_rng(1)
    B, ncap = 4096, 64

    def mk(recs, extra=b""):
        raw = b"".join(b"%d " % len(r) + r for r in recs) + extra
        buf = np.zeros(B, np.uint8)
        buf[:len(raw)] = np.frombuffer(raw, np.uint8)
        return buf, len(raw)

    def cmp(region, rlen, tag):
        a = framing.frame_syslen_spans_jit(region, rlen, ncap=ncap)
        b = PK.frame_syslen_spans_pallas(region, rlen, ncap=ncap,
                                         interpret=True)
        ad, bd = bool(a["decline"]), bool(b["decline"])
        assert ad == bd, (tag, "decline", ad, bd)
        if not ad:
            for k in ("starts", "lens", "n", "consumed", "err"):
                assert np.array_equal(np.asarray(a[k]),
                                      np.asarray(b[k])), (tag, k)

    for trial in range(12):
        nrec = int(rng.integers(0, 12))
        recs = [bytes(rng.integers(33, 127, size=int(rng.integers(0, 50)))
                      .astype(np.uint8)) for _ in range(nrec)]
        extra = [b"", b"12", b"12 abc", b"garbage no prefix",
                 b"0 "][int(rng.integers(0, 5))]
        cmp(*mk(recs, extra), trial)
    # the hand-picked edges: empty, exact-one, partial body, >9-digit
    # prefix (host-owned decline), space at offset 0 (malformed),
    # empty records, ncap overflow, chain-then-garbage, leading zero
    cmp(*mk([]), "empty")
    cmp(*mk([], b"5 hello"), "exact-one")
    cmp(*mk([], b"5 hel"), "partial-body")
    cmp(*mk([], b"9999999999 x"), "too-long-prefix")
    cmp(*mk([], b" leading-space"), "space-at-0")
    cmp(*mk([b""] * 5), "empty-records")
    cmp(*mk([b"x"] * 100), "overflow")
    cmp(*mk([], b"3 abc12 nodigitspace"), "chain-then-garbage")
    cmp(*mk([], b"03 abc"), "leading-zero")
    # host-oracle spot check (the scalar scan the splitter rides)
    blob = b"5 hello14 hello world!!3 abc12 trunc"
    hs, hl, hn, hcons, herr = _scan_syslen_region(blob)
    out = PK.frame_syslen_spans_pallas(
        np.frombuffer(blob, np.uint8), np.int32(len(blob)), ncap=64,
        interpret=True)
    assert not bool(out["decline"])
    assert int(out["n"]) == hn and int(out["consumed"]) == hcons
    assert bool(out["err"]) == herr
    assert np.array_equal(np.asarray(out["starts"])[:hn], hs)
    assert np.array_equal(np.asarray(out["lens"])[:hn], hl)


def test_frame_gather_matches_host_pack():
    rng = np.random.default_rng(3)
    recs = [b"x" * int(k) for k in rng.integers(0, 100, 30)]
    blob = b"".join(b"%d " % len(r) + r for r in recs)
    reg = np.frombuffer(blob, np.uint8)
    pos, starts, lens = 0, [], []
    for r in recs:
        pos += len(b"%d " % len(r))
        starts.append(pos)
        lens.append(len(r))
        pos += len(r)
    st = np.array(starts + [0] * (64 - len(starts)), np.int32)
    ln = np.array(lens + [0] * (64 - len(lens)), np.int32)
    bat, lens_o = PK.frame_gather_pallas(reg, st, ln, max_len=MAX_LEN,
                                         interpret=True)
    bat, lens_o = np.asarray(bat), np.asarray(lens_o)
    for i, r in enumerate(recs):
        want = r[:MAX_LEN]  # oversized records clamp, pack.py contract
        assert bytes(bat[i][:lens_o[i]]) == want, i
        assert not bat[i][lens_o[i]:].any(), i


# ---------------------------------------------------------------------------
# stage-1 structural classifier + decode passes (FC03 DIFF_TEST)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_structural_index_pallas_matches_jnp():
    import jax

    msgs = [b'{"a":1,"b":"x"}', b'{"k":"v\\"esc","n":[1,2,3]}',
            b'not json', b'{"s":"' + b"\\" * 15 + b'q"}',
            b'{"deep":{"a":{"b":1}}}', b'',
            b'{"u":"\xc3\xa9"}', b'{"t":true,"f":false,"z":null}']
    ML = 64  # interpret-mode cost scales with [rows, L]; the corpus
    bat = np.zeros((32, ML), np.uint8)  # rows fit well under this
    lens = np.zeros(32, np.int32)
    for i in range(32):
        r = (msgs[i % len(msgs)] + b" " * (i % 3))[:ML]
        bat[i, :len(r)] = np.frombuffer(r, np.uint8)
        lens[i] = len(r)
    ref = jax.jit(lambda b, l: JI.structural_index(
        b, l, max_fields=8, scan_impl="lax", extract_impl="sum",
        nested=4))(bat, lens)
    got = PK.structural_index_pallas(bat, lens, max_fields=8, nested=4,
                                     block_rows=32, interpret=True)
    for k in ref:
        a, b = np.asarray(ref[k]), np.asarray(got[k])
        assert (a == b).all(), (k, np.argwhere(a != b)[:4])
    # backslash runs straddling the parity-ladder cap: the NFA string
    # machine computes EXACT escape parity, so identity holds at every
    # run length — including at and past ESC_RUN_CAP (one length per
    # side of the cap plus the cap itself; same [4, ML] geometry so
    # the interpreter program compiles once)
    for nbs in (15, 16, 21):
        capmsg = b'{"s":"' + b"\\" * nbs + b'q"}'
        bat2 = np.zeros((4, ML), np.uint8)
        lens2 = np.zeros(4, np.int32)
        for i in range(4):
            bat2[i, :len(capmsg)] = np.frombuffer(capmsg, np.uint8)
            lens2[i] = len(capmsg)
        r2 = jax.jit(lambda b, l: JI.structural_index(
            b, l, max_fields=8, scan_impl="lax", extract_impl="sum",
            nested=4))(bat2, lens2)
        g2 = PK.structural_index_pallas(bat2, lens2, max_fields=8,
                                        nested=4, block_rows=4,
                                        interpret=True)
        for k in r2:
            assert (np.asarray(r2[k]) == np.asarray(g2[k])).all(), \
                (nbs, k)


@pytest.mark.slow
def test_decode_rfc5424_pallas_matches_jnp():
    import jax

    good = (b'<165>1 2023-10-11T22:14:15.003Z host app 123 ID47 '
            b'[ex@32473 k="v"] hello')
    msgs = [good, b'<34>1 2024-01-01T00:00:00Z h a p m - msg',
            b'garbage line', good.replace(b"165", b"999"),
            b'<1>1 2024-06-30T23:59:60Z - - - - -',
            b'<13>1 2025-02-28T12:00:00.123456+05:30 h a - - '
            b'[a@1 x="1"][b@2 y="2"] m']
    bat = np.zeros((12, 128), np.uint8)
    lens = np.zeros(12, np.int32)
    for i in range(12):
        r = msgs[i % len(msgs)][:128]
        bat[i, :len(r)] = np.frombuffer(r, np.uint8)
        lens[i] = len(r)
    ref = jax.jit(lambda b, l: R.decode_rfc5424(b, l))(bat, lens)
    got = R.decode_rfc5424_pallas(bat, lens, block_rows=12,
                                  interpret=True)
    for k in ref:
        a, b = np.asarray(ref[k]), np.asarray(got[k])
        assert (a == b).all(), (k, np.argwhere(a != b)[:4])


@pytest.mark.slow
def test_fused_frame_decode_matches_split():
    """fused_frame_decode_*: spans → gather → decode under one jit must
    equal framing + the standalone decode, channel for channel."""
    import jax

    good = (b'<165>1 2023-10-11T22:14:15.003Z host app 123 ID47 '
            b'[ex@32473 k="v"] hello')
    rmsgs = [good, b'<34>1 2024-01-01T00:00:00Z h a p m - msg',
             b'garbage line',
             b'<1>1 2024-06-30T23:59:60Z - - - - -']
    recs = [rmsgs[i % len(rmsgs)] for i in range(20)]
    blob = b"".join(r + b"\n" for r in recs)
    reg = np.frombuffer(blob, np.uint8)
    spans, dec = PK.fused_frame_decode_rfc5424(
        reg, np.int32(len(blob)), ncap=32, max_len=256, interpret=True)
    assert int(spans["n"]) == len(recs)
    b2 = np.zeros((32, 256), np.uint8)
    l2 = np.zeros(32, np.int32)
    for i, r in enumerate(recs):
        b2[i, :len(r)] = np.frombuffer(r, np.uint8)
        l2[i] = len(r)
    ref = jax.jit(lambda b, l: R.decode_rfc5424(b, l))(b2, l2)
    for k in ref:
        assert (np.asarray(ref[k]) == np.asarray(dec[k])).all(), k

    jrecs = [m for m in (b'{"a":1}', b'{"b":"x","c":[1]}', b'oops',
                         b'{"d":{"e":2}}') for _ in range(5)]
    blob = b"".join(r + b"\n" for r in jrecs)
    reg = np.frombuffer(blob, np.uint8)
    spans, dec = PK.fused_frame_decode_jsonl(
        reg, np.int32(len(blob)), ncap=32, max_len=256, interpret=True)
    assert int(spans["n"]) == len(jrecs)
    b2 = np.zeros((32, 256), np.uint8)
    l2 = np.zeros(32, np.int32)
    for i, r in enumerate(jrecs):
        b2[i, :len(r)] = np.frombuffer(r, np.uint8)
        l2[i] = len(r)
    ref = jax.jit(lambda b, l: TJ.decode_jsonl(b, l))(b2, l2)
    for k in ref:
        assert (np.asarray(ref[k]) == np.asarray(dec[k])).all(), k


# ---------------------------------------------------------------------------
# decline ladder: a failing kernel falls back to the jnp tier, counts a
# decline, emits the event — and never drops a record
# ---------------------------------------------------------------------------

def test_watchdog_decline_falls_back_to_jnp_tier(monkeypatch):
    PK.set_mode("interpret")
    blob = b"".join(b"record number %d payload\n" % i
                    for i in range(200))
    # engaged path first: the pallas tier frames the region
    packed, consumed, err = framing.device_frame_region(
        blob, "line", 512, n_records=200)
    assert packed[5] == 200 and consumed == len(blob) and not err
    b0 = np.asarray(packed[0])
    assert bytes(b0[0][:int(packed[1][0])]) == b"record number 0 payload"
    assert registry.get("pallas_rows") > 0
    assert registry.get("pallas_declines") == 0

    # induced kernel failure: same region, byte-identical output from
    # the jnp fallback, one decline counted, the event on the journal
    registry.reset()
    events.journal.reset()
    framing._PALLAS_STATE.clear()

    def boom(*a, **k):
        raise RuntimeError("induced lowering failure")

    monkeypatch.setattr(PK, "frame_sep_spans_pallas", boom)
    packed2, consumed2, err2 = framing.device_frame_region(
        blob, "line", 512, n_records=200)
    assert packed2[5] == 200 and consumed2 == len(blob) and not err2
    assert np.array_equal(np.asarray(packed2[0]), b0)
    assert registry.get("pallas_declines") == 1
    assert "pallas_decline" in [e["reason"]
                                for e in events.journal.snapshot()]


def test_decode_tier_decline_hysteresis(monkeypatch):
    PK.set_mode("interpret")
    calls = {"n": 0}

    def boom(*a, **k):
        calls["n"] += 1
        raise RuntimeError("induced decode failure")

    monkeypatch.setattr(R, "decode_rfc5424_pallas", boom)
    bat = np.zeros((8, 64), np.uint8)
    lens = np.zeros(8, np.int32)
    for _ in range(PK.DECLINE_LIMIT + 2):
        out = PK.decode_tier("rfc5424", bat, lens)
        assert out is None  # tier declines; caller runs the jnp kernel
    # after DECLINE_LIMIT strikes the tier cools down without calling
    # the kernel again
    assert calls["n"] == PK.DECLINE_LIMIT


def test_pallas_mode_off_never_calls_kernels(monkeypatch):
    PK.set_mode("off")

    def boom(*a, **k):
        raise AssertionError("kernel called with tier off")

    monkeypatch.setattr(PK, "frame_sep_spans_pallas", boom)
    blob = b"".join(b"line %d\n" % i for i in range(50))
    packed, _, _ = framing.device_frame_region(blob, "line", 512,
                                               n_records=50)
    assert packed[5] == 50
    assert registry.get("pallas_rows") == 0


def test_fused_leg_mode_never_interpret():
    # the fused decode→encode programs trace the Pallas decode leg only
    # where the tier itself is compiled (an explicit "on" off the CPU
    # backend); interpret mode never rides a fused program, and the
    # default ("auto" -> off) traces the jnp leg on every backend
    try:
        PK.set_mode("interpret")
        assert PK.fused_leg_mode() == "off"
        PK.set_mode("compiled")
        assert PK.fused_leg_mode() == "compiled"
        PK.set_mode("off")
        assert PK.fused_leg_mode() == "off"
    finally:
        PK.set_mode("off")


def _handler(pallas_line=""):
    return BatchHandler(
        queue.Queue(), RFC5424Decoder(), LTSVEncoder(CFG),
        Config.from_string("[input]\n" + pallas_line),
        fmt="rfc5424", start_timer=False, merger=None)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_pallas_auto_is_off_on_every_backend(monkeypatch, backend):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    PK.set_mode("interpret")  # a stale mode must not survive the handler
    try:
        for line in ("", 'tpu_pallas = "auto"\n', 'tpu_pallas = "off"\n'):
            h = _handler(line)
            assert PK.mode() == "off", (backend, line)
            assert PK.fused_leg_mode() == "off"
            h.close()
    finally:
        PK.set_mode("off")


def test_pallas_on_resolves_by_backend(monkeypatch):
    import jax

    try:
        h = _handler('tpu_pallas = "on"\n')
        assert PK.mode() == "interpret"  # CPU backend: the interpreter
        h.close()
        # off the CPU backend "on" means compiled kernels, once the
        # start-up compile check passed
        checked = []
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(PK, "require_compiles",
                            lambda *a, **k: checked.append((a, k)))
        h = _handler('tpu_pallas = "on"\n')
        assert PK.mode() == "compiled"
        assert PK.fused_leg_mode() == "compiled"
        assert checked and checked[0][0][0] == "rfc5424"
        h.close()
    finally:
        PK.set_mode("off")


def test_pallas_on_refusal_is_a_startup_error(monkeypatch):
    # the real check, on a backend whose compiler refuses the kernels
    # (here the CPU backend refuses any non-interpret pallas_call, as
    # Mosaic refuses these six on the chip): the handler does not come
    # up, the error names the kernel, and nothing is left engaged
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        with pytest.raises(ConfigError, match="decode_rfc5424_pallas"):
            _handler('tpu_pallas = "on"\n')
        assert PK.mode() == "off"
    finally:
        PK.set_mode("off")


def test_compiled_mode_raises_where_interpret_declines(monkeypatch):
    # a kernel failure after start-up: interpret mode declines to the
    # jnp tier (the ladder tests above); compiled mode raises it to the
    # caller, where the breaker counts it — never a quiet retry loop
    def boom(*a, **k):
        raise RuntimeError("induced lowering failure")

    monkeypatch.setattr(R, "decode_rfc5424_pallas", boom)
    bat = np.zeros((8, 64), np.uint8)
    lens = np.zeros(8, np.int32)
    try:
        PK.set_mode("compiled")
        with pytest.raises(RuntimeError, match="induced"):
            PK.decode_tier("rfc5424", bat, lens)
        assert registry.get("pallas_declines") == 0
    finally:
        PK.set_mode("off")


def test_pallas_config_validation():
    with pytest.raises(ConfigError):
        BatchHandler(queue.Queue(), RFC5424Decoder(), LTSVEncoder(CFG),
                     Config.from_string('[input]\ntpu_pallas = "maybe"\n'),
                     fmt="rfc5424", start_timer=False, merger=None)


def test_pallas_on_notice_when_route_cannot_engage(capsys):
    # RFC3164 output has no columnar block route: "on" must say why
    # and pin the tier off (the tpu_framing notice precedent)
    from flowgger_tpu.encoders.rfc3164 import RFC3164Encoder

    h = BatchHandler(
        queue.Queue(), RFC5424Decoder(), RFC3164Encoder(CFG),
        Config.from_string('[input]\ntpu_pallas = "on"\n'),
        fmt="rfc5424", start_timer=False, merger=None)
    assert "cannot run Pallas" in capsys.readouterr().err
    assert PK.mode() == "off"
    h.close()


# ---------------------------------------------------------------------------
# end-to-end: raw socket bytes → emitted bytes, pallas tier on vs off,
# across the framing × format × lane matrix (FC03 DIFF_TEST for the
# whole ingest path)
# ---------------------------------------------------------------------------

class ChunkedStream:
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


def _collect(tx):
    out = []
    while not tx.empty():
        item = tx.get_nowait()
        if isinstance(item, EncodedBlock):
            out.extend(item.iter_unframed())
        else:
            out.append(item)
    return out


RFC_CORPUS = [
    f"<34>1 2023-10-11T22:14:15.003Z host{i % 7} app {i} ID47 - msg "
    f"number {i}".encode()
    for i in range(60)
] + [b"", b"plain junk", b"x" * 300]

# every record carries a timestamp so no now()-stamp perturbs the
# on-vs-off comparison
JSON_CORPUS = [
    b'{"timestamp":%d.5,"host":"h%d","message":"json msg %d","n":%d}'
    % (1438790000 + i, i % 5, i, i)
    for i in range(60)
] + [b'{"timestamp":1,"esc":"a\\"b\\\\c"}', b'not json at all', b'']


def _cfg(pallas, fmt_extra="", lanes=1):
    return Config.from_string(
        "[input]\n"
        'tpu_framing = "on"\n'
        f'tpu_pallas = "{pallas}"\n'
        'tpu_fuse = "off"\n'
        f"tpu_max_line_len = {MAX_LEN}\n"
        + (f"tpu_lanes = {lanes}\n" if lanes > 1 else "")
        + fmt_extra)


def _run_e2e(pallas, fmt, splitter_cls, stream, sizes, lanes=1):
    cfg = _cfg(pallas, lanes=lanes)
    tx = queue.Queue()
    if fmt == "rfc5424":
        dec, enc = RFC5424Decoder(), LTSVEncoder(cfg)
    else:
        dec, enc = JSONLDecoder(cfg), GelfEncoder(cfg)
    h = BatchHandler(tx, dec, enc, cfg, fmt=fmt, start_timer=False,
                     merger=None)
    try:
        splitter_cls().run(ChunkedStream(stream, sizes), h)
        h.close()
    finally:
        PK.set_mode("off")
    return _collect(tx)


def _streams(corpus):
    return {
        "line": (LineSplitter,
                 b"".join(ln + b"\n" for ln in corpus)),
        "nul": (NulSplitter,
                b"".join(ln.replace(b"\0", b"~") + b"\0"
                         for ln in corpus)),
        "syslen": (SyslenSplitter,
                   b"".join(b"%d %s" % (len(ln), ln) for ln in corpus)),
    }


@pytest.mark.slow
def test_raw_ingest_byte_identity_pallas():
    # the fast representative of the matrix: line framing, both decode
    # formats, one lane — the pallas tier on vs off must emit the same
    # bytes, and the on run must prove the tier actually ran
    for fmt, corpus in (("rfc5424", RFC_CORPUS),
                        ("jsonl", JSON_CORPUS)):
        splitter_cls, stream = _streams(corpus)["line"]
        registry.reset()
        want = _run_e2e("off", fmt, splitter_cls, stream, [37])
        registry.reset()
        got = _run_e2e("on", fmt, splitter_cls, stream, [37])
        assert want == got, fmt
        assert len(want) >= 55, fmt
        assert registry.get("pallas_rows") > 0, fmt
        assert registry.get("pallas_declines") == 0, fmt


@pytest.mark.slow
@pytest.mark.parametrize("framing_kind", ["line", "nul", "syslen"])
@pytest.mark.parametrize("fmt", ["rfc5424", "jsonl"])
@pytest.mark.parametrize("lanes", [1, 2])
def test_e2e_matrix_framing_format_lanes(framing_kind, fmt, lanes):
    corpus = RFC_CORPUS if fmt == "rfc5424" else JSON_CORPUS
    splitter_cls, stream = _streams(corpus)[framing_kind]
    sizes = [53] if lanes == 2 else [13, 1, 777]
    registry.reset()
    want = _run_e2e("off", fmt, splitter_cls, stream, sizes,
                    lanes=lanes)
    registry.reset()
    got = _run_e2e("on", fmt, splitter_cls, stream, sizes, lanes=lanes)
    assert want == got, (framing_kind, fmt, lanes)
    assert len(want) >= 55
    assert registry.get("pallas_rows") > 0


# ---------------------------------------------------------------------------
# AOT pallas family: build → load → dispatch round trip with aot_hits
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_pallas_aot_round_trip(tmp_path):
    import jax.numpy as jnp

    from flowgger_tpu.tpu import aot
    from flowgger_tpu.tpu.framing import region_bucket

    d = str(tmp_path / "aot")
    PK.set_mode("interpret")
    aot.build_artifacts(d, platforms=("cpu", "tpu"),
                        families=("pallas",),
                        formats=("rfc5424", "jsonl"), rows_grid=(256,),
                        max_len=512, quiet=True)
    store = aot.AotStore.load(d)
    aot.activate_store(store)
    try:
        registry.reset()
        # framing spans via the cpu artifact (zero fresh compiles)
        rb = region_bucket(256 * aot.FRAMING_AVG_BYTES)
        blob = b"".join(b"hello world %d\n" % i for i in range(50))
        reg = np.zeros(rb, np.uint8)
        reg[:len(blob)] = np.frombuffer(blob, np.uint8)
        st = aot.pallas_statics("line", 256, rb)
        out = aot.pallas_call(
            "line", (jnp.asarray(reg), jnp.asarray(np.int32(len(blob)))),
            st)
        assert out is not None and int(out["n"]) == 50
        assert registry.get("aot_hits") == 1

        # decode via the artifact, and again through decode_tier
        msg = (b'<165>1 2023-10-11T22:14:15.003Z host app 123 ID47 '
               b'[ex@32473 k="v"] hi')
        bat = np.zeros((256, 512), np.uint8)
        lens = np.zeros(256, np.int32)
        for i in range(256):
            bat[i, :len(msg)] = np.frombuffer(msg, np.uint8)
            lens[i] = len(msg)
        st = aot.pallas_statics("decode_rfc5424", 256, 0)
        out = aot.pallas_call("decode_rfc5424",
                              (jnp.asarray(bat), jnp.asarray(lens)), st)
        assert out is not None
        assert int(np.asarray(out["ok"]).sum()) == 256
        out2 = PK.decode_tier("rfc5424", jnp.asarray(bat),
                              jnp.asarray(lens))
        assert out2 is not None
        assert int(np.asarray(out2["ok"]).sum()) == 256
        assert registry.get("aot_hits") == 3
        # the tpu half of the manifest exists alongside (cross-platform
        # build from this CPU host)
        entries = store.manifest["entries"].values()
        plats = {e["platform"] for e in entries}
        assert plats == {"cpu", "tpu"}
        assert any(e["family"].startswith("pallas_") for e in entries)
    finally:
        aot.activate_store(None)
