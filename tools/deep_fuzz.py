"""Deep cross-route differential fuzz: random corpora through every
(input fmt, encoder, merger) block route vs the scalar pipeline.

Usage: python tools/deep_fuzz.py [seed] [trials]
       python tools/deep_fuzz.py --routes fused [seed] [trials]
       python tools/deep_fuzz.py --routes framing [seed] [trials]
       python tools/deep_fuzz.py --routes jsonl,dns [seed] [trials]
Prints per-route mismatches (none expected) and a FAILURES count.
A bounded version runs in CI as tests/test_cross_route_fuzz.py.

``--routes`` either selects the fused decode→encode tier (``fused``)
or filters the classic block-route matrix to a comma-separated list of
input formats (e.g. ``jsonl,dns`` — the new-format CI step).  Classic
new-format runs randomize the lane count (1/2) so the LaneSet
sequencer's ordering contract is fuzzed too.

``--routes fused`` fuzzes the fused decode→encode tier
(flowgger_tpu/tpu/fused_routes.py) instead: every registered fused
route (rfc5424/rfc3164/ltsv/gelf → GELF) over line/nul/syslen framing
against its scalar oracle, run eagerly (``jax.disable_jit()``) so the
byte-identity claim is checked even on hosts whose XLA cannot compile
the fused programs.  ci.sh runs a bounded pass as its slow fuzz step.
"""
import os, queue, random, re, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"

FUSED_MODE = False
FRAMING_MODE = False
ROUTE_FILTER = None
if "--routes" in sys.argv:
    i = sys.argv.index("--routes")
    if i + 1 >= len(sys.argv):
        print("--routes takes a value: fused, framing, or a comma-"
              "separated format list (e.g. jsonl,dns)", file=sys.stderr)
        sys.exit(2)
    val = sys.argv[i + 1]
    del sys.argv[i:i + 2]
    if val == "fused":
        FUSED_MODE = True
    elif val == "framing":
        FRAMING_MODE = True
    else:
        ROUTE_FILTER = set(val.split(","))

if FUSED_MODE or FRAMING_MODE:
    # fused/framing modes never touch the device-encode compiles (the
    # routes they exercise have no device-encode tier engaged): inline
    # guarded calls can never hang, so the watchdog comes off entirely
    os.environ["FLOWGGER_COMPILE_TIMEOUT_MS"] = "0"
    os.environ["FLOWGGER_FUSED_COMPILE_TIMEOUT_MS"] = "0"
else:
    # classic mode compiles for real: keep the shared watchdog, and
    # bound the fused tier's first-compile waits so its decline ladder
    # doesn't tax the split-route fuzz on hosts that can't compile it
    # (every fresh shape the fuzz generates would otherwise pay one
    # full wait before declining — 50ms keeps the aggregate negligible;
    # the background compiles keep warming either way)
    os.environ.setdefault("FLOWGGER_FUSED_COMPILE_TIMEOUT_MS", "50")
import jax
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from flowgger_tpu.config import Config
from flowgger_tpu.block import EncodedBlock
from flowgger_tpu.decoders.gelf import GelfDecoder
from flowgger_tpu.decoders.ltsv import LTSVDecoder
from flowgger_tpu.decoders.rfc3164 import RFC3164Decoder
from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
from flowgger_tpu.encoders.gelf import GelfEncoder
from flowgger_tpu.encoders.ltsv import LTSVEncoder
from flowgger_tpu.encoders.passthrough import PassthroughEncoder
from flowgger_tpu.encoders.capnp import CapnpEncoder
from flowgger_tpu.encoders.rfc3164 import RFC3164Encoder
from flowgger_tpu.encoders.rfc5424 import RFC5424Encoder
from flowgger_tpu.mergers import LineMerger, NulMerger, SyslenMerger
from flowgger_tpu.tpu.batch import BatchHandler

CFG = Config.from_string("")
CFG_TYPED = Config.from_string(
    '[input.ltsv_schema]\ncounter = "u64"\ndelta = "i64"\n'
    'flag = "bool"\nratio = "f64"\n')


class TypedLTSVDecoder(LTSVDecoder):
    """Marker so ROUTES can carry the typed config."""

    def __init__(self, _cfg):
        super().__init__(CFG_TYPED)
rng = random.Random(int(sys.argv[1]) if len(sys.argv) > 1 else 1)

def rnd_bytes(n):
    return bytes(rng.randrange(256) for _ in range(n))

def gen_rfc5424():
    sd = ""
    if rng.random() < 0.7:
        nb = rng.randrange(1, 4)
        blocks = []
        for b in range(nb):
            pairs = " ".join(
                f'k{rng.randrange(20)}="{rnd_val()}"'
                for _ in range(rng.randrange(0, 9)))
            blocks.append(f"[b{b}@{rng.randrange(9)}{(' ' + pairs) if pairs else ''}]")
        sd = "".join(blocks)
    else:
        sd = "-"
    frac = f".{rng.randrange(1, 999999)}" if rng.random() < 0.5 else ""
    off = rng.choice(["Z", "+02:00", "-11:30", "z"])
    return (f"<{rng.randrange(200)}>1 2015-08-05T15:53:45{frac}{off} "
            f"host{rng.randrange(5)} app {rng.randrange(100)} m {sd} "
            f"msg {rnd_val()}").encode()

def rnd_val():
    alphabet = 'abc"\\]\t~é '
    return "".join(rng.choice(alphabet) for _ in range(rng.randrange(0, 10)))

def gen_rfc3164():
    return (f"<{rng.randrange(200)}>Aug  5 15:53:45 host{rng.randrange(5)} "
            f"app[{rng.randrange(100)}]: legacy {rnd_val()}").encode()

def gen_ltsv():
    parts = [f"host:h{rng.randrange(5)}",
             rng.choice(["time:1438790025.5", "time:2015-08-05T15:53:45Z"])]
    for _ in range(rng.randrange(0, 6)):
        parts.append(f"k{rng.randrange(9)}:{rnd_val()}")
    if rng.random() < 0.7:
        parts.append(f"message:{rnd_val()}")
    rng.shuffle(parts)
    return "\t".join(parts).encode()


def gen_ltsv_typed():
    parts = [f"host:h{rng.randrange(5)}", "time:1438790025"]
    for key, pool in (("counter", ["42", "007", "0", "18446744073709551615",
                                   "+5", "x"]),
                      ("delta", ["-7", "-0", "12", "9" * 25]),
                      ("flag", ["true", "false", "TRUE", "1"]),
                      ("ratio", ["2.5", "1438790025.25", "2.50", "1e1",
                                 "inf", "nan", "-0.0", ".5", "5.", "1_0",
                                 "-0", "1e999", "0.1"])):
        if rng.random() < 0.6:
            parts.append(f"{key}:{rng.choice(pool)}")
    parts.append(f"k{rng.randrange(3)}:{rnd_val()}")
    rng.shuffle(parts)
    return "\t".join(parts).encode()

def gen_gelf():
    import json as _json
    obj = {"host": f"h{rng.randrange(5)}", "timestamp": rng.choice([1438790025, 1438790025.42, -5, 0])}
    for _ in range(rng.randrange(0, 5)):
        obj[f"k{rng.randrange(9)}"] = rng.choice([rnd_val(), rng.randrange(-99, 99), True, False, None, 3.25])
    if rng.random() < 0.5:
        obj["short_message"] = rnd_val()
    if rng.random() < 0.3:
        obj["level"] = rng.randrange(0, 10)
    return _json.dumps(obj).encode()

def gen_jsonl():
    import json as _json
    obj = {"timestamp": rng.choice([1438790025, 1438790025.42, -5, 0])}
    # up to 12 DISTINCT extra keys: with the three specials below this
    # crosses the DEFAULT_MAX_FIELDS=8 boundary, so the tier-2 rescue
    # path (9..24 fields, decode_jsonl_fetch) gets fuzzed too
    for kn in rng.sample(range(20), rng.randrange(0, 13)):
        k = f"k{kn}"
        r = rng.random()
        if r < 0.15:
            obj[k] = {"a": rng.randrange(9), "b": [1, rnd_val()]}
        elif r < 0.25:
            obj[k] = [rng.randrange(9), rnd_val(), None]
        else:
            obj[k] = rng.choice([rnd_val(), rng.randrange(-99, 99),
                                 True, False, None, 3.25])
    if rng.random() < 0.5:
        obj["message"] = rnd_val()
    if rng.random() < 0.5:
        obj["host"] = f"h{rng.randrange(5)}"
    if rng.random() < 0.3:
        obj["level"] = rng.randrange(0, 10)
    return _json.dumps(obj).encode()


def gen_dns():
    ts = rng.choice(["1438790025", "1438790025.5", "1438790025.123",
                     "0", ".5", "5.", "x", "-1"])
    client = rng.choice(["10.0.0.9", "2001:db8::1", f"h{rng.randrange(5)}",
                         ""])
    qname = rng.choice([f"q{rng.randrange(9)}.example.com.", "a.b.", ""])
    qtype = rng.choice(["A", "AAAA", "TXT", "28", ""])
    rcode = rng.choice(["NOERROR", "NXDOMAIN", "SERVFAIL", "3"])
    lat = rng.choice(["0", "523", "007", str(rng.randrange(10 ** 7)),
                      "18446744073709551615", "99999999999999999999999"])
    parts = [ts, client, qname, qtype, rcode, lat]
    # occasionally break the field count
    if rng.random() < 0.1:
        parts = parts[:5] if rng.random() < 0.5 else parts + ["extra"]
    return "\t".join(parts).encode()


GENS = [gen_rfc5424, gen_rfc3164, gen_ltsv, gen_gelf]


def norm(bs):
    """Mask now()-stamps (rows whose input lacked a numeric timestamp
    differ between the two runs) and, when present, the syslen length
    prefix their varying width perturbs."""
    def repl(m):
        v = float(m.group(1))
        if abs(v - time.time()) < 86400:
            return b'"timestamp":NOW'
        return m.group(0)

    out = re.sub(rb'"timestamp":([0-9.e+-]+)', repl, bs)
    # ltsv output form of the same now()-stamp hazard
    def repl_t(m):
        try:
            v = float(m.group(1))
        except ValueError:  # rfc3339 text stamps etc.
            return m.group(0)
        if abs(v - time.time()) < 86400:
            return b"time:NOW"
        return m.group(0)

    out = re.sub(rb'time:([0-9.e+-]+)', repl_t, out)

    # rfc5424-output form: a freshly minted rfc3339 text stamp (only
    # now() rows carry today's date; corpus stamps are fixed past dates)
    today = time.strftime("%Y-%m-%d", time.gmtime()).encode()
    def repl_iso(m):
        return b"TSNOW" if m.group(0)[:10] == today else m.group(0)

    out = re.sub(rb'\d{4}-\d{2}-\d{2}T[0-9:.]+Z', repl_iso, out)
    if (b'"timestamp":NOW' in out or b"time:NOW" in out
            or b"TSNOW" in out):
        out = re.sub(rb'^[0-9]+ ', b'LEN ', out)
    return out


def norm_capnp(bs):
    """Binary form of the now()-stamp mask: the record's f64 ts sits at
    a fixed offset (16) past any syslen prefix; masking it keeps the
    frame length unchanged."""
    import struct
    off = 0
    if bs[:1].isdigit():
        off = bs.find(b" ") + 1
    if len(bs) >= off + 24:
        try:
            (v,) = struct.unpack_from("<d", bs, off + 16)
        except struct.error:
            return bs
        if abs(v - time.time()) < 86400:
            bs = bs[:off + 16] + b"NOWNOWNO" + bs[off + 24:]
    return bs

def corpus(n, gen):
    out = []
    for _ in range(n):
        r = rng.random()
        if r < 0.08:
            out.append(rnd_bytes(rng.randrange(0, 60)))
        elif r < 0.25:
            b = bytearray(gen())
            for _ in range(rng.randrange(1, 5)):
                if b:
                    b[rng.randrange(len(b))] = rng.randrange(256)
            out.append(bytes(b))
        else:
            out.append(gen())
    return out

if FUSED_MODE:
    from flowgger_tpu.tpu import fused_routes as _fr
    from flowgger_tpu.tpu import pack as _pack

    # tier-friendly value alphabet: the shared rnd_val leans on é /
    # RFC5424 value escapes, which correctly push rows OFF the fused
    # tier — a corpus full of them declines whole batches instead of
    # fuzzing the fused assembly.  Mutations below still inject the
    # broken/off-tier rows that exercise the scalar-fallback splicing.
    def rnd_val_tier():
        # interior spaces only (the kernels' fast-path grammars reject
        # leading/trailing-space fields, correctly routing them to the
        # scalar oracle — mutations cover that; here we want tier rows)
        v = "".join(rng.choice("abcxyz ~.,:}{")
                    for _ in range(rng.randrange(1, 12))).strip()
        return v or f"v{rng.randrange(10)}"

    def gen_rfc5424_fused():
        # 1..4 SD pairs with UNIQUE keys: the fused tier has no
        # wide-pair escalation and duplicate names take the dict
        # last-wins scalar path, so a pair-heavy/dup-heavy corpus would
        # decline whole batches instead of fuzzing the assembly;
        # off-tier rows still appear via mutation
        if rng.random() < 0.8:
            keys = rng.sample(range(20), rng.randrange(1, 5))
            pairs = " ".join(f'k{k}="{rnd_val_tier()}"' for k in keys)
            sd = f"[b@9 {pairs}]"
        else:
            sd = "-"
        frac = f".{rng.randrange(1, 999999)}" if rng.random() < 0.5 else ""
        return (f"<{rng.randrange(200)}>1 2015-08-05T15:53:45{frac}Z "
                f"host{rng.randrange(5)} app {rng.randrange(100)} m {sd} "
                f"msg {rnd_val_tier()}").encode()

    def gen_rfc3164_fused():
        return (f"<{rng.randrange(200)}>Aug  5 15:53:45 "
                f"host{rng.randrange(5)} app[{rng.randrange(100)}]: "
                f"legacy {rnd_val_tier()}").encode()

    def gen_ltsv_fused():
        parts = [f"host:h{rng.randrange(5)}",
                 rng.choice(["time:1438790025.5",
                             "time:2015-08-05T15:53:45Z",
                             "time:1438790025"])]
        parts += [f"k{k}:{rnd_val_tier()}"
                  for k in rng.sample(range(9), rng.randrange(0, 4))]
        if rng.random() < 0.7:
            parts.append(f"message:{rnd_val_tier()}")
        rng.shuffle(parts)
        return "\t".join(parts).encode()

    def gen_gelf_fused():
        import json as _json

        obj = {"host": f"h{rng.randrange(5)}",
               "timestamp": rng.choice([1438790025, 1438790025.42, -5])}
        for k in rng.sample(range(9), rng.randrange(0, 5)):
            obj[f"k{k}"] = rng.choice(
                [rnd_val_tier(), rng.randrange(1, 99),
                 True, False, None])
        if rng.random() < 0.5:
            obj["short_message"] = rnd_val_tier()
        if rng.random() < 0.3:
            obj["level"] = rng.randrange(0, 8)
        return _json.dumps(obj).encode()

    FUSED_GENS = {"rfc5424": gen_rfc5424_fused,
                  "rfc3164": gen_rfc3164_fused,
                  "ltsv": gen_ltsv_fused, "gelf": gen_gelf_fused}
    FUSED_DECS = {"rfc5424": RFC5424Decoder, "rfc3164": RFC3164Decoder,
                  "ltsv": LTSVDecoder, "gelf": GelfDecoder}

    def fused_corpus(n, gen):
        # mostly-clean stream with a ~3% mutation rate: enough broken
        # rows to fuzz the scalar-fallback splicing, few enough that
        # the tier-fraction gate (5%) keeps the batch on the fused tier
        out = []
        for _ in range(n):
            if rng.random() < 0.03:
                b = bytearray(gen())
                if b:
                    b[rng.randrange(len(b))] = rng.randrange(256)
                out.append(bytes(b))
            else:
                out.append(gen())
        return out

    import re as _now_re_mod

    # a record whose input lost its timestamp (a mutation eating the
    # "timestamp" key) gets stamped with "now" independently by the
    # fused path and by this oracle loop — two wall-clock reads that
    # can never be byte-equal.  Mask now-era stamps (corpus stamps are
    # 2015-era, 14xxxxxxxx) on BOTH sides so the diff ignores only the
    # injection point; the syslen prefix is recomputed from the masked
    # body so its length stays consistent too.
    _NOW_RE = _now_re_mod.compile(rb'("timestamp":)1[7-9]\d{8}(\.\d+)?')

    def mask_now(frame, merger):
        body = frame
        if isinstance(merger, SyslenMerger):
            sp = frame.find(b" ")
            body = frame[sp + 1:]
        body = _NOW_RE.sub(rb"\1<now>", body)
        if isinstance(merger, SyslenMerger):
            body = str(len(body)).encode() + b" " + body
        return body

    # route matrix under fuzz: every →GELF leg plus the PR 19 output
    # legs (rfc5424/ltsv/capnp out).  Encoder classes are constructed
    # per trial; the corpus generator is keyed by the input format.
    from flowgger_tpu.encoders.capnp import CapnpEncoder
    from flowgger_tpu.encoders.ltsv import LTSVEncoder
    from flowgger_tpu.encoders.rfc5424 import RFC5424Encoder

    FUSED_COMBOS = ([(fmt, GelfEncoder) for fmt in FUSED_GENS]
                    + [("rfc5424", RFC5424Encoder),
                       ("rfc5424", LTSVEncoder),
                       ("rfc5424", CapnpEncoder),
                       ("rfc3164", RFC5424Encoder)])

    fails = engaged = 0
    for trial in range(int(sys.argv[2]) if len(sys.argv) > 2 else 4):
        for fmt, enc_cls in FUSED_COMBOS:
            gen = FUSED_GENS[fmt]
            dec = FUSED_DECS[fmt](CFG)
            enc = enc_cls(CFG)
            merger = rng.choice([LineMerger(), NulMerger(),
                                 SyslenMerger()])
            ltsv_dec = dec if fmt == "ltsv" else None
            lines = fused_corpus(160, gen)
            route = _fr.route_for(fmt, enc, merger, ltsv_dec)
            if route is None:
                print(f"NO ROUTE fmt={fmt} enc={enc_cls.__name__}")
                fails += 1
                continue
            packed = _pack.pack_lines_2d(lines, 256)
            with jax.disable_jit():
                h = _fr.submit(route, packed)
                res, _ = _fr.fetch_encode(h, packed, enc, merger,
                                          ltsv_dec, {})
            want = []
            for ln in lines:
                try:
                    want.append(merger.frame(
                        enc.encode(dec.decode(ln.decode("utf-8")))))
                except Exception:
                    continue
            if res is None:
                print(f"DECLINED route={route.name} trial={trial} "
                      "(tier fraction over budget this corpus)")
                continue
            engaged += 1
            # whole-blob comparison: capnp payloads are binary, so
            # framed re-splitting on b"\n" would cut inside records.
            # Only GELF output can carry a now-stamp (missing input
            # timestamp); the other legs' stamps come from the input.
            if type(enc) is GelfEncoder:
                got_blob = b"".join(
                    mask_now(g, merger)
                    for g in res.block.iter_framed())
                want_blob = b"".join(mask_now(w, merger) for w in want)
            else:
                got_blob = res.block.data
                want_blob = b"".join(want)
            if got_blob != want_blob:
                fails += 1
                print(f"FUSED MISMATCH route={route.name} "
                      f"merger={type(merger).__name__} trial={trial}")
                for i in range(min(len(got_blob), len(want_blob))):
                    if got_blob[i] != want_blob[i]:
                        print("  WANT:", want_blob[max(0, i - 40):i + 80])
                        print("  GOT :", got_blob[max(0, i - 40):i + 80])
                        break
                else:
                    print("  length:", len(want_blob), "vs",
                          len(got_blob))
    print("ENGAGED:", engaged, "FAILURES:", fails)
    sys.exit(1 if fails or not engaged else 0)

from flowgger_tpu.decoders.jsonl import JSONLDecoder
if FRAMING_MODE:
    # ---- device-resident framing fuzz (tpu/framing.py) ----------------
    # Random chunk sizes that split records mid-byte — including mid-
    # syslen-length-prefix and a delimiter landing exactly on a chunk
    # edge — asserting (a) device spans == host splitter output per
    # region and (b) end-to-end handler bytes identical to the host-
    # framed pipeline, for line/nul/syslen x 1/2 lanes.
    import numpy as np

    from flowgger_tpu.splitters import (LineSplitter, NulSplitter,
                                        SyslenSplitter,
                                        _scan_syslen_region)
    from flowgger_tpu.tpu import framing as _framing
    from flowgger_tpu.tpu import pack as _pack
    from flowgger_tpu.utils.metrics import registry as _registry

    # run the framing jits inline (no single-flight semaphore): the
    # routes below engage no device-encode tier, so nothing can wedge
    _framing._watchdogged = lambda slot, fn: fn()

    def _cfg(framing_on, lanes):
        return Config.from_string(
            "[input]\n"
            f'tpu_framing = "{"on" if framing_on else "off"}"\n'
            'tpu_fuse = "off"\n'
            "tpu_max_line_len = 192\n"
            + (f"tpu_lanes = {lanes}\n" if lanes > 1 else ""))

    class _ChunkedStream:
        def __init__(self, data, sizes):
            self.data, self.pos = data, 0
            self.sizes, self.i = sizes or [len(data) or 1], 0

        def read(self, n):
            if self.pos >= len(self.data):
                return b""
            sz = max(1, self.sizes[self.i % len(self.sizes)])
            self.i += 1
            out = self.data[self.pos:self.pos + sz]
            self.pos += len(out)
            return out

    def _sizes_from_cuts(stream, forced):
        cuts = {c for c in forced if 0 < c < len(stream)}
        for _ in range(rng.randrange(0, 14)):
            if len(stream) > 1:
                cuts.add(rng.randrange(1, len(stream)))
        prev, sizes = 0, []
        for c in sorted(cuts):
            sizes.append(c - prev)
            prev = c
        sizes.append(max(1, len(stream) - prev))
        return sizes

    def _run(stream, splitter_cls, framing_on, lanes, sizes):
        tx = queue.Queue()
        h = BatchHandler(tx, RFC5424Decoder(), LTSVEncoder(CFG),
                         _cfg(framing_on, lanes), fmt="rfc5424",
                         start_timer=False, merger=None)
        splitter_cls().run(_ChunkedStream(stream, sizes), h)
        h.close()
        out = []
        while not tx.empty():
            item = tx.get_nowait()
            out.extend(item.iter_unframed()
                       if isinstance(item, EncodedBlock) else [item])
        return out

    fails = 0
    trials = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    for trial in range(trials):
        lines = [ln.replace(b"\n", b"~").replace(b"\0", b"~")
                 for ln in corpus(rng.randrange(1, 160), gen_rfc5424)]
        # (framing, stream bytes, splitter, forced cut positions)
        line_stream = b"".join(ln + b"\n" for ln in lines)
        nul_stream = b"".join(ln + b"\0" for ln in lines)
        sys_stream = b"".join(b"%d %s" % (len(ln), ln) for ln in lines)
        # forced adversarial cuts: a delimiter exactly on a chunk edge,
        # the byte after it, and (syslen) mid-length-prefix
        pos = 0
        line_cuts, sys_cuts = set(), set()
        for ln in lines[: 1 + trial % 5]:
            pos += len(ln) + 1
            line_cuts |= {pos, pos - 1, pos + 1}
        pos = 0
        for ln in lines[: 1 + trial % 5]:
            plen = len(b"%d" % len(ln))
            sys_cuts |= {pos + 1, pos + plen, pos + plen + 1}
            pos += plen + 1 + len(ln)
        if trial % 3 == 0:
            # tail variants: partial record / bad length / huge prefix
            line_stream += rnd_bytes(rng.randrange(0, 30)) \
                .replace(b"\n", b"~")
            sys_stream += rng.choice(
                [b"9999 short", b"xx junk", b"123456789012 x", b""])
        cases = [
            ("line", line_stream, LineSplitter, line_cuts),
            ("nul", nul_stream, NulSplitter, set()),
            ("syslen", sys_stream, SyslenSplitter, sys_cuts),
        ]
        for framing, stream, splitter_cls, forced in cases:
            # (a) span identity on the whole region
            if framing == "syslen":
                hs, hl, hn, hcons, herr = _scan_syslen_region(stream)
                try:
                    p, c, e = _framing.device_frame_region(
                        stream, "syslen", 192,
                        n_records=max(stream.count(b" "), 1))
                except _framing.FramingDeclined:
                    p = None  # >9-digit prefix: host owns it, by design
                if p is not None and not (
                        p[5] == hn and c == hcons and e == herr
                        and np.array_equal(p[3][:hn], hs)
                        and np.array_equal(p[4], hl)):
                    fails += 1
                    print(f"SPAN MISMATCH syslen trial={trial}")
            else:
                sep = b"\0" if framing == "nul" else b"\n"
                cut = stream.rfind(sep)
                if cut >= 0:
                    framed = stream[:cut + 1]
                    hs, hl, hn, _c = _pack._split_np(
                        framed, strip_cr=framing == "line",
                        sep=sep[0])
                    p, _, _ = _framing.device_frame_region(
                        framed, framing, 192,
                        n_records=framed.count(sep))
                    if not (p[5] == hn
                            and np.array_equal(p[3][:hn], hs)
                            and np.array_equal(p[4], hl)):
                        fails += 1
                        print(f"SPAN MISMATCH {framing} trial={trial}")
            # (b) e2e byte identity across chunk boundaries and lanes
            sizes = _sizes_from_cuts(stream, forced)
            lanes = 2 if trial % 2 else 1
            want = _run(stream, splitter_cls, False, lanes, sizes)
            got = _run(stream, splitter_cls, True, lanes, sizes)
            if want != got:
                fails += 1
                print(f"E2E MISMATCH {framing} lanes={lanes} "
                      f"trial={trial} want={len(want)} got={len(got)}")
    engaged = _registry.get("framing_rows") > 0
    print("ENGAGED:", engaged, "FAILURES:", fails)
    sys.exit(1 if fails or not engaged else 0)

from flowgger_tpu.decoders.dns import DNSDecoder

ROUTES = [
    ("rfc5424", RFC5424Decoder, [GelfEncoder, PassthroughEncoder, RFC5424Encoder, LTSVEncoder, CapnpEncoder], gen_rfc5424),
    ("rfc3164", RFC3164Decoder, [GelfEncoder, PassthroughEncoder, RFC3164Encoder, CapnpEncoder, LTSVEncoder, RFC5424Encoder], gen_rfc3164),
    ("ltsv", LTSVDecoder, [GelfEncoder, CapnpEncoder, LTSVEncoder, RFC5424Encoder], gen_ltsv),
    ("ltsv", TypedLTSVDecoder, [GelfEncoder, CapnpEncoder, LTSVEncoder, RFC5424Encoder], gen_ltsv_typed),
    ("gelf", GelfDecoder, [GelfEncoder, LTSVEncoder, CapnpEncoder, RFC5424Encoder], gen_gelf),
    ("jsonl", JSONLDecoder, [GelfEncoder, LTSVEncoder], gen_jsonl),
    ("dns", DNSDecoder, [GelfEncoder, LTSVEncoder], gen_dns),
]
if ROUTE_FILTER is not None:
    unknown = ROUTE_FILTER - {fmt for fmt, *_ in ROUTES}
    if unknown:
        print(f"--routes: unknown format(s) {sorted(unknown)}",
              file=sys.stderr)
        sys.exit(2)
    ROUTES = [r for r in ROUTES if r[0] in ROUTE_FILTER]
# new-format handler configs: eager kernel cost scales with max_len,
# and the generators' longest lines stay well under 192 (over-long
# rows would take the per-row oracle, which the fuzz compares against
# anyway)
CFG_NEWFMT = Config.from_string("[input]\ntpu_max_line_len = 192\n")
CFG_LANES2 = Config.from_string(
    "[input]\ntpu_lanes = 2\ntpu_max_line_len = 192\n")
MERGERS = [None, LineMerger(), NulMerger(), SyslenMerger()]
fails = 0
for trial in range(int(sys.argv[2]) if len(sys.argv) > 2 else 6):
    for fmt, dec_cls, encs, gen in ROUTES:
        # the new-format legs fuzz the host-side screen/assembly/
        # splicing logic eagerly on a smaller corpus: a fresh
        # [512, 512] jsonl structural-index compile per CI pass buys
        # nothing the eager run doesn't check (compiled-vs-eager
        # channel equality has its own tests, and bench.py --smoke
        # gates the compiled block route's bytes)
        new_fmt = fmt in ("jsonl", "dns")
        lines = corpus(256 if new_fmt else 400, gen)
        for enc_cls in encs:
            dec = dec_cls(CFG)
            enc = enc_cls(CFG)
            merger = rng.choice(MERGERS)
            want = []
            for ln in lines:
                try:
                    payload = enc.encode(dec.decode(ln.decode("utf-8")))
                except Exception:
                    continue
                want.append(merger.frame(payload) if merger else payload)
            tx = queue.Queue()
            # the new-format routes fuzz the 1/2-lane sequencer too
            hcfg = CFG
            if new_fmt:
                hcfg = CFG_LANES2 if rng.random() < 0.5 else CFG_NEWFMT
            h = BatchHandler(tx, dec, enc, hcfg, fmt=fmt, start_timer=False, merger=merger)
            import contextlib
            with jax.disable_jit() if new_fmt else contextlib.nullcontext():
                for ln in lines:
                    h.handle_bytes(ln)
                h.flush()
            got = []
            while not tx.empty():
                item = tx.get_nowait()
                if isinstance(item, EncodedBlock):
                    got.extend(item.iter_framed())
                else:
                    got.append(merger.frame(item) if merger else item)
            fix = norm_capnp if enc_cls is CapnpEncoder else norm
            got = [fix(g) for g in got]
            want = [fix(w) for w in want]
            if got != want:
                fails += 1
                print(f"MISMATCH fmt={fmt} enc={enc_cls.__name__} merger={type(merger).__name__ if merger else None} trial={trial}")
                for i, (w, g) in enumerate(zip(want, got)):
                    if w != g:
                        print("  WANT:", w[:140])
                        print("  GOT :", g[:140])
                        break
                if len(want) != len(got):
                    print("  count:", len(want), "vs", len(got))
print("FAILURES:", fails)
sys.exit(1 if fails else 0)
