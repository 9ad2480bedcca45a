"""Differential tests: columnar GELF tokenizer and auto-detect dispatch
vs the scalar oracles."""

import random

import pytest

from flowgger_tpu.decoders import DecodeError, GelfDecoder
from flowgger_tpu.tpu.batch import _decode_auto_batch, _decode_gelf_batch

ORACLE = GelfDecoder()

CORPUS = [
    '{"version":"1.1", "host": "example.org",'
    '"short_message": "A short message", '
    '"full_message": "Backtrace here\\n\\nmore stuff", "timestamp": 1385053862.3072, '
    '"level": 1, "_user_id": 9001, "_some_info": "foo"}',
    '{"host":"h"}',
    '{"host":"h","timestamp":1}',
    '{"host":"h","timestamp":-1.5}',
    '{"host":"h","x":null,"b":true,"c":false}',
    '{"host":"h","n":-3,"f":1.5,"big":18446744073709551615}',
    '{"host":"h","esc":"a\\"b\\\\c\\n\\u00e9"}',
    '{"host":"h","uni":"ünïcode"}',
    '{ "host" : "h" , "k" : "v" }',          # whitespace everywhere
    '{"host":"h","z":1,"a":2,"m":3}',        # sorted pair order
    '{"host":"h","dup":1,"dup":2}',          # duplicate keys: last wins
    '{"host":"h","empty":""}',
    "{}",                                     # missing hostname error
    '{"some_key": []}',                      # array -> fallback, exact error
    '{"some_key": {"nested":1}}',
    '{"timestamp": "a string", "host": "h"}',
    '{some_key = "some_value"}',
    '{"version":"42","host":"h"}',
    '{"level": 8, "host":"h"}',
    '{"level": true, "host":"h"}',
    '{"host": 42}',
    "[1,2,3]",
    "not json at all",
    "",
    '{"host":"h",}',                         # trailing comma
    '{"host":"h" "k":1}',                    # missing comma
    '{"host":"h","k":}',                     # missing value
    '{"host":"h","k":01}',                   # leading zero number
    '{"host":"h","k":1e309}',                # overflow -> inf, like oracle
    '{"host":"h","k":truex}',
    '{"host":"h","level":1.0}',              # float level: invalid severity
]


def run_both(lines):
    raw = [ln.encode("utf-8") for ln in lines]
    results = _decode_gelf_batch(raw, 512)
    pairs = []
    for ln, res in zip(lines, results):
        kernel = ("rec", res.record) if res.record is not None else ("err", res.error)
        try:
            oracle = ("rec", ORACLE.decode(ln))
        except DecodeError as e:
            oracle = ("err", str(e))
        pairs.append((ln, kernel, oracle))
    return pairs


def assert_identical(lines):
    for ln, kernel, oracle in run_both(lines):
        if kernel[0] == "rec" and oracle[0] == "rec" and '"timestamp"' not in ln:
            # missing timestamp defaults to now() on both paths; compare
            # modulo the clock
            krec, orec = kernel[1], oracle[1]
            assert abs(krec.ts - orec.ts) < 5, ln
            krec.ts = orec.ts
        assert kernel == oracle, (
            f"divergence on {ln!r}:\n  kernel: {kernel}\n  oracle: {oracle}")


def test_corpus_differential():
    assert_identical(CORPUS)


def test_corpus_differential_under_the_tpus_scan_lowering(monkeypatch):
    """The oracle differential again with the scans lowered as every TPU
    run lowers them (MXU tri-matmul), which the CPU backend never picks
    of itself; eagerly, so that no cached CPU trace answers."""
    import jax

    from flowgger_tpu.tpu import aot

    monkeypatch.setattr(aot, "_scan_impl_for", lambda platform: "mm")
    with jax.disable_jit():
        assert_identical(CORPUS)


def test_fast_path_coverage():
    import jax.numpy as jnp
    import numpy as np

    from flowgger_tpu.tpu import gelf, pack

    clean = [ln for ln in CORPUS[:12]]
    raw = [ln.encode() for ln in clean]
    batch, lens, chunk, starts, orig, n = pack.pack_lines_2d(raw, 512)
    out = gelf.decode_gelf_jit(jnp.asarray(batch), jnp.asarray(lens))
    okf = np.asarray(out["ok"])[:n]
    assert okf.mean() >= 0.8, list(zip(clean, okf))


def test_fuzz_differential():
    rng = random.Random(4242)
    alphabet = list('{}":,\\ abhostk0123456789.-eltrun')
    base = '{"host":"abc","level":3,"short_message":"hi there","k":"v","n":42}'
    lines = []
    for _ in range(300):
        chars = list(base)
        for _ in range(rng.randint(1, 5)):
            op = rng.random()
            pos = rng.randrange(len(chars)) if chars else 0
            if op < 0.4 and chars:
                chars[pos] = rng.choice(alphabet)
            elif op < 0.7:
                chars.insert(pos, rng.choice(alphabet))
            elif chars:
                del chars[pos]
        lines.append("".join(chars))
    assert_identical(lines)


def test_autodetect_mixed_batch():
    from flowgger_tpu.decoders import LTSVDecoder, RFC3164Decoder, RFC5424Decoder
    from flowgger_tpu.config import Config

    mixed = [
        "<13>1 2015-08-05T15:53:45Z host5424 app 1 2 - via rfc5424",
        "<34>Aug  6 11:15:24 host3164 su: message here",
        "time:1438790025.5\thost:hostltsv\tmessage:via ltsv",
        '{"host":"hostgelf","short_message":"via gelf","timestamp":5.5}',
        "Aug  6 11:15:24 bare3164 appname msg",
        "garbage that matches nothing <",
    ]
    results = _decode_auto_batch([m.encode() for m in mixed], 512)
    assert results[0].record.hostname == "host5424"
    assert results[1].record.hostname == "host3164"
    assert results[2].record.hostname == "hostltsv"
    assert results[3].record.hostname == "hostgelf"
    assert results[4].record.hostname == "bare3164"
    assert results[5].record is None  # rfc3164 decode error

    # each class must equal its dedicated scalar decoder's output
    assert results[0].record == RFC5424Decoder().decode(mixed[0])
    assert results[1].record == RFC3164Decoder().decode(mixed[1])
    assert results[2].record == LTSVDecoder(Config.from_string("")).decode(mixed[2])


def test_autodetect_order_preserved():
    mixed = []
    for i in range(50):
        if i % 3 == 0:
            mixed.append(f"<13>1 2015-08-05T15:53:45Z h5424-{i} a p m - x".encode())
        elif i % 3 == 1:
            mixed.append(f"time:1.5\thost:hl-{i}\tk:v".encode())
        else:
            mixed.append(f'{{"host":"hg-{i}"}}'.encode())
    results = _decode_auto_batch(mixed, 512)
    for i, res in enumerate(results):
        assert res.record is not None
        expect = {0: f"h5424-{i}", 1: f"hl-{i}", 2: f"hg-{i}"}[i % 3]
        assert res.record.hostname == expect


def test_gelf_rescue_tier_wide_rows():
    """Rows with DEFAULT_MAX_FIELDS < fields <= RESCUE_MAX_FIELDS must
    decode on-device via the tier-2 rescue in decode_gelf_fetch (not the
    scalar fallback), and match the oracle exactly."""
    import numpy as np

    from flowgger_tpu.tpu import gelf, pack

    wide = ('{"version":"1.1","host":"h","short_message":"m","timestamp":7'
            + "".join(f',"_k{i}":{i}' for i in range(12)) + "}")
    narrow = '{"host":"n","short_message":"x","timestamp":1}'
    lines = [wide.encode(), narrow.encode(), b"junk not json"] * 3
    batch, lens, *_ = pack.pack_lines_2d(lines, 256)
    host = gelf.decode_gelf_fetch(gelf.decode_gelf_submit(batch, lens))
    ok = np.asarray(host["ok"])
    nf = np.asarray(host["n_fields"])
    assert host["key_start"].shape[1] == gelf.RESCUE_MAX_FIELDS
    for i, ln in enumerate(lines):
        if ln.startswith(b"junk"):
            assert not ok[i]
        else:
            assert ok[i], f"row {i} should stay on-device"
    assert nf[0] == 16 and nf[1] == 3

    # span-level parity with the oracle for the rescued row
    rec = ORACLE.decode(wide)
    row = np.asarray(batch[0])
    keys = set()
    for k in range(int(nf[0])):
        ks, ke = int(host["key_start"][0][k]), int(host["key_end"][0][k])
        keys.add(bytes(row[ks:ke]).decode())
    assert "_k11" in keys and "host" in keys and len(keys) == 16
    assert rec.hostname == "h"


def test_classify_device_matches_scalar():
    """The device classifier must reproduce classify() bit-for-bit on a
    corpus large enough to engage the device path (n >= 512)."""
    import numpy as np

    from flowgger_tpu.tpu import pack
    from flowgger_tpu.tpu.autodetect import classify, classify_packed

    base = [
        b"<13>1 2015-08-05T15:53:45Z h a p m - x",       # rfc5424
        b"\xef\xbb\xbf<13>1 2015-08-05T15:53:45Z h a p m - x",  # BOM 5424
        b"<34>Aug  6 11:15:24 host su: msg",              # rfc3164 w/ pri
        b"Aug  6 11:15:24 host app msg",                  # bare rfc3164
        b"time:1.5\thost:h\tk:v",                         # ltsv
        b'{"host":"h","short_message":"m"}',              # gelf
        b"\xef\xbb\xbf{\"host\":\"h\"}",                  # BOM gelf
        b"<999999>1 not valid pri",                       # '>' past window
        b"<13>not5424",                                   # pri, no version
        b"<1a3>1 junk digits",                            # non-digit pri
        b"has\ttab but no colon-free",                    # tab+colon -> ltsv
        b"has\ttab only",                                 # tab, no colon
        b"plain text line",                               # catch-all
        b"<>",                                            # empty pri
        b"{",                                             # bare brace
        b"",                                              # empty
    ]
    lines = [base[i % len(base)] + b" pad%d" % i if i % 3 == 0
             else base[i % len(base)] for i in range(1024)]
    packed = pack.pack_lines_2d(lines, 64)
    got = classify_packed(packed)
    want = np.array([classify(ln) for ln in lines], dtype=np.int8)
    assert (got == want).all(), np.flatnonzero(got != want)[:10]
