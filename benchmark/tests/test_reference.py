"""The plain reference: golden records, what it drops, and a second
witness (the program's own scalar ``rfc5424`` -> ``gelf`` pipeline) on
the benchmark's corpus."""

import random

import pytest

from benchmark import corpus, reference

# the second: seconds * 10**9 + nanos does not fit a double, so upstream's
# (and the program's) arithmetic lands a quarter of a microsecond off
GOLDEN = [
    (b"<23>1 2015-08-05T15:53:45.637824Z testhostname appname 69 42 "
     b'[origin@123 software="te\\st sc\\"ript" swVersion="0.0.1"] test message',
     b'{"_software":"te\\\\st sc\\"ript","_swVersion":"0.0.1",'
     b'"application_name":"appname","full_message":"<23>1 '
     b"2015-08-05T15:53:45.637824Z testhostname appname 69 42 "
     b'[origin@123 software=\\"te\\\\st sc\\\\\\"ript\\" swVersion=\\"0.0.1\\"] '
     b'test message","host":"testhostname","level":7,"process_id":"69",'
     b'"sd_id":"origin@123","short_message":"test message",'
     b'"timestamp":1438790025.637824,"version":"1.1"}'),
    (b"<13>1 2026-09-30T12:00:00.250000Z h app - - - -",
     b'{"application_name":"app","full_message":"<13>1 '
     b'2026-09-30T12:00:00.250000Z h app - - - -","host":"h","level":5,'
     b'"process_id":"-","short_message":"-","timestamp":1790769600.2499998,'
     b'"version":"1.1"}'),
]


@pytest.mark.parametrize("line, record", GOLDEN)
def test_golden(line, record):
    assert reference.gelf(line) == record


@pytest.mark.parametrize("line", [
    b"-- MARK -- not a syslog line", b"", b"<13>1 2026-09-30T12:00:00Z h app - -",
    b"<999>1 2026-09-30T12:00:00Z h a p m - x",
    b"<13>2 2026-09-30T12:00:00Z h a p m - x",
    b"<13>1 2026-13-30T12:00:00Z h a p m - x",
    b"<13>1 2026-09-30T12:00:00Z h a p m [x k=v] x",
    b"<13>1 2026-09-30T12:00:00Z h a p m \xff x",
])
def test_dropped(line):
    assert reference.gelf(line) is None


def test_the_programs_scalar_pipeline_agrees_on_the_corpus():
    """The reference imports nothing of the program; this test does, as
    the second witness."""
    decoders = pytest.importorskip("flowgger_tpu.decoders.rfc5424")
    from flowgger_tpu.config import Config
    from flowgger_tpu.encoders.gelf import GelfEncoder

    cfg = Config.from_string(
        '[input]\nformat = "rfc5424"\n[output]\nformat = "gelf"\n')
    dec, enc = decoders.RFC5424Decoder(cfg), GelfEncoder(cfg)
    pool = corpus.build_pool(2**31 + 17, 12000, "loghub_syslog")
    rng = random.Random(1)
    kept = 0
    for i in range(pool.n):
        line = pool.line(i, 1_790_000_000_000_000 + rng.randrange(10**9))
        try:
            theirs = enc.encode(dec.decode(line.decode("utf-8")))
        except Exception:  # noqa: BLE001 - whatever it raises, it drops the line
            theirs = None
        assert reference.gelf(line) == theirs, line
        kept += theirs is not None
    assert 0 < pool.n - kept < 20        # junk, and RFC 5424's example 4
