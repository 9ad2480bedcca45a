"""The sink reader's framing and its one regex, on records split across
reads."""

import numpy as np

from benchmark import reference, sinktail

LINES = [
    b"<13>1 2026-09-30T12:00:00.000001Z h1 app 1 - - first",
    b'<14>1 2026-09-30T12:00:00.250000Z h2 app 2 ID1 [x@1 k="v"] second',
    b"<15>1 2026-09-30T12:00:01.999999Z h3 app - - - third \"timestamp\":5",
]


def blob():
    recs = [reference.gelf(x) for x in LINES]
    assert all(recs)
    return b"".join(r + b"\0" for r in recs), recs


def want_us():
    return [1790769600_000001, 1790769600_250000, 1790769601_999999]


def test_whole_reads():
    data, recs = blob()
    t = sinktail.Tail()
    t.feed(data, 42)
    ts, seen, end = t.columns()
    assert ts.tolist() == want_us()
    assert seen.tolist() == [42, 42, 42]
    assert end.tolist() == list(np.cumsum([len(r) + 1 for r in recs]) - 1)
    assert t.rest == b""


def test_every_split_point_gives_the_same_columns():
    data, recs = blob()
    whole = sinktail.Tail()
    whole.feed(data, 1)
    for cut in range(1, len(data)):
        t = sinktail.Tail()
        t.feed(data[:cut], 1)
        t.feed(data[cut:], 2)
        ts, seen, end = t.columns()
        assert ts.tolist() == want_us(), cut
        assert end.tolist() == whole.columns()[2].tolist(), cut
        # a record belongs to the read that brought its terminator
        assert seen.tolist() == [1 if e < cut else 2 for e in end], cut


def test_a_record_cut_short_stays_in_rest():
    data, _ = blob()
    t = sinktail.Tail()
    t.feed(data[:-1], 1)
    assert len(t.columns()[0]) == 2 and t.rest


def test_a_record_without_a_timestamp_reads_as_minus_one():
    t = sinktail.Tail()
    t.feed(b'{"host":"x"}\0' + blob()[0], 1)
    assert t.columns()[0].tolist() == [-1_000_000] + want_us()
