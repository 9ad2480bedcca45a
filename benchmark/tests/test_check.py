"""The comparison that decides ``correct``, on a sink made by the plain
reference itself and then broken in each of the ways ``faults.py``
knows: no pipeline, no JAX."""

import numpy as np
import pytest

from benchmark import check, corpus, faults, refchunk, sinktail

BASE = 1_790_000_000_000_000


@pytest.fixture(scope="module")
def sent(tmp_path_factory):
    """A work directory with a pool and a generator's log: three passes
    over a 1,500-line pool in writes of 250."""
    work = tmp_path_factory.mktemp("work")
    pool = corpus.build_pool(5, 1500, "loghub_syslog")
    corpus.save_pool(pool, work / "pool.npz")
    rows, base = [], BASE
    for k in range(18):
        rows.append([0, (k * 250) % 1500, 250, base, base + 40])
        base += 2000
    log = np.asarray(rows, np.int64)
    np.save(work / "gen_log.npy", log)
    records = [r for r in map(
        refchunk.reference.gelf, refchunk.written_lines(pool, log)) if r]
    return work, log, [r + b"\0" for r in records]


def judge(work, log, records):
    path = work / "sink.gelf"
    path.write_bytes(b"".join(records))
    tail = sinktail.Tail()
    tail.feed(path.read_bytes(), BASE + 50_000)
    ts, seen, end = tail.columns()
    sink = check.Sink(str(path), dict(ts=ts, seen=seen, end=end,
                                      rest=len(tail.rest)))
    return check.compare(str(work), sink, log, (BASE, BASE + 40_000),
                         seed=3)


def test_the_reference_against_itself_is_correct(sent):
    work, log, records = sent
    got, made = judge(work, log, records)
    assert got == {"missing": 0, "unexpected": 0, "out_of_order": 0,
                   "bytes_differ": 0}
    assert made["attempted"] == len(records) > 4000
    assert 100 < made["line_bytes"] < made["record_bytes"] < 600
    assert made["sampled"] == made["attempted"]      # a small run: all


def test_a_long_run_is_sampled_from_the_seed(sent, monkeypatch):
    work, log, records = sent
    monkeypatch.setattr(check, "SAMPLE_LINES", 1500)
    a = check.sample_rows(log, 5)
    assert (a == check.sample_rows(log, 5)).all()
    assert (a != check.sample_rows(log, 6)).any()
    assert 0 < a.sum() < len(log)
    got, made = judge(work, log, records)
    assert not any(got.values()) and 0 < made["sampled"] < made["attempted"]
    # every record altered: the sample sees it, whatever it holds
    broken = [r.replace(b'"version":"1.1"', b'"version":"1.0"')
              for r in records]
    got, _ = judge(work, log, broken)
    assert got["bytes_differ"] == made["sampled"]
    assert got["missing"] == got["unexpected"] == 0


@pytest.mark.parametrize("name, fails", [
    ("coarse_ts", {"missing", "unexpected", "out_of_order", "bytes_differ"}),
    ("drop", {"missing", "bytes_differ"}),
    ("dup", {"unexpected", "out_of_order"}),
    ("alter", {"bytes_differ"}),
    ("half", {"missing", "bytes_differ"}),
    ("swap", {"out_of_order"}),
    ("reverse", {"out_of_order"}),
])
def test_each_fault_fails_the_numbers_it_should(sent, name, fails):
    work, log, records = sent
    got, _ = judge(work, log, faults.FAULTS[name](list(records)))
    assert {k for k, v in got.items() if v > check.LIMITS[k]} == fails


def test_a_line_lost_is_missing(sent):
    work, log, records = sent
    got, _ = judge(work, log, records[:-1])
    assert got["missing"] == 1 and got["bytes_differ"] == 1


def test_a_record_cut_short_at_the_files_end_is_unexpected(sent):
    work, log, records = sent
    got, _ = judge(work, log, records[:-1] + [records[-1][:-5]])
    assert got["unexpected"] == 1 and got["missing"] == 1


def test_a_junk_line_kept_is_unexpected(sent):
    work, log, records = sent
    got, _ = judge(work, log, records + [b'{"short_message":"junk"}\0'])
    assert got["unexpected"] == 1


# -- order is a connection's own ---------------------------------------------

@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """Two connections' writes, turn by turn: the log's first column
    says whose each is; the records in the order written."""
    work = tmp_path_factory.mktemp("fleet")
    pool = corpus.build_pool(5, 1500, "loghub_syslog")
    corpus.save_pool(pool, work / "pool.npz")
    rows = [[k % 2, (k * 250) % 1500, 250, BASE + 2000 * k,
             BASE + 2000 * k + 40] for k in range(12)]
    log = np.asarray(rows, np.int64)
    per_row = [[r + b"\0" for r in map(
        refchunk.reference.gelf, refchunk.written_lines(pool, log[k:k + 1]))
        if r] for k in range(12)]
    return work, log, per_row


def flat(blocks):
    return [r for b in blocks for r in b]


def old_count(records):
    """``out_of_order`` as it was before a log had connections: every
    place where the sink's order departs from the order sent."""
    tail = sinktail.Tail()
    tail.feed(b"".join(records), BASE)
    return int((np.diff(tail.columns()[0]) <= 0).sum())


def test_a_legal_interleaving_of_two_connections_is_in_order(fleet):
    work, log, per_row = fleet
    # as sent; then one connection's writes all before the other's; then
    # the two taking turns two writes at a time: each keeps its own order
    for order in (range(12), [0, 2, 4, 6, 8, 10, 1, 3, 5, 7, 9, 11],
                  [1, 3, 0, 2, 5, 7, 4, 6, 9, 11, 8, 10]):
        records = flat(per_row[k] for k in order)
        got, _ = judge(work, log, records)
        assert got == {"missing": 0, "unexpected": 0, "out_of_order": 0,
                       "bytes_differ": 0}
    assert old_count(records) == 3      # what one stream's rule would say


def test_a_swap_inside_a_connection_is_out_of_order(fleet):
    work, log, per_row = fleet
    # two of connection 0's writes exchanged, connection 1's between
    records = flat(per_row[k] for k in [2, 1, 0, 3, 4, 5, 6, 7, 8, 9, 10, 11])
    got, _ = judge(work, log, records)
    assert got["out_of_order"] == 1
    assert got["missing"] == got["unexpected"] == got["bytes_differ"] == 0
    # two neighbouring records of one write exchanged
    got, _ = judge(work, log, flat(per_row[:3]) + faults.swap(per_row[3])
                   + flat(per_row[4:]))
    assert got["out_of_order"] == 1
    # the same fault on a block that holds both connections' records
    # falls on two senders' neighbours here, which may change places
    got, _ = judge(work, log, faults.swap(flat(per_row)))
    assert got["out_of_order"] == 0
    # and a block of both connections' records back to front
    got, _ = judge(work, log, faults.reverse(flat(per_row)))
    assert got["out_of_order"] >= len(flat(per_row)) - 12


def test_records_across_connections_may_swap(fleet):
    work, log, per_row = fleet
    records = flat(per_row)
    k = len(per_row[0])                 # last of write 0, first of write 1
    records[k - 1], records[k] = records[k], records[k - 1]
    got, _ = judge(work, log, records)
    assert got["out_of_order"] == 0 and old_count(records) == 1


@pytest.mark.parametrize("name", ["swap", "reverse", "dup", "coarse_ts",
                                  "drop"])
def test_one_stream_reads_what_it_always_read(sent, name):
    """With one source the per-connection count is the count over the
    whole sink, as ``check.py`` made it before connections."""
    work, log, records = sent
    broken = faults.FAULTS[name](list(records))
    got, _ = judge(work, log, broken)
    assert got["out_of_order"] == old_count(broken)
    shuffled = [records[i] for i in
                np.random.default_rng(7).permutation(len(records))]
    got, _ = judge(work, log, shuffled)
    assert got["out_of_order"] == old_count(shuffled) > 1000


# -- the sink's fingerprints, a chunk at a time -------------------------------

def whole_read(data):
    """``refchunk.sink`` as it was before PR 33: the share read whole
    and split, twice the share in memory.  Kept here as the oracle."""
    return refchunk.fingerprints(data.split(b"\0")[:-1])


def _seeded(seed, n=300):
    rng = np.random.default_rng(seed)
    return b"".join(rng.integers(1, 256, int(k), np.uint8).tobytes() + b"\0"
                    for k in rng.integers(0, 120, n))


# what a sink can hold, by name: each ends as the case says
SINKS = {
    "seeded": _seeded(1),
    "seeded_2": _seeded(2**31 + 9),
    "empty_records": b"\0\0ab\0\0\0c\0\0",
    "only_nuls": b"\0" * 41,
    "one_long_record": b"x" * 5000 + b"\0",
    "long_among_short": b"a\0" + b"y" * 3000 + b"\0b\0\0" + b"z" * 2047
                        + b"\0",
    "trailing_piece": _seeded(3, 40) + b"cut short, no terminator",
    "no_terminator_at_all": b"never closed",
    "nothing": b"",
    # a NUL on a chunk's last byte and on the next one's first, for
    # chunks of 4 and 8
    "nul_at_the_seams": b"abc\0\0efg\0ijk\0\0nop\0",
}
CHUNKS = [1, 2, 3, 4, 5, 7, 8, 64, 1000, 4096, refchunk.SINK_CHUNK]


@pytest.mark.parametrize("chunk", CHUNKS)
@pytest.mark.parametrize("name", SINKS)
def test_a_share_read_in_chunks_is_the_share_read_whole(tmp_path, name,
                                                        chunk):
    data = SINKS[name]
    path = tmp_path / "sink.gelf"
    path.write_bytes(data)
    want = whole_read(data)
    got = refchunk.sink_fingerprints(str(path), 0, len(data), chunk)
    assert got.dtype == np.uint64 and got.tolist() == want.tolist()
    # shares as ``compare`` cuts them: at records' starts, one share a
    # child; and a share of no bytes
    ends = np.flatnonzero(np.frombuffer(data, np.uint8) == 0)
    if not len(ends):
        return
    shares = check.sink_shares(ends, 3)
    parts = [refchunk.sink_fingerprints(str(path), a, b, chunk)
             for a, b in shares]
    assert sum(map(len, parts)) == len(ends)
    assert np.concatenate(parts).tolist() == want.tolist()
    for (a, b), part in zip(shares, parts):
        assert part.tolist() == whole_read(data[a:b]).tolist()
    first = shares[0][1]
    assert len(refchunk.sink_fingerprints(str(path), first, first,
                                          chunk)) == 0


def test_a_range_past_the_files_end_stops_at_the_end(tmp_path):
    path = tmp_path / "sink.gelf"
    path.write_bytes(b"ab\0cd\0ef")
    assert refchunk.sink_fingerprints(str(path), 0, 10_000, 4).tolist() \
        == whole_read(b"ab\0cd\0ef").tolist()


@pytest.mark.slow
def test_a_sink_child_holds_a_chunk_not_its_share(tmp_path):
    """A child over a share of 1 GiB of 822 B records (the size of
    ``backfill.longlines``'s) peaks under 0.4 GB; the whole read peaked
    at 2.06 times its share (PERF.md, PR 33)."""
    import os
    import subprocess
    import sys

    from benchmark import peakrss

    rng = np.random.default_rng(33)
    block = b"".join(rng.integers(1, 256, 821, np.uint8).tobytes() + b"\0"
                     for _ in range(1275))          # 1,048,050 B
    path, out = tmp_path / "sink.gelf", tmp_path / "chunk.npz"
    with open(path, "wb") as f:
        for _ in range(1025):
            f.write(block)
    size = os.path.getsize(path)
    assert size >= 1 << 30
    p = subprocess.Popen(
        [sys.executable, os.path.join(check.HERE, "refchunk.py"), "sink",
         str(path), "0", str(size), str(out)], stdin=subprocess.DEVNULL,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    rc, kernel_says = peakrss.wait(p, 600)
    assert rc == 0
    made = np.load(out)
    assert len(made["fp"]) == 1025 * 1275
    assert (made["fp"][:1275] == whole_read(block)).all()
    assert (made["fp"][-1275:] == made["fp"][:1275]).all()
    # its own word (the most it held at its fullest instants) and the
    # kernel's, which is never under what this test's process held when
    # it started the child, and that is under 0.4 GB too (``statm``
    # counts in batches of pages: a percent of slack)
    assert 60e6 < int(made["peak_rss"]) < 0.4e9
    assert int(made["peak_rss"]) <= 1.02 * kernel_says < 0.4e9
