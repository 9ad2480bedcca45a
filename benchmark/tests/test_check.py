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
