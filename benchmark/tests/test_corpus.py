"""The corpus is a pure function of its file and the seed, every line
of it is RFC 5424 as the plain reference reads it (bar the junk and RFC
5424's own example 4), and its shape is the file's, not the program's."""

import numpy as np
import pytest

from benchmark import corpus, reference

NAME = "loghub_syslog"


@pytest.fixture(scope="module")
def pool():
    return corpus.build_pool(2**31 + 5, 20000, NAME)


def test_same_seed_same_pool_other_seed_other_lines(pool):
    again = corpus.build_pool(2**31 + 5, 20000, NAME)
    assert again.blob == pool.blob
    assert corpus.build_pool(6, 20000, NAME).blob != pool.blob


def test_every_source_is_named():
    table = corpus.load(NAME)
    assert set(table["sources"]) >= {"rfc5424", "loghub"}
    assert table["assumed"]
    used = set(corpus.FIELD_RE.findall(str(table["messages"])
                                       + str(table["sd"]) + table["host"]
                                       + str(table["fields"])))
    assert used <= set(table["fields"])


def test_lines_are_what_the_reference_reads(pool):
    table = corpus.load(NAME)
    junk = table["junk"]["text"].encode()
    dropped = [pool.line(i) for i in range(pool.n)
               if reference.gelf(pool.line(i, 1_790_000_000_000_000 + i))
               is None]
    # RFC 5424's example 4 has no MSG: upstream's decoder refuses it
    assert all(l == junk or l.endswith(b'class="high"]') for l in dropped)
    n_junk = sum(l == junk for l in dropped)
    assert 0 < n_junk < 20000 * 3 * table["junk"]["per_10000"] / 10000
    assert (pool.ts_off >= 0).sum() == pool.n - n_junk


def test_the_shape_is_the_sources(pool):
    lens = pool.line_off[1:] - pool.line_off[:-1] - 1
    # Loghub's two syslog sets run 88-107 B a line in BSD format; the
    # RFC 5424 header is some 25 B longer (version, year, microseconds,
    # a qualified host name), structured data on half of the lines more
    assert 140 < lens.mean() < 190
    assert np.percentile(lens, 99) < 400
    assert 0 < (lens > 512).mean() < 0.005      # rpc.statd's overflow
    blob = pool.blob
    assert blob.count(b" - - ") > 0.4 * pool.n   # no structured data
    assert b"[meta sequenceId=" in blob and b"[timeQuality tzKnown=" in blob
    assert "\ufeff'su root' failed".encode() in blob
