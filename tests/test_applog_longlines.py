"""Lines longer than the device's row (``input.tpu_max_line_len``) on
the rfc5424 -> GELF block route: the ``applog_stdin_gelf`` deployment's
corpus (``benchmark/corpora/loghub_applog.json``: one line in twelve an
exception with its stack folded into the line) against the benchmark's
plain reference, which imports nothing of the program.

An over-length row is clipped at pack and decoded as clipped.  Where
the clip holds the whole header and a non-blank byte of MSG, and the
bytes past it are ASCII, the row stays on the columnar encoder
(``encode_gelf_block``), which reads MSG's end from the tail; every
other over-length row is served whole by the scalar oracle in
``block_common.finish_block``, which joins its output with the columnar
tier's in input order.  Held here: the sink's bytes and order for every
line at three row widths, on the native row assembler and the numpy
engine, the joining's edges, the counters that say how many rows took
which way and why, and the one ``splice`` sub-span a batch with a
fallback row.  On the CPU this proves bytes and counts, never a rate.
The device encode tiers stay at ``auto`` where bytes are compared; the
counter and tracing cases pin the host block route, whose counts they
are.  (The clip's every position: ``tests/test_encode_gelf_block.py``.)
"""

import queue

import numpy as np
import pytest

from benchmark import corpus, reference
from benchmark.tests import test_check as harness_check
from flowgger_tpu.config import Config
from flowgger_tpu.decoders import RFC5424Decoder
from flowgger_tpu.encoders import GelfEncoder
from flowgger_tpu.mergers import LineMerger, NulMerger, SyslenMerger
from flowgger_tpu.obs import trace as obs_trace
from flowgger_tpu.tpu import block_common
from flowgger_tpu.utils.metrics import registry

CORPUS = "loghub_applog"
SEEDS = (11, 2**31 + 5, 2147492000)
POOL_LINES = 2048
BATCH_LINES = 512
DUE_US = 1_790_000_000_000_000      # 2026-09-21, microseconds
JUNK = b"-- MARK -- not a syslog line"

FRAMES = {
    "nul": (NulMerger, lambda r: r + b"\0"),
    "line": (LineMerger, lambda r: r + b"\n"),
    "syslen": (SyslenMerger, lambda r: b"%d " % (len(r) + 1) + r + b"\n"),
}


@pytest.fixture(autouse=True)
def _clean():
    registry.reset()
    obs_trace.tracer.configure("off")
    yield
    obs_trace.tracer.configure("off")
    registry.reset()


@pytest.fixture
def host_route(monkeypatch):
    """Split decode on the device, block encode on the host: the route
    that serves ``backfill.longlines``, whose probes of the device
    encoders all decline at 8% fallback."""
    monkeypatch.setenv("FLOWGGER_DEVICE_ENCODE", "0")
    return 'tpu_fuse = "off"\n'


_POOLS = {}


def pool_lines(seed, n=POOL_LINES):
    """Lines of the corpus, each stamped with a due time of its own, as
    the generator writes them."""
    if (seed, n) not in _POOLS:
        pool = corpus.build_pool(seed, n, CORPUS)
        _POOLS[seed, n] = [pool.line(i, DUE_US + i) for i in range(n)]
    return _POOLS[seed, n]


def line_of(length, tail=b"", sd=b"-"):
    """A well-formed line of exactly ``length`` bytes."""
    head = (b"<131>1 2026-09-21T10:00:00.000001Z dn01.ams.example.net "
            b"hadoop-datanode 4242 - " + sd + b" ERROR java.io.IOException:")
    pad = length - len(head) - len(tail)
    assert pad >= 0
    return head + b"x" * pad + tail


def still_scalar():
    """Over-length lines that the scalar oracle still serves: a byte
    >= 0x80 past the clip, MSG beginning past the clip, structured data
    crossing the clip."""
    return [line_of(700, "caf\u00e9".encode()),
            line_of(107).replace(b" ERROR java.io.IOException:", b"")
            + b" " * 500 + b"begins late",
            line_of(800, b" end", sd=b'[mdc@18060 thread="' + b"t" * 500
                    + b'"]')]


def run(lines_by_batch, extra="", frame="nul", max_len=None):
    """Each entry is flushed as a device batch of its own, the fetcher a
    batch behind the ingest thread; returns the sink's records as
    framed, one per message, and the blocks' bytes as written."""
    from flowgger_tpu.tpu.batch import BatchHandler

    text = "[input]\n" + extra
    if max_len is not None:
        text += f"tpu_max_line_len = {max_len}\n"
    cfg = Config.from_string(text)
    tx = queue.Queue()
    h = BatchHandler(tx, RFC5424Decoder(), GelfEncoder(cfg), cfg,
                     start_timer=False, merger=FRAMES[frame][0](cfg))
    h.ingest_sep = b"\n"
    h.ingest_strip_cr = True
    try:
        for lines in lines_by_batch:
            h.ingest_chunk(b"".join(ln + b"\n" for ln in lines))
            h.flush()
    finally:
        h.close()
    framed, data = [], []
    while not tx.empty():
        item = tx.get()
        if hasattr(item, "iter_framed"):
            framed += list(item.iter_framed())
            data.append(bytes(item.data))
        else:
            framed.append(bytes(item))
            data.append(bytes(item))
    return framed, b"".join(data)


def expected(lines, frame="nul"):
    """What the sink must hold, by the plain reference."""
    recs = (reference.gelf(ln) for ln in lines)
    return [FRAMES[frame][1](r) for r in recs if r is not None]


def batches_of(lines, size=BATCH_LINES):
    return [lines[i:i + size] for i in range(0, len(lines), size)]


def assert_sink(lines_by_batch, **kw):
    framed, data = run(lines_by_batch, **kw)
    want = expected([ln for b in lines_by_batch for ln in b],
                    kw.get("frame", "nul"))
    assert len(framed) == len(want)
    assert framed == want               # every record, in order
    assert data == b"".join(want)       # and nothing between them


# ---- every line of the pool, at three row widths ----------------------------

@pytest.mark.parametrize("max_len", [256, 512, 2048])
@pytest.mark.parametrize("seed", SEEDS)
def test_the_pools_sink_is_the_references_line_for_line(seed, max_len):
    lines = pool_lines(seed)
    lens = np.array([len(ln) for ln in lines])
    assert (lens > max_len).any()       # some row is clipped at each width
    assert_sink(batches_of(lines), max_len=max_len)
    over = int((lens > max_len).sum())
    assert registry.get("overlen_rows") == over
    assert registry.get("overlen_bytes_clipped") == int(
        (lens[lens > max_len] - max_len).sum())


@pytest.mark.parametrize("frame", sorted(FRAMES))
@pytest.mark.parametrize("seed", SEEDS)
def test_the_pools_sink_on_each_engine_and_merger(
        seed, frame, gelf_engine, host_route):
    lines = pool_lines(seed)
    assert_sink(batches_of(lines), frame=frame, extra=host_route)
    # every over-length row of the pool is an ASCII stack trace whose
    # MSG begins far inside the clip: none goes to the scalar oracle
    assert registry.get("overlen_rows_kept") == sum(
        len(ln) > 512 for ln in lines)
    assert registry.get("splice_rows_overlen") == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_same_with_tracing_on(seed):
    obs_trace.tracer.configure("ring", ring=64)
    assert_sink(batches_of(pool_lines(seed)))


def test_no_row_is_clipped_where_the_row_is_wider_than_every_line():
    lines = pool_lines(SEEDS[0], 64)
    width = 8192
    assert max(len(ln) for ln in lines) > 512
    assert max(len(ln) for ln in lines) < width
    assert_sink([lines], max_len=width)
    assert registry.get("overlen_rows") == 0
    assert registry.get("overlen_bytes_clipped") == 0
    assert registry.get("splice_rows_overlen") == 0
    assert registry.get("splice_rows") == lines.count(JUNK)


@pytest.mark.parametrize("frame", sorted(FRAMES))
def test_each_merger_frames_the_spliced_rows_as_its_own(frame):
    lines = pool_lines(SEEDS[1])[:BATCH_LINES]
    assert sum(len(ln) > 512 for ln in lines) > 20
    assert_sink([lines], frame=frame)


# ---- the row's width, to the byte -------------------------------------------

@pytest.mark.parametrize("length", [511, 512, 513, 4800])
def test_a_row_of_just_the_rows_width(length, host_route, gelf_engine):
    lines = pool_lines(SEEDS[0], 64)
    short = [ln for ln in lines if len(ln) <= 512][:6]
    batch = short[:3] + [line_of(length, b" end")] + short[3:]
    assert len(batch[3]) == length
    assert_sink([batch], extra=host_route)
    clipped = length > 512
    assert registry.get("overlen_rows") == int(clipped)
    assert registry.get("overlen_bytes_clipped") == max(0, length - 512)
    assert registry.get("overlen_rows_kept") == int(clipped)
    assert registry.get("splice_rows") == 0
    assert registry.get("splice_rows_overlen") == 0


# ---- the joining's edges ----------------------------------------------------

def _short_and_long(seed=SEEDS[2]):
    lines = pool_lines(seed)
    return ([ln for ln in lines if JUNK != ln and len(ln) <= 512][:8],
            [ln for ln in lines if len(ln) > 512][:8])


_PLACES = {
    "first": lambda s, g: [g[0]] + s,
    "last": lambda s, g: s + [g[0]],
    "two-in-a-row": lambda s, g: s[:4] + g[:2] + s[4:],
    "first-and-last": lambda s, g: [g[0]] + s + [g[1]],
    "every-other": lambda s, g: [x for pair in zip(g, s) for x in pair],
    "all-over-length": lambda s, g: g,
    "one-alone": lambda s, g: g[:1],
}


@pytest.mark.parametrize("frame", ["nul", "syslen"])
@pytest.mark.parametrize("place", sorted(_PLACES))
def test_an_over_length_row_at_the_pieces_edges(place, frame, gelf_engine):
    short, long_ = _short_and_long()
    batch = _PLACES[place](short, long_)
    assert_sink([batch], frame=frame)
    n_long = sum(len(ln) > 512 for ln in batch)
    assert registry.get("overlen_rows") == n_long
    assert (registry.get("overlen_rows_kept")
            + registry.get("splice_rows_overlen")) == n_long


@pytest.mark.parametrize("frame", ["nul", "syslen"])
@pytest.mark.parametrize("place", sorted(_PLACES))
def test_a_row_that_still_falls_back_at_the_pieces_edges(
        place, frame, gelf_engine, host_route):
    short, long_ = _short_and_long()
    batch = _PLACES[place](short + long_[:2], still_scalar())
    assert_sink([batch], frame=frame, extra=host_route)
    n_kept = sum(ln in long_ for ln in batch)
    n_late = sum(ln in still_scalar() for ln in batch)
    assert n_late and registry.get("overlen_rows") == n_kept + n_late
    assert registry.get("overlen_rows_kept") == n_kept
    assert registry.get("splice_rows_overlen") == n_late


def test_an_over_length_row_the_scalar_decoder_refuses_is_dropped_once(
        host_route, gelf_engine, capfd):
    short, _long = _short_and_long()
    # a 13th month, past the row's width: the clipped decode and the
    # scalar decoder both refuse the line
    bad = line_of(700).replace(b"2026-09-21", b"2026-13-21")
    assert len(bad) == 700 and reference.gelf(bad) is None
    batch = short[:2] + [bad] + short[2:4]
    framed, _data = run([batch], extra=host_route)
    assert framed == expected(short[:4])
    assert registry.get("decode_errors") == 1
    assert registry.get("input_lines") == 5
    assert registry.get("splice_rows") == 1
    assert registry.get("splice_rows_overlen") == 1
    assert registry.get("overlen_rows_kept") == 0
    assert registry.get("splice_bytes_out") == 0
    # counted as a fallback row like every row the oracle was asked
    assert registry.get("fallback_rows") == 1
    assert capfd.readouterr().err.count("2026-13-21") == 1


def test_an_over_length_row_with_an_escaped_sd_value(gelf_engine):
    short, _long = _short_and_long()
    sd = b'[mdc@18060 thread="main" class="te\\st sc\\"ript \\] x"]'
    esc = line_of(900, b" tail", sd=sd)
    assert len(esc) == 900
    want = reference.gelf(esc)
    assert b'"_class":"te\\\\st sc\\"ript ] x"' in want
    assert_sink([short[:3] + [esc] + short[3:6]])


def test_over_length_rows_in_every_batch_of_a_stream_keep_their_order():
    short, long_ = _short_and_long()
    batches = [short[:3] + long_[:1], long_[1:3] + short[3:5],
               long_[3:4], short[5:8] + long_[4:6] + short[:1]]
    assert_sink(batches)


# ---- the counters -----------------------------------------------------------

# fallback_rows of the four batches of each seed's pool at the default
# row width, as PR 31's program counted them, when every over-length
# row went to the scalar oracle: the rows past 512 B and the junk lines,
# every one of them valid UTF-8
_PR31_FALLBACK_ROWS = {11: 162, 2**31 + 5: 185, 2147492000: 138}


@pytest.mark.parametrize("seed", SEEDS)
def test_the_counters_say_what_the_pool_holds(seed, host_route, gelf_engine):
    """The pool, and in its first batch three over-length rows that the
    scalar oracle still serves."""
    pool = pool_lines(seed)
    late = still_scalar()
    lines = late + pool
    lens = np.array([len(ln) for ln in lines])
    over = lens > 512
    junk = sum(ln == JUNK for ln in lines)
    want = expected(lines)
    framed, _data = run([late + pool[:BATCH_LINES]]
                        + batches_of(pool[BATCH_LINES:]), extra=host_route)
    assert framed == want
    snap = registry.snapshot()
    assert snap["batch_rows_real"] == len(lines)
    assert snap["overlen_rows"] == int(over.sum())
    assert snap["overlen_bytes_clipped"] == int((lens[over] - 512).sum())
    # the pool's over-length rows stay columnar; the three do not
    assert snap["overlen_rows_kept"] == int(over.sum()) - len(late)
    assert snap["splice_rows_overlen"] == len(late)
    assert (snap["overlen_rows_kept"] + snap["splice_rows_overlen"]
            == snap["overlen_rows"])
    assert snap["splice_rows"] == len(late) + junk
    assert snap["splice_bytes_out"] == sum(
        len(reference.gelf(ln)) + 1 for ln in late)
    assert snap["splice_seconds"] > 0
    assert snap["fallback_rows"] == snap["splice_rows"]
    assert (snap["fallback_rows"] + snap["overlen_rows_kept"]
            == _PR31_FALLBACK_ROWS[seed] + len(late))
    assert snap["decode_errors"] == junk
    assert snap["input_lines"] == len(lines)
    # 7-9% of the rows, as the deployment's table has it
    assert 0.06 < snap["overlen_rows"] / snap["batch_rows_real"] < 0.10


def test_the_kept_rows_are_counted_once_a_batch(host_route, monkeypatch):
    short, long_ = _short_and_long()
    batches = [short[:4] + long_[:3], short, long_[3:5] + still_scalar(),
               still_scalar()[:1] + short[:2]]
    calls = []
    inc = registry.inc

    def spy(name, n=1):
        calls.append((name, n))
        return inc(name, n)

    monkeypatch.setattr(registry, "inc", spy)
    framed, _data = run(batches, extra=host_route)
    assert framed == expected([ln for b in batches for ln in b])
    # one increment for each batch that holds an over-length row whose
    # clipped decode could speak for the line (the last batch's one has
    # a high byte in its tail); none for a batch without
    assert [n for name, n in calls if name == "overlen_rows_kept"] == [
        3, 2, 0]
    assert [n for name, n in calls if name == "splice_rows_overlen"] == [3, 1]
    assert registry.get("overlen_rows") == 9


def test_a_batch_whose_over_length_rows_all_stay_columnar_opens_no_splice(
        host_route, gelf_engine):
    short, long_ = _short_and_long()
    obs_trace.tracer.configure("ring", ring=4)
    assert_sink([short[:3] + long_ + short[3:]], extra=host_route)
    snap = registry.snapshot()
    assert snap["overlen_rows"] == snap["overlen_rows_kept"] == len(long_)
    assert snap["splice_rows"] == 0 and snap["splice_rows_overlen"] == 0
    assert "splice_seconds" not in snap
    (rec,) = obs_trace.tracer.snapshot()
    assert _splices(rec) == []


def test_a_batch_without_a_fallback_row_counts_no_splice(host_route):
    short, _long = _short_and_long()
    assert_sink([short], extra=host_route)
    snap = registry.snapshot()
    assert snap["splice_rows"] == 0 and snap["overlen_rows"] == 0
    assert "splice_seconds" not in snap


def test_the_new_counters_are_in_the_registrys_snapshot_from_the_start():
    from flowgger_tpu.utils import metrics

    snap = registry.snapshot()
    for name in ("overlen_rows", "overlen_bytes_clipped", "splice_rows",
                 "splice_rows_overlen", "splice_bytes_out",
                 "overlen_rows_kept"):
        assert snap[name] == 0
        assert metrics.classify_metric(name) == "counter"
    assert metrics.classify_metric("splice_seconds") == "seconds"


# ---- the sub-span -----------------------------------------------------------

def _splices(rec):
    return [sp for sp in rec["sub"] if sp["stage"] == "splice"]


def test_one_splice_sub_span_a_batch_with_fallback_rows_and_none_without(
        host_route):
    short, long_ = _short_and_long()
    late = still_scalar()
    batches = [short[:4] + late + short[4:],        # three rows spliced
               short,                               # none
               [late[0], JUNK] + short[:2],         # two, one of them junk
               long_[4:8],                          # none: all stay columnar
               [late[1]] + long_[:2] + [late[2]]]   # two of the four
    obs_trace.tracer.configure("ring", ring=16)
    framed, _data = run(batches, extra=host_route)
    assert framed == expected([ln for b in batches for ln in b])
    recs = sorted(obs_trace.tracer.snapshot(), key=lambda r: r["bid"])
    assert len(recs) == len(batches)
    got = [[(sp["parent"], sp["rows"], sp["bytes"]) for sp in _splices(r)]
           for r in recs]
    assert got == [
        [("encode", 3, sum(len(ln) for ln in late))],
        [],
        [("encode", 2, len(late[0]) + len(JUNK))],
        [],
        [("encode", 2, len(late[1]) + len(late[2]))],
    ]
    for rec in recs:
        for sp in _splices(rec):
            # on the fetcher's thread, inside the batch's encode stage
            enc = next(s for s in rec["spans"] if s["stage"] == "encode")
            assert sp["thread"] == enc["thread"]
            assert enc["t0"] <= sp["t0"] <= sp["t1"] <= enc["t1"]
    # the counter holds the same seconds the sub-spans bound
    in_subs = sum(sp["t1"] - sp["t0"] for r in recs for sp in _splices(r))
    assert registry.snapshot()["splice_seconds"] == pytest.approx(
        in_subs, rel=0.2, abs=2e-3)
    assert registry.get("splice_rows") == 7
    assert registry.get("overlen_rows_kept") == 6


def _finish_two_fallback_rows():
    """``finish_block`` as a block encoder calls it for a batch whose two
    rows both go to the scalar oracle."""
    cfg = Config.from_string("")
    lines = [line_of(600), line_of(140)]
    chunk = b"".join(lines)
    starts = np.array([0, 600], dtype=np.int64)
    lens = np.array([600, 140], dtype=np.int64)
    res = block_common.finish_block(
        chunk, starts, lens, 2, np.zeros(2, dtype=bool),
        np.zeros(0, dtype=np.int64), b"", np.zeros(1, dtype=np.int64),
        None, b"\0", False, NulMerger(cfg), GelfEncoder(cfg), max_len=512)
    assert list(res.block.iter_framed()) == expected(lines)
    return res


@pytest.mark.parametrize("mode", ["off", "ring"])
def test_nothing_is_recorded_and_nothing_raised_without_a_batch(mode):
    obs_trace.tracer.configure(mode)
    obs_trace.tracer.bind(None)
    res = _finish_two_fallback_rows()
    assert res.fallback_rows == 2
    assert obs_trace.tracer.snapshot() == []
    assert obs_trace.tracer.stats()["open"] == 0
    assert registry.get("splice_rows") == 2
    assert registry.get("splice_rows_overlen") == 1


def test_a_bound_batch_gets_the_sub_span_from_whatever_thread_it_is_on():
    obs_trace.tracer.configure("ring")
    bid = obs_trace.tracer.begin("rfc5424")
    _finish_two_fallback_rows()
    obs_trace.tracer.end(bid)
    (rec,) = obs_trace.tracer.snapshot()
    assert [(sp["stage"], sp["parent"], sp["rows"], sp["bytes"])
            for sp in rec["sub"]] == [("splice", "encode", 2, 740)]


def test_the_harness_reads_a_sinks_share_in_chunks_as_it_read_it_whole(
        tmp_path):
    """``correct`` on a sink of this cell's size rests on the harness
    fingerprinting it 16 MiB at a time (PR 33).  The harness's own test
    of that is not among the tests the floor counts, so it runs here:
    every sink it names at every chunk size it names."""
    for name in harness_check.SINKS:
        for chunk in harness_check.CHUNKS:
            harness_check.test_a_share_read_in_chunks_is_the_share_read_whole(
                tmp_path, name, chunk)
