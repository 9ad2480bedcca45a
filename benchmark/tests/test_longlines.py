"""``backfill.longlines`` and what came with it (PR 31): the corpus
keeps the shape the deployment's table gives it from any seed; the three
per-layer metrics it brought are data files on readers that were there,
and read a number or nothing, never raise, on whatever a ``tput`` cell
hands them (``run.py`` ``layer_metrics`` loads every ``*.tput`` file for
every such cell, ``backfill.drain`` too); and both cells rehearse traced
to exit code 0.  The traced drain is the run PR 30 was refused for."""

import json
import math
import os
import queue
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import corpus, reference

# run.py is a script: imported, it puts the repo's root first on the
# path and takes JAX_COMPILATION_CACHE_DIR out of the environment.  The
# tests beside this one import their helpers from this directory, which
# pytest has put first, so both are put back
_first, _cache = sys.path[0], os.environ.get("JAX_COMPILATION_CACHE_DIR")
from benchmark import run as bench_run  # noqa: E402

sys.path[0] = _first
if _cache is not None:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "loghub_applog"
NEW = ("pack.overlen_share.tput", "splice.self_us_per_row.tput",
       "splice.us_per_spliced_row.tput")
NEW_COUNTERS = ("overlen_rows", "overlen_bytes_clipped", "splice_rows",
                "splice_rows_overlen", "splice_bytes_out", "splice_seconds")


# ---- the corpus -------------------------------------------------------------

@pytest.fixture(scope="module", params=[7, 2**31 + 5, 2147492000])
def pool(request):
    return corpus.build_pool(request.param, 40000, NAME)


def test_the_shape_is_the_tables_from_any_seed(pool):
    lens = pool.line_off[1:] - pool.line_off[:-1] - 1
    over = lens > 512
    assert 0.07 <= over.mean() <= 0.09
    assert 0.33 <= lens[over].sum() / lens.sum() <= 0.40
    assert 330 <= lens.mean() <= 380
    assert 230 <= np.median(lens) <= 270
    assert 3000 <= np.percentile(lens, 99) <= 3500
    assert 4000 <= lens.max() <= 5000


def test_long_lines_are_exceptions_folded_into_the_line(pool):
    lens = pool.line_off[1:] - pool.line_off[:-1] - 1
    blob = pool.blob
    assert blob.isascii()
    # nothing but the terminators is a control character: LF and TAB
    # inside a message are #012 and #011, as the emitters fold them
    ctl = np.frombuffer(blob, np.uint8) < 32
    assert ctl.sum() == pool.n and blob.count(b"\n") == pool.n
    frames = np.array([pool.line(i).count(b"#012#011at ")
                       for i in np.flatnonzero(lens > 512)])
    # OpenStack's longest request lines pass 512 B with no frame at all
    assert (frames == 0).mean() < 0.02
    traces = frames[frames > 0]
    assert 5 <= traces.min() and traces.max() <= 60
    short = np.array([pool.line(i).count(b"#012")
                      for i in np.flatnonzero(lens <= 512)[:5000]])
    assert not short.any()
    assert 0.35 < blob.count(b" [mdc@18060 thread=") / pool.n < 0.45
    assert 0.55 < blob.count(b" - - ") / pool.n


def test_lines_are_what_the_reference_reads(pool):
    junk = corpus.load(NAME)["junk"]["text"].encode()
    n = 8000
    recs = [reference.gelf(pool.line(i, 1_790_000_000_000_000 + i))
            for i in range(n)]
    dropped = [pool.line(i) for i in range(n) if recs[i] is None]
    assert all(ln == junk for ln in dropped)
    assert len(dropped) < n * 3 * 2 / 10000 + 3
    # a long line's record holds the whole of it, twice
    i = next(i for i in range(n) if len(pool.line(i)) > 3000)
    whole = pool.line(i, 1_790_000_000_000_000 + i)
    rec = json.loads(recs[i])
    assert len(recs[i]) > 2 * 3000
    assert rec["full_message"].encode() == whole
    assert rec["short_message"].count("#012#011at ") >= 20
    with_mdc = next(json.loads(r) for r in recs
                    if r is not None and b'"sd_id":"mdc@18060"' in r)
    assert with_mdc["_thread"] and with_mdc["_class"].startswith("org.apache.")


def test_every_source_is_named_and_every_choice_is_listed():
    table = corpus.load(NAME)
    assert set(table["sources"]) >= {"loghub", "stacks", "rfc5424", "log4j2",
                                     "rsyslog", "flowgger"}
    said = " ".join(table["assumed"])
    for word in ("weights", "frame counts", "8%", "structured data",
                 "host names", "junk", "nothing here was read from a file"):
        assert word in said, word
    used = set(corpus.FIELD_RE.findall(str(table["messages"])
                                       + str(table["sd"]) + table["host"]
                                       + str(table["fields"])))
    assert used <= set(table["fields"])


def test_the_deployment_is_the_drains_with_other_lines():
    conf = os.path.join(ROOT, "benchmark", "configs")

    def toml(name):
        with open(os.path.join(conf, name + ".toml")) as f:
            return [ln for ln in f.read().splitlines()
                    if ln and not ln.startswith("#")]

    def facts(name):
        with open(os.path.join(conf, name + ".json")) as f:
            return json.load(f)

    assert toml("applog_stdin_gelf") == toml("backfill_stdin_gelf")
    assert "tpu_max_line_len" not in " ".join(toml("applog_stdin_gelf"))
    mine, drain = facts("applog_stdin_gelf"), facts("backfill_stdin_gelf")
    # the drain's guarantees word for word, and one said aloud
    assert mine["guarantees"][:3] == drain["guarantees"]
    assert "of any length" in mine["guarantees"][3]
    assert mine["reduced"] == [] and "reference.py as it is" in \
        mine["reference"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "drain.json")) as f:
        was = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "drain_applog.json")) as f:
        now = json.load(f)
    assert dict(was, corpus=NAME, sources=1) == now


# ---- the three metrics, on whatever a tput cell hands them ------------------

def specs():
    by = {s["name"]: s for s in bench_run.layer_metrics("tput")}
    assert set(NEW) <= set(by)
    for name in NEW:
        assert by[name]["reader"] in ("span_sub", "counter_ratio")
    return [by[name] for name in NEW]


def read_all(ctx):
    """Every new metric through its reader, as ``layer_values`` calls
    it: a finite number or None."""
    out = {}
    for spec in specs():
        v = spec["read"](ctx, spec.get("args"))
        assert v is None or (isinstance(v, (int, float))
                             and math.isfinite(v)), (spec["name"], v)
        out[spec["name"]] = v
    return out


def program_ctx(lines, traced=True):
    """``ctx`` as ``run.py`` builds it, from the program itself run on
    ``lines`` in this process at a small size: the registry's delta over
    the window and, traced, the tracer's records with the process's
    offset to the wall clock."""
    from flowgger_tpu.config import Config
    from flowgger_tpu.decoders import RFC5424Decoder
    from flowgger_tpu.encoders import GelfEncoder
    from flowgger_tpu.mergers import NulMerger
    from flowgger_tpu.obs.trace import tracer
    from flowgger_tpu.tpu.batch import BatchHandler

    cfg = Config.from_string("[input]\ntpu_batch_size = 512\n")
    tracer.configure("ring" if traced else "off", ring=64)
    m0, t0 = bench_run.snapshot(), time.time()
    tx = queue.Queue()
    h = BatchHandler(tx, RFC5424Decoder(), GelfEncoder(cfg), cfg,
                     start_timer=False, merger=NulMerger(cfg))
    h.ingest_sep = b"\n"
    h.ingest_strip_cr = True
    try:
        for i in range(0, len(lines), 512):
            h.ingest_chunk(b"".join(ln + b"\n" for ln in lines[i:i + 512]))
            h.flush()
    finally:
        h.close()
    t1 = time.time()
    spans = None
    if traced:
        wall = time.time() - time.perf_counter()
        spans = [dict(rec, wall=wall) for rec in tracer.snapshot()]
    tracer.configure("off")
    return {"counters": bench_run.delta(bench_run.snapshot(), m0),
            "window": (int(t0 * 1e6), int(t1 * 1e6) + 1),
            "window_s": t1 - t0, "spans": spans}


def parents(ctx):
    """The same window as the parent commit's program would have
    counted and traced it: none of the new counters, no ``splice``."""
    spans = ctx["spans"]
    if spans is not None:
        spans = [dict(rec, sub=[s for s in rec["sub"]
                                if s["stage"] != "splice"])
                 for rec in spans]
    return dict(ctx, spans=spans, counters={
        k: v for k, v in ctx["counters"].items() if k not in NEW_COUNTERS})


def lines_of(name, n=2048, seed=2**31 + 9):
    pool = corpus.build_pool(seed, n, name)
    return [pool.line(i, 1_790_000_000_000_000 + i) for i in range(n)]


@pytest.fixture(scope="module")
def drain_ctx():
    """The drain's lines: a few rows in a thousand pass 512 B."""
    lines = lines_of("loghub_syslog", 4096)
    assert 0 < sum(len(ln) > 512 for ln in lines) < 20
    return program_ctx(lines)


@pytest.fixture(scope="module")
def long_ctx():
    return program_ctx(lines_of(NAME))


def test_on_the_drains_counters_and_spans(drain_ctx):
    got = read_all(drain_ctx)
    assert 0 < got["pack.overlen_share.tput"] < 0.5
    assert got["splice.us_per_spliced_row.tput"] > 0
    assert 0 <= got["splice.self_us_per_row.tput"] < \
        got["splice.us_per_spliced_row.tput"]


def test_on_the_new_cells_counters_and_spans(long_ctx):
    got = read_all(long_ctx)
    c = long_ctx["counters"]
    lines = lines_of(NAME)
    assert got["pack.overlen_share.tput"] == pytest.approx(
        100 * sum(len(ln) > 512 for ln in lines) / len(lines))
    assert 6 <= got["pack.overlen_share.tput"] <= 10    # of 2,048 lines
    # the sub-spans bound the seconds the counter holds, over all rows
    assert got["splice.self_us_per_row.tput"] == pytest.approx(
        c["splice_seconds"] / c["batch_rows_real"] * 1e6, rel=0.2)
    assert got["splice.us_per_spliced_row.tput"] == pytest.approx(
        c["splice_seconds"] / c["splice_rows"] * 1e6)


@pytest.mark.parametrize("which", ["drain", "long"])
def test_on_the_parents_program_the_new_metrics_read_zero_or_nothing(
        which, drain_ctx, long_ctx):
    ctx = parents(drain_ctx if which == "drain" else long_ctx)
    got = read_all(ctx)
    # no counter of the new names: 0 over a denominator that exists,
    # nothing over one that does not; no splice sub-span: a true zero
    assert got == {"pack.overlen_share.tput": 0.0,
                   "splice.self_us_per_row.tput": 0.0,
                   "splice.us_per_spliced_row.tput": None}


@pytest.mark.parametrize("which", ["drain", "long"])
def test_untraced_the_span_metric_has_nothing_to_read(
        which, drain_ctx, long_ctx):
    ctx = dict(drain_ctx if which == "drain" else long_ctx, spans=None)
    got = read_all(ctx)
    assert got["splice.self_us_per_row.tput"] is None
    assert got["pack.overlen_share.tput"] is not None
    assert read_all(parents(ctx))["splice.self_us_per_row.tput"] is None


def test_with_no_over_length_row_at_all():
    # nor a row that falls back for another cause: high bytes, an
    # escaped value, more pairs than the first decode program takes
    lines = [ln for ln in lines_of("loghub_syslog", 1024)
             if len(ln) <= 512 and reference.gelf(ln) is not None
             and ln.isascii() and b'="' not in ln]
    assert len(lines) > 400
    ctx = program_ctx(lines)
    assert ctx["counters"]["splice_rows"] == 0
    assert not ctx["counters"].get("splice_seconds")
    assert read_all(ctx) == {"pack.overlen_share.tput": 0.0,
                             "splice.self_us_per_row.tput": 0.0,
                             "splice.us_per_spliced_row.tput": None}
    assert read_all(parents(ctx))["pack.overlen_share.tput"] == 0.0


@pytest.mark.parametrize("ctx", [
    {"counters": {}, "window": (0, 1), "window_s": 1.0, "spans": None},
    {"counters": {}, "window": (0, 1), "window_s": 1.0, "spans": []},
    {"counters": {"batch_rows_real": 0, "splice_rows": 0},
     "window": (0, 1), "window_s": 1.0,
     "spans": [{"t0": 0.0, "wall": 0.0, "rows": 0, "spans": [],
                "sub": []}]},
], ids=["no-counter", "no-batch", "a-batch-of-no-rows"])
def test_an_empty_window_reads_nothing(ctx):
    assert set(read_all(ctx).values()) == {None}


# ---- whole runs: both cells rehearse traced ---------------------------------

def rehearse(cell, trace, seed=2**31 + 31):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", "3",
         "--rehearse", "--trace", trace],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1]), p


@pytest.mark.parametrize("cell", ["backfill.drain", "backfill.longlines"])
def test_the_cell_rehearses_traced_to_exit_code_0(cell):
    result, p = rehearse(cell, "1")
    assert result["correct"] is True and result["failed"] == 0
    assert all(v == 0 and lim == 0 for v, lim in result["compared"].values())
    assert "breakdown" in result
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        mine = {m["name"] for m in json.load(f)["per_layer"]
                if cell in m["workloads"]}
    assert len(mine) == 29 and set(NEW) <= mine
    got = set(result["metrics"])
    assert got <= mine
    # on the CPU the device's plane is missing and nothing else
    assert mine - got <= {n for n in mine if n.startswith(
        ("device.idle_share", "kernels.hbm_roofline"))}
    said = [ln for ln in p.stdout.splitlines() if "nothing to read" in ln]
    assert len(said) == len(mine - got)
    if cell == "backfill.longlines":
        assert 7 <= result["metrics"]["pack.overlen_share.tput"]["value"] <= 9
        assert result["metrics"]["tiers.device_decode_share.tput"][
            "value"] > 90
        assert result["metrics"]["splice.self_us_per_row.tput"]["value"] > 0


def test_the_new_cell_rehearses_untraced_with_its_two_metrics():
    result, _p = rehearse("backfill.longlines", "0")
    assert result["correct"] is True
    assert sorted(result["metrics"]) == ["lines_per_s", "setup_s"]
    assert result["attempted"] > 10_000
