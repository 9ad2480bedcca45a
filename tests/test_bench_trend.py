"""tools/bench_trend.py: the BENCH_r01..rNN trajectory aggregator and
its CI --check contract (a malformed new BENCH entry must fail fast;
the backfilled r06 metadata stub must not)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import bench_trend  # noqa: E402


def test_repo_series_parses_clean():
    rows = bench_trend.load_series(REPO)
    assert len(rows) >= 10
    assert bench_trend.check(rows) == []
    text = bench_trend.table(rows)
    # the first entry (r04: r01-r03 were deleted records of a retired
    # set-up), a bytes/row pair, and the r06 stub all land
    assert "BENCH_r04.json" in text
    assert "stub: backfilled in PR 10" in text


def test_extract_handles_heterogeneous_schemas():
    r01 = {"parsed": {"metric": "lines_per_sec", "value": 40028,
                      "unit": "lps"}}
    ex = bench_trend.extract(r01)
    assert ex["lines_per_sec"] == {"parsed.lines_per_sec": 40028.0}
    nested = {"pr": 7, "fused_routes": {"ok": True, "routes": {
        "a": {"fetch_bytes_per_row": 10.0, "emit_bytes_per_row": 20.0,
              "lines_per_sec": 5}}}}
    ex = bench_trend.extract(nested)
    assert ex["gates"] == {"fused_routes.ok": True}
    assert list(ex["fetch_bytes_per_row"].values()) == [10.0]
    assert list(ex["emit_bytes_per_row"].values()) == [20.0]


def test_check_flags_malformed_entries(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text('{"not": "a metric"}')
    (tmp_path / "BENCH_r02.json").write_text("{broken json")
    (tmp_path / "BENCH_r03.json").write_text('["a", "list"]')
    (tmp_path / "BENCH_r04.json").write_text(
        '{"backfilled_in_pr": 99}')  # marked stub: allowed
    rows = bench_trend.load_series(str(tmp_path))
    bad = bench_trend.check(rows)
    assert len(bad) == 3
    assert any("BENCH_r01" in b for b in bad)
    assert any("BENCH_r02" in b for b in bad)
    assert any("BENCH_r03" in b for b in bad)


def test_check_flags_series_gaps(tmp_path):
    """A missing BENCH_rNN between the lowest and highest committed
    entry is a finding (the r06/r11 lesson): a new PR cannot skip its
    snapshot silently, but a marked backfill stub closes a hole."""
    ok_doc = '{"pr": 1, "x_lines_per_sec": 1.0}'
    (tmp_path / "BENCH_r01.json").write_text(ok_doc)
    (tmp_path / "BENCH_r03.json").write_text(ok_doc)
    bad = bench_trend.check(bench_trend.load_series(str(tmp_path)))
    assert len(bad) == 1
    assert "BENCH_r02.json is missing" in bad[0]
    assert "backfilled_in_pr" in bad[0]
    # a marked stub closes the gap
    (tmp_path / "BENCH_r02.json").write_text('{"backfilled_in_pr": 99}')
    assert bench_trend.check(
        bench_trend.load_series(str(tmp_path))) == []
    # leading entries below the series start are NOT gaps (the series
    # starts wherever it starts)
    os.unlink(tmp_path / "BENCH_r01.json")
    os.unlink(tmp_path / "BENCH_r02.json")
    assert bench_trend.check(
        bench_trend.load_series(str(tmp_path))) == []


def test_cli_check_exit_codes(tmp_path):
    ok = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_trend.py"),
         "--check", REPO], capture_output=True, text=True)
    assert ok.returncode == 0, ok.stderr
    (tmp_path / "BENCH_r01.json").write_text("nope")
    bad = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_trend.py"),
         "--check", str(tmp_path)], capture_output=True, text=True)
    assert bad.returncode == 2
    assert "unreadable" in bad.stderr


def test_json_mode_emits_rows():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_trend.py"),
         "--json", REPO], capture_output=True, text=True)
    assert r.returncode == 0
    payload = json.loads(r.stdout)
    assert len(payload) >= 10
    assert payload[0]["entry"] == "BENCH_r04.json"
