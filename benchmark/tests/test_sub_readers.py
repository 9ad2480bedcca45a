"""The two readers that came with the sub-spans: ``span_sub`` on
synthetic batch records, ``xplane_idle_under`` on synthetic planes in
the shape ``xplane.planes_of`` hands out.  Every expected number is
worked out by hand in the comments."""

import pytest

from benchmark.readers import span_sub, xplane_idle_under

WINDOW = (100_000_000, 200_000_000)     # microseconds: 100 s .. 200 s


def rec(t0, rows, sub=None, wall=0.0):
    r = {"t0": t0, "wall": wall, "rows": rows, "spans": []}
    if sub is not None:
        r["sub"] = [{"stage": s, "parent": p, "t0": a, "t1": b,
                     "thread": "t"} for s, p, a, b in sub]
    return r


def test_span_sub_sums_the_named_sub_spans_over_the_rows():
    spans = [
        rec(110.0, 1000, [("d2h", "fetch", 110.0, 110.002),
                          ("d2h", "fetch", 110.002, 110.003),
                          ("device_wait", "fetch", 109.9, 110.0)]),
        rec(120.0, 3000, [("d2h", "fetch", 120.0, 120.001)]),
        # began before the window opened, and after it closed: not read
        rec(99.0, 500, [("d2h", "fetch", 99.0, 99.5)]),
        rec(200.0, 500, [("d2h", "fetch", 200.0, 200.5)]),
    ]
    ctx = {"spans": spans, "window": WINDOW}
    # 2 ms + 1 ms + 1 ms over 4,000 rows = 1 us a row
    assert span_sub.read(ctx, ["d2h"]) == pytest.approx(1.0)
    # 100 ms over 4,000 rows
    assert span_sub.read(ctx, ["device_wait"]) == pytest.approx(25.0)
    assert span_sub.read(ctx, ["d2h", "device_wait"]) == pytest.approx(26.0)
    # batches with a sub list and none of that stage: a true zero
    assert span_sub.read(ctx, ["window_wait"]) == 0.0


def test_span_sub_places_a_batch_by_the_process_clock_offset():
    ctx = {"spans": [rec(10.0, 100, [("h2d", "decode", 10.0, 10.001)],
                         wall=100.0)], "window": WINDOW}
    assert span_sub.read(ctx, ["h2d"]) == pytest.approx(10.0)


@pytest.mark.parametrize("spans", [
    None, [],
    # a program from before the sub-spans: records without the list
    [rec(110.0, 1000)],
    # nothing began in the window
    [rec(50.0, 1000, [("d2h", "fetch", 50.0, 50.1)])],
], ids=["untraced", "empty", "parent", "outside"])
def test_span_sub_finds_nothing_to_read(spans):
    assert span_sub.read({"spans": spans, "window": WINDOW},
                         ["d2h"]) is None


# one device, busy 0..100 and 900..1000 ns of a slice 0..1000: idle is
# the 800 ns between them
DEVICE = {"XLA Ops": [("fusion.1", 0, 100), ("fusion.2", 900, 1000)],
          "XLA Modules": [("jit_f(1)", 0, 100), ("jit_f(1)", 900, 1000)]}


def planes(*threads, device=DEVICE):
    out = {"/host:CPU": {f"thread-{i}": list(evs)
                         for i, evs in enumerate(threads)}}
    if device is not None:
        out["/device:TPU:0"] = device
    return out


def under(p, *names):
    return xplane_idle_under.idle_share(p, {"under": list(names)})


def outside(p):
    return xplane_idle_under.idle_share(p, {"outside": "flowgger."})


def test_a_gap_fully_covered():
    p = planes([("flowgger.d2h", 50, 950)])
    assert under(p, "flowgger.d2h") == pytest.approx(100.0)
    assert outside(p) == pytest.approx(0.0)
    assert under(p, "flowgger.encode") == 0.0


def test_a_gap_half_covered():
    # 100..500 of the idle 100..900
    p = planes([("flowgger.d2h", 0, 500), ("pjit_dispatch", 500, 900)])
    assert under(p, "flowgger.d2h") == pytest.approx(50.0)
    # JAX's own host events attribute nothing
    assert outside(p) == pytest.approx(50.0)


def test_two_threads_overlapping_count_once():
    # thread 0 covers 200..600, thread 1 300..700: the union 200..700
    # is 500 of 800 ns; encode alone 300..700 is 400
    p = planes([("flowgger.d2h#batch=7#", 200, 400),
                ("flowgger.d2h", 400, 600)],
               [("flowgger.encode", 300, 700)])
    assert under(p, "flowgger.d2h", "flowgger.encode") == \
        pytest.approx(62.5)
    assert under(p, "flowgger.d2h") == pytest.approx(50.0)
    assert under(p, "flowgger.encode") == pytest.approx(50.0)
    assert outside(p) == pytest.approx(37.5)


def test_the_slice_runs_from_the_first_event_to_the_last():
    # the host wrote before the device's first op and after its last:
    # idle is -200..0, 100..900 and 1000..1100 = 1,100 ns, 900 covered
    p = planes([("flowgger.pack", -200, 0), ("flowgger.fetch", 100, 800),
                ("flowgger.emit", 1000, 1100)])
    assert outside(p) == pytest.approx(100.0 * 100 / 1100)


@pytest.mark.parametrize("p", [
    {"/device:TPU:0": DEVICE},                          # no host plane
    planes([("pjit_dispatch", 0, 1000)]),               # no annotation
    planes([("flowgger.d2h", 0, 1000)], device=None),   # no device
    # a device that never idled
    planes([("flowgger.d2h", 0, 1000)],
           device={"XLA Ops": [("fusion.1", 0, 1000)]}),
], ids=["no-host-plane", "parent", "no-device", "never-idle"])
def test_idle_under_finds_nothing_to_read(p):
    assert under(p, "flowgger.d2h") is None
    assert outside(p) is None


def test_a_stage_longer_than_the_slice_is_placed_from_the_tracer():
    # the fetcher sat in one fetch from before the slice to after it: no
    # annotation of it is in the file, the tracer has it.  The trace's
    # clock reads 0 at epoch second 1,000; the process's perf_counter
    # is 900 s behind the wall clock.
    p = planes([("flowgger.pack", 0, 100)])
    assert outside(p) == pytest.approx(100.0)
    spans = [{"wall": 900.0, "rows": 1, "t0": 99.0,
              "spans": [{"stage": "fetch", "thread": "f",
                         "t0": 99.9999990, "t1": 100.0000020}],
              "sub": [{"stage": "d2h", "parent": "fetch", "thread": "f",
                       "t0": 100.0000001, "t1": 100.0000005}]}]
    placed = xplane_idle_under.placed_spans(spans, 1_000 * 10**9)
    assert [n for n, _s, _e in placed] == ["flowgger.fetch", "flowgger.d2h"]
    assert placed[0][1] == pytest.approx(-1000, abs=1)
    assert placed[0][2] == pytest.approx(2000, abs=1)
    assert xplane_idle_under.idle_share(
        p, {"outside": "flowgger."}, placed) == pytest.approx(0.0, abs=0.2)
    # the copy inside it covers 100..500 of the idle 100..900
    assert xplane_idle_under.idle_share(
        p, {"under": ["flowgger.d2h"]}, placed) == pytest.approx(50, abs=0.2)
    # a program from before the annotations stays unread, spans or not
    assert xplane_idle_under.idle_share(
        planes([("pjit_dispatch", 0, 1000)]), {"outside": "flowgger."},
        placed) is None
    assert xplane_idle_under.placed_spans(spans, None) == []
    assert xplane_idle_under.placed_spans(None, 5) == []


def test_the_recorded_trace_has_no_start_time_and_that_is_no_error():
    import os

    path = os.path.join(os.path.dirname(__file__), "data",
                        "drain_v5e_slice.xplane.pb")
    assert xplane_idle_under.profile_start_ns(path) is None


def test_read_parses_the_trace_once_for_all_its_metrics(monkeypatch):
    calls = []
    monkeypatch.setattr(xplane_idle_under.xplane, "find",
                        lambda d: d + "/x.xplane.pb")
    monkeypatch.setattr(xplane_idle_under, "profile_start_ns",
                        lambda path: None)
    monkeypatch.setattr(
        xplane_idle_under.xplane, "planes_of",
        lambda path: calls.append(path) or planes(
            [("flowgger.d2h", 50, 950)]))
    ctx = {"trace_dir": "/t"}
    assert xplane_idle_under.read(ctx, {"under": ["flowgger.d2h"]}) == \
        pytest.approx(100.0)
    assert xplane_idle_under.read(ctx, {"outside": "flowgger."}) == \
        pytest.approx(0.0)
    assert calls == ["/t/x.xplane.pb"]
    assert xplane_idle_under.read({"trace_dir": None}, {"under": []}) is None
