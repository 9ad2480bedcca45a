"""The reduction from a profiler trace to busy time, idle share and top
ops, on a small trace recorded on the chip: the first 0.45 s of the
profiler's slice of a ``backfill.drain`` run on a TPU v5 lite (PR 25),
cut down with TensorFlow's ``xplane_pb2`` to the device plane and three
host lines, event stats dropped.  The numbers it is held to were worked
out from the protobuf itself, not by the code under test."""

import os

import pytest

from benchmark import xplane
from benchmark.readers import xplane_idle

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "drain_v5e_slice.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(TRACE)


def test_union_merges_overlaps_and_keeps_gaps():
    assert xplane.union([(5, 7), (1, 3), (2, 4), (7, 8), (10, 11)]) \
        == [[1, 4], [5, 8], [10, 11]]


def test_busy_is_the_union_of_the_op_intervals(reduced):
    assert reduced["devices"] == 1
    # (the reader hands out whole nanoseconds: 1,935 events lose ~1 us)
    assert reduced["busy_s"] == pytest.approx(7917900312e-12, rel=1e-3)
    assert reduced["window_s"] == pytest.approx(0.342779181, rel=1e-6)


def test_idle_share(reduced):
    assert xplane_idle.read({"profile": reduced}, None) == pytest.approx(
        100.0 * (1 - 7917900312e-12 / 0.342779181), abs=1e-3)
    assert xplane_idle.read({"profile": None}, None) is None
    assert xplane_idle.read({"profile": dict(reduced, devices=0)},
                            None) is None


def test_programs_and_top_ops(reduced):
    assert reduced["programs"][0][0] == "jit_decode_rfc5424_jit"
    assert reduced["programs"][0][1] == pytest.approx(
        (7722139766 + 202247344) * 1e-12, rel=1e-4)
    ops = reduced["top_ops"]
    assert ops[0][0] == "program jit_decode_rfc5424_jit"
    assert 1 < len(ops) <= xplane.TOP
    assert all(name.startswith("jit_decode_rfc5424_jit/")
               for name, _s in ops[1:])
    secs = [s for _n, s in ops[1:]]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= reduced["busy_s"] * 1.0001


def test_idle_gaps_are_the_complement_and_unattributed(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) == xplane.TOP
    assert {label for label, _s in gaps} == {"unattributed"}
    assert gaps[0][1] >= gaps[-1][1] > 0
    # five batches in the slice: the long gaps are the waits between them
    assert gaps[0][1] > 0.03
    assert sum(s for _l, s in gaps) <= reduced["window_s"] - reduced["busy_s"]


def test_an_empty_trace_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce_planes({"/device:TPU:0": {"XLA Ops": []}})


def test_the_roofline_counts_what_the_device_served_and_no_more():
    from benchmark.readers import xplane_hbm

    args = {"channel_bytes_per_row": 100}
    ctx = {"profile": {"busy_s": 0.05}, "peaks": {"hbm_bytes_per_s": 819e9},
           "line_bytes": 166.0, "record_bytes": 429.0,
           "slice_counters": {"input_lines": 500_000, "fallback_rows": 1_000,
                              "device_encode_rows": 0}}
    want = 100.0 * 499_000 * 266.0 / 0.05 / 819e9
    assert xplane_hbm.read(ctx, args) == pytest.approx(want)
    # rows the device encoded as well count their records besides
    ctx["slice_counters"]["device_encode_rows"] = 16_000
    assert xplane_hbm.read(ctx, args) == pytest.approx(
        want + 100.0 * 16_000 * 429.0 / 0.05 / 819e9)
    # nothing decoded while the profiler listened, or a device that
    # never ran: nothing to read, never 0
    for broken in ({"slice_counters": {"input_lines": 0}},
                   {"profile": {"busy_s": 0.0}}, {"profile": None},
                   {"peaks": None}):
        assert xplane_hbm.read(dict(ctx, **broken), args) is None
