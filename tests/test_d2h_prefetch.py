"""A decode program's device-to-host copies are begun together at its
dispatch and collected with one wait (tpu/device_common.py ``d2h_begin``
/ ``d2h_all``; tpu/rfc5424.py submit, fetch and pair rescue).

On the CPU this proves identity and counts, never a rate: the host
channels are the program's own arrays, the programs are the ones that
were there, the sink holds the scalar pipeline's bytes, and the link
counters say one wait per program."""

import queue
import threading

import jax
import numpy as np
import pytest

from flowgger_tpu.config import Config
from flowgger_tpu.decoders import DecodeError, RFC5424Decoder
from flowgger_tpu.encoders import GelfEncoder
from flowgger_tpu.mergers import NulMerger
from flowgger_tpu.obs import trace as obs_trace
from flowgger_tpu.tpu import device_common, pack, rfc5424
from flowgger_tpu.utils.metrics import registry

MAX_LEN = 512
CHANNELS = 31

PLAIN = [b"<13>1 2015-08-05T15:53:45Z h a p m - hello %d" % i
         for i in range(5)]
MIXED = PLAIN + [
    b"not a syslog line",
    b'<13>1 2015-08-05T15:53:45.637824Z h a p m [x@1 k="v"] tail',
    b'<165>1 2003-10-11T22:14:15.003Z mymachine evntslog - ID47 '
    b'[exampleSDID@32473 iut="3" eventSource="App \\"quoted\\""] BOM',
]
# 7 SD pairs: over DEFAULT_MAX_PAIRS, so the row takes the pair rescue
SEVEN = (b'<13>1 2015-08-05T15:53:45Z h a p m [a@1 k1="1" k2="2" '
         b'k3="3" k4="4"][b@1 k5="5" k6="6" k7="7"] rescued')


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
    ``backfill.drain`` serves nearly all its rows on."""
    monkeypatch.setenv("FLOWGGER_DEVICE_ENCODE", "0")
    return '[input]\ntpu_fuse = "off"\n'


def _packed(lines, max_len=MAX_LEN):
    return pack.pack_lines_2d(lines, max_len)[:2]


def _program(batch, lens, max_pairs):
    out = rfc5424.decode_rfc5424_jit(
        batch, lens, max_pairs=max_pairs,
        extract_impl=rfc5424.best_extract_impl())
    return {k: np.asarray(v) for k, v in out.items()}


def _assert_same_arrays(got, want):
    assert list(got) == list(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        assert got[k].shape == want[k].shape, k
        assert np.array_equal(got[k], want[k]), k


# ---- the host channels are the program's own arrays ------------------------

@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("rescued", [False, True], ids=["plain", "rescued"])
def test_fetch_returns_the_programs_outputs_array_for_array(rescued, traced):
    lines = MIXED + ([SEVEN] if rescued else [])
    batch, lens = _packed(lines)
    if traced:
        obs_trace.tracer.configure("ring")
    host = rfc5424.decode_rfc5424_fetch(
        rfc5424.decode_rfc5424_submit(batch, lens))
    first = _program(batch, lens, rfc5424.DEFAULT_MAX_PAIRS)
    if not rescued:
        _assert_same_arrays(host, first)
        return
    # the rescued row reads as the wide program decodes it, every other
    # row as the first program did, pair channels widened with zeros
    wide = _program(batch, lens, rfc5424.RESCUE_MAX_PAIRS)
    row = len(lines) - 1
    assert first["pair_count"][row] == 7
    want = {}
    for k, v in first.items():
        if k in rfc5424._PAIR_KEYS:
            w = np.zeros((v.shape[0], rfc5424.RESCUE_MAX_PAIRS), v.dtype)
            w[:, :v.shape[1]] = v
        else:
            w = v.copy()
        w[row] = wide[k][row]
        want[k] = w
    _assert_same_arrays(host, want)
    assert host["ok"][row] and not first["ok"][row]


def test_fetch_without_a_begin_gives_the_same_arrays():
    """A handle whose copies were not begun (made by other code than
    the submit) is fetched all the same: ``d2h_all`` then makes the
    copies itself."""
    batch, lens = _packed(MIXED)
    out = rfc5424.decode_rfc5424_jit(
        jax.numpy.asarray(batch), jax.numpy.asarray(lens),
        extract_impl=rfc5424.best_extract_impl())
    host = rfc5424.decode_rfc5424_fetch(
        (out, batch, lens, rfc5424.DEFAULT_MAX_SD, "sum"))
    _assert_same_arrays(host, _program(batch, lens,
                                       rfc5424.DEFAULT_MAX_PAIRS))
    assert registry.get("d2h_prefetched") == 0
    assert registry.get("d2h_calls") == 1


# ---- the programs are the ones that were there ------------------------------

@pytest.mark.parametrize("rows,max_pairs", [
    (256, rfc5424.DEFAULT_MAX_PAIRS),      # the batch's program here
    (256, rfc5424.RESCUE_MAX_PAIRS),       # the rescue's
    (16384, rfc5424.DEFAULT_MAX_PAIRS),    # a full batch's bucket
], ids=["first", "rescue", "full-batch"])
def test_a_submit_and_fetch_leaves_the_lowered_program_as_it_was(
        rows, max_pairs):
    """The persistent cache's key is made from the lowered module: the
    copies are asked for outside it, so it reads the same before and
    after a batch went through the helper."""
    shapes = (jax.ShapeDtypeStruct((rows, MAX_LEN), np.uint8),
              jax.ShapeDtypeStruct((rows,), np.int32))

    def lowered():
        return rfc5424.decode_rfc5424_jit.lower(
            *shapes, max_sd=rfc5424.DEFAULT_MAX_SD, max_pairs=max_pairs,
            extract_impl="sum").as_text()

    before = lowered()
    batch, lens = _packed(MIXED + [SEVEN])
    rfc5424.decode_rfc5424_fetch(rfc5424.decode_rfc5424_submit(batch, lens))
    assert registry.get("d2h_prefetched") == 2 * CHANNELS
    assert lowered() == before


def test_a_batch_with_a_rescue_runs_two_programs_and_no_third():
    """No second family of decode programs beside the prewarmed one: a
    shape met for the first time adds the batch's program and the
    rescue's to the jit's cache, and a second batch adds none."""
    lines = MIXED + [SEVEN]
    batch, lens = _packed(lines, max_len=384)    # a width no test shares
    size0 = rfc5424.decode_rfc5424_jit._cache_size()
    rfc5424.decode_rfc5424_fetch(rfc5424.decode_rfc5424_submit(batch, lens))
    assert rfc5424.decode_rfc5424_jit._cache_size() == size0 + 2
    batch, lens = _packed(lines[::-1], max_len=384)
    rfc5424.decode_rfc5424_fetch(rfc5424.decode_rfc5424_submit(batch, lens))
    assert rfc5424.decode_rfc5424_jit._cache_size() == size0 + 2


# ---- the helper -------------------------------------------------------------

class _Leaf:
    """Stands for a device array: says what was asked of it."""

    def __init__(self, log, name):
        self.log, self.name = log, name

    def copy_to_host_async(self):
        self.log.append(("begin", self.name))

    def __array__(self, *a, **kw):
        raise AssertionError("d2h_begin materialised " + self.name)


def test_begin_asks_every_leaf_once_and_materialises_none():
    log = []
    out = {k: _Leaf(log, k) for k in ("ok", "days", "name_start")}
    assert device_common.d2h_begin(out) is out
    assert log == [("begin", "days"), ("begin", "name_start"),
                   ("begin", "ok")]             # a dict's leaves, sorted
    assert registry.get("d2h_prefetched") == 3
    assert registry.get("d2h_calls") == 0
    assert registry.get("d2h_bytes") == 0


def _device_dict():
    return {"ok": jax.numpy.asarray(np.array([True, False, True])),
            "pair_count": jax.numpy.asarray(np.arange(3, dtype=np.int16)),
            "name_start": jax.numpy.asarray(
                np.arange(18, dtype=np.int32).reshape(3, 6))}


@pytest.mark.parametrize("begun", [True, False], ids=["begun", "not-begun"])
def test_all_keeps_order_dtype_and_bytes_and_blocks_once(begun):
    out = _device_dict()
    if begun:
        device_common.d2h_begin(out)
    host = device_common.d2h_all(out)
    _assert_same_arrays(host, {k: np.asarray(v) for k, v in out.items()})
    assert list(host) == ["ok", "pair_count", "name_start"]
    assert all(type(v) is np.ndarray for v in host.values())
    assert registry.get("d2h_calls") == 1
    assert registry.get("d2h_bytes") == 3 + 6 + 72
    assert registry.get("d2h_prefetched") == (3 if begun else 0)


def test_all_is_one_sub_span_of_fetch_for_the_program():
    obs_trace.tracer.configure("ring")
    bid = obs_trace.tracer.begin("t")
    host = device_common.d2h_all(device_common.d2h_begin(_device_dict()))
    rec = next(r for r in obs_trace.tracer._open.values()
               if r["bid"] == bid)
    assert [(sp["stage"], sp["parent"], sp["note"], sp["bytes"])
            for sp in rec["sub"]] == [("d2h", "fetch", "3", 81)]
    assert len(host) == 3
    obs_trace.tracer.end(bid)


def test_the_single_copy_keeps_its_count():
    """``fetch_encode_driver._fetch``'s copies depend on one another and
    stay one blocking call each."""
    arr = jax.numpy.arange(8, dtype=np.int32)
    for _ in range(3):
        assert np.array_equal(device_common.d2h(arr), np.arange(8))
    assert registry.get("d2h_calls") == 3
    assert registry.get("d2h_bytes") == 3 * 32
    assert registry.get("d2h_prefetched") == 0


# ---- the served route -------------------------------------------------------

def _scalar_frames(lines, cfg):
    dec, enc, merger = RFC5424Decoder(), GelfEncoder(cfg), NulMerger(cfg)
    out = []
    for ln in lines:
        try:
            rec = dec.decode(ln.decode("utf-8"))
        except (DecodeError, UnicodeDecodeError):
            continue
        out.append(merger.frame(enc.encode(rec)))
    return b"".join(out)


def _run_batches(cfg_text, batches):
    """Each entry of ``batches`` is flushed as a device batch of its
    own; the fetcher works a batch behind the ingest thread."""
    from flowgger_tpu.tpu.batch import BatchHandler

    cfg = Config.from_string(cfg_text)
    tx = queue.Queue()
    h = BatchHandler(tx, RFC5424Decoder(), GelfEncoder(cfg), cfg,
                     start_timer=False, merger=NulMerger(cfg))
    h.ingest_sep = b"\n"
    h.ingest_strip_cr = True
    try:
        for lines in batches:
            h.ingest_chunk(b"".join(ln + b"\n" for ln in lines))
            h.flush()
    finally:
        h.close()
    out = []
    while not tx.empty():
        item = tx.get()
        out.append(bytes(getattr(item, "data", item)))
    return b"".join(out), cfg


_PATTERNS = {
    "no-rescue": [MIXED, PLAIN, MIXED],
    "every-batch": [MIXED + [SEVEN], [SEVEN] + PLAIN, PLAIN + [SEVEN] * 3],
    "some-batches": [MIXED, [SEVEN] + MIXED, PLAIN, MIXED + [SEVEN]],
}


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("pattern", sorted(_PATTERNS))
def test_sink_bytes_are_the_scalar_pipelines(host_route, pattern, traced):
    batches = _PATTERNS[pattern]
    if traced:
        obs_trace.tracer.configure("ring")
    got, cfg = _run_batches(host_route, batches)
    assert got == _scalar_frames([ln for b in batches for ln in b], cfg)
    assert got.count(b'"short_message":"rescued"') == sum(
        b.count(SEVEN) for b in batches)
    # one wait per program, every channel of each begun at its dispatch
    programs = len(batches) + sum(1 for b in batches if SEVEN in b)
    snap = registry.snapshot()
    assert snap["batches"] == len(batches)
    assert snap["d2h_calls"] == programs
    assert snap["d2h_prefetched"] == CHANNELS * programs
    assert snap.get("fallback_rows", 0) == sum(
        b.count(b"not a syslog line") for b in batches)


def test_copies_begin_on_the_ingest_thread_and_end_on_the_fetcher(
        host_route, monkeypatch):
    """The first program's copies are begun by the thread that
    dispatched it; the fetcher waits for them a batch later, and both
    begins and waits for the rescue's."""
    seen = []
    begin, collect = device_common.d2h_begin, device_common.d2h_all

    def spy_begin(out):
        seen.append(("begin", threading.current_thread().name))
        return begin(out)

    def spy_all(out):
        seen.append(("all", threading.current_thread().name))
        return collect(out)

    monkeypatch.setattr(device_common, "d2h_begin", spy_begin)
    monkeypatch.setattr(device_common, "d2h_all", spy_all)
    _run_batches(host_route, [MIXED + [SEVEN]])
    me = threading.current_thread().name
    assert [k for k, _t in seen] == ["begin", "all", "begin", "all"]
    assert seen[0][1] == me
    fetcher = {t for _k, t in seen[1:]}
    assert len(fetcher) == 1 and me not in fetcher


def test_two_lanes_take_the_same_call(host_route):
    batches = [MIXED + [SEVEN], PLAIN, [SEVEN] + PLAIN, MIXED]
    got, cfg = _run_batches(host_route + "tpu_lanes = 2\n", batches)
    assert got == _scalar_frames([ln for b in batches for ln in b], cfg)
    assert registry.get("d2h_calls") == 6
    assert registry.get("d2h_prefetched") == CHANNELS * 6


def test_the_mesh_programs_outputs_take_the_same_call():
    from flowgger_tpu.parallel import mesh as mesh_mod

    lines = (MIXED + [SEVEN]) * 4
    batch, lens = _packed(lines)
    sharded = mesh_mod.ShardedDecode(
        mesh_mod.make_decode_mesh(jax.devices(), sp=1), "rfc5424")
    handle = rfc5424.decode_rfc5424_submit(batch, lens, sharded=sharded)
    assert registry.get("d2h_prefetched") == CHANNELS
    assert len(handle[0]["ok"].sharding.device_set) == len(jax.devices())
    host = rfc5424.decode_rfc5424_fetch(handle)
    single = rfc5424.decode_rfc5424_host(batch, lens)
    _assert_same_arrays(host, single)
    assert registry.get("d2h_calls") == 4      # two programs, twice


# ---- who else runs the shared code ------------------------------------------

@pytest.mark.parametrize("fmt", ["gelf", "jsonl"])
def test_other_formats_rescue_keeps_its_contract(fmt):
    """``rescue_refetch`` is shared with formats whose fetch is a bare
    ``np.asarray`` per channel: they dispatch, fetch and merge as before
    and touch none of the link's counters."""
    import json

    from flowgger_tpu.tpu import gelf, jsonl

    wide = {"version": "1.1", "host": "h", "short_message": "m"}
    wide.update({"_k%d" % i: "v%d" % i for i in range(20)})
    lines = [json.dumps({"version": "1.1", "host": "h",
                         "short_message": "m %d" % i}).encode()
             for i in range(4)] + [json.dumps(wide).encode()]
    batch, lens = _packed(lines)
    before = registry.snapshot()
    if fmt == "gelf":
        host = gelf.decode_gelf_fetch(gelf.decode_gelf_submit(batch, lens))
        assert host["key_start"].shape[1] == gelf.RESCUE_MAX_FIELDS
    else:
        host = jsonl.decode_jsonl_fetch(
            jsonl.decode_jsonl_submit(batch, lens))
    assert bool(host["ok"][len(lines) - 1])
    snap = registry.snapshot()
    for name in ("d2h_calls", "d2h_prefetched", "d2h_bytes"):
        assert snap[name] == before[name], name
