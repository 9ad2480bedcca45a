"""Differential tests: columnar JSON-lines decoder + block routes vs
the scalar oracle (flowgger_tpu/decoders/jsonl.py).

Kernel identity runs eagerly (``jax.disable_jit()``) so the claims
hold even on hosts whose XLA is slow to compile; one small compiled
decode keeps the jit path honest."""

import queue
import re
import time

import jax
import pytest

from flowgger_tpu.block import EncodedBlock
from flowgger_tpu.config import Config
from flowgger_tpu.decoders import DecodeError, JSONLDecoder
from flowgger_tpu.encoders.gelf import GelfEncoder
from flowgger_tpu.encoders.ltsv import LTSVEncoder
from flowgger_tpu.mergers import LineMerger, NulMerger, SyslenMerger
from flowgger_tpu.tpu.batch import BatchHandler, _decode_jsonl_batch

CFG = Config.from_string("[input]\ntpu_max_line_len = 160\n")
ORACLE = JSONLDecoder()

CORPUS = [
    '{"timestamp":1438790025.42,"host":"h1","message":"hello world",'
    '"level":3,"user":"bob","n":42}',
    '{"host":"h"}',                              # no timestamp -> now()
    '{"timestamp":1,"host":"h"}',
    '{"timestamp":-1.5,"host":"h"}',
    '{"timestamp":2,"x":null,"b":true,"c":false}',
    '{"timestamp":3,"n":-3,"f":1.5,"big":18446744073709551615}',
    '{"timestamp":4,"esc":"a\\"b\\\\c\\n\\u00e9"}',
    '{"timestamp":5,"uni":"ünïcode"}',
    '{ "timestamp" : 6 , "k" : "v" }',           # whitespace everywhere
    '{"timestamp":7,"z":1,"a":2,"m":3}',         # sorted pair order
    '{"timestamp":8,"dup":1,"dup":2}',           # duplicates: last wins
    '{"timestamp":9,"_pre":"kept","x":"_prefixed"}',
    '{"timestamp":10,"empty":""}',
    # nested containers: VT_OBJECT/VT_ARRAY spans up to the depth cap
    '{"timestamp":11,"k":{"a":1,"b":[2,3]},"z":"s"}',
    '{"timestamp":12,"k":[{"x":"}"},null]}',
    '{"timestamp":13,"k":{}}',
    '{"timestamp":14,"deep":{"a":{"b":{"c":{"d":{"e":1}}}}}}',
    '{"timestamp":15,"short_message":"a pair, not a special"}',
    '{"timestamp":16,"version":"1.1"}',          # pair too (no handshake)
    "{}",
    '{"timestamp":"a string"}',
    '{"host": 42}',
    '{"message": 42, "timestamp":17}',
    '{"level": 8, "timestamp":18}',
    '{"level": true, "timestamp":19}',
    "[1,2,3]",
    "not json at all",
    "",
    '{"timestamp":20,}',
    '{"timestamp":21 "k":1}',
    '{"timestamp":22,"k":}',
    '{"timestamp":23,"k":01}',
    '{"timestamp":24,"k":truex}',
    '{"timestamp":25,"k":[1,2}',                 # mismatched brackets
]


def run_both(lines):
    raw = [ln.encode("utf-8") for ln in lines]
    with jax.disable_jit():
        results = _decode_jsonl_batch(raw, 160)
    pairs = []
    for ln, res in zip(lines, results):
        kernel = ("rec", res.record) if res.record is not None else \
            ("err", res.error)
        try:
            oracle = ("rec", ORACLE.decode(ln))
        except DecodeError as e:
            oracle = ("err", str(e))
        pairs.append((ln, kernel, oracle))
    return pairs


def test_corpus_differential():
    for ln, kernel, oracle in run_both(CORPUS):
        if kernel[0] == "rec" and oracle[0] == "rec" \
                and '"timestamp"' not in ln:
            krec, orec = kernel[1], oracle[1]
            assert abs(krec.ts - orec.ts) < 5, ln
            krec.ts = orec.ts
        assert kernel == oracle, (
            f"divergence on {ln!r}:\n  kernel: {kernel}\n  oracle: {oracle}")


def test_corpus_differential_under_the_tpus_scan_lowering(monkeypatch):
    """The oracle differential again with the scans lowered as every TPU
    run lowers them (MXU tri-matmul), which the CPU backend never picks
    of itself (``run_both`` is eager: no cached CPU trace answers)."""
    from flowgger_tpu.tpu import aot

    monkeypatch.setattr(aot, "_scan_impl_for", lambda platform: "mm")
    test_corpus_differential()


@pytest.mark.parametrize("name,nested,max_len", [
    ("jsonl", 4, 160),      # the JSON-lines decoder's index (depth channel)
    ("gelf", 0, 256),       # the GELF screen: flat only, no depth channel
    ("esc_cap", 4, 64),     # backslash runs of 15, 16 and 21 before a quote
    ("wide", 4, 4608),      # past L = 4094: one int8 matmul per channel
])
def test_structural_index_mm_matches_lax(name, nested, max_len):
    """scan_impl='mm' (MXU tri-matmul scans, what every TPU run lowers)
    against 'lax' (the CPU's cumsum), channel for channel, for the
    structural index both JSON decoders ride."""
    import jax.numpy as jnp
    import numpy as np

    from flowgger_tpu.tpu import jsonidx, pack

    if name == "gelf":
        from test_tpu_gelf_auto import CORPUS as GELF_CORPUS

        lines = [ln.encode("utf-8") for ln in GELF_CORPUS]
    elif name == "esc_cap":
        lines = [b'{"s":"' + b"\\" * nbs + b'q"}' for nbs in (15, 16, 21)]
    else:
        lines = BLOCK_CORPUS
    batch, lens, *_ = pack.pack_lines_2d(lines, max_len)
    a, b = (jax.jit(lambda bt, ln, impl=impl: jsonidx.structural_index(
        bt, ln, max_fields=8, scan_impl=impl, extract_impl="sum",
        nested=nested))(jnp.asarray(batch), jnp.asarray(lens))
        for impl in ("lax", "mm"))
    assert set(a) == set(b)
    for k in a:
        assert (np.asarray(a[k]) == np.asarray(b[k])).all(), k
    assert np.asarray(a["ok"]).any() or name == "esc_cap"


def test_nested_spans_on_tier():
    """Depth-capped nested containers decode as spans (ok=True), only
    beyond-cap rows fall back."""
    import numpy as np
    import jax.numpy as jnp

    from flowgger_tpu.tpu import jsonl, pack

    lines = [
        b'{"timestamp":1,"k":{"a":[1,2],"b":"x"}}',
        b'{"timestamp":2,"k":[[[1]]]}',          # within the cap
        b'{"timestamp":3,"k":[[[[[1]]]]]}',      # beyond the cap
    ]
    batch, lens, chunk, starts, orig, n = pack.pack_lines_2d(lines, 256)
    with jax.disable_jit():
        out = jsonl.decode_jsonl(jnp.asarray(batch), jnp.asarray(lens))
    ok = np.asarray(out["ok"])[:n]
    assert ok.tolist() == [True, True, False]


@pytest.mark.slow
def test_rescue_tier_wide_rows():
    """9..24 fields re-dispatch through the wider kernel instead of the
    oracle.  Slow-marked for the tier-1 wall budget; ci.sh's
    new-format step runs it."""
    import numpy as np
    import jax.numpy as jnp

    from flowgger_tpu.tpu import jsonl, pack

    wide = ('{"timestamp":1,' + ",".join(
        f'"k{i:02d}":"v{i}"' for i in range(14)) + "}").encode()
    batch, lens, chunk, starts, orig, n = pack.pack_lines_2d(
        [wide] * 3, 320)
    with jax.disable_jit():
        host = jsonl.decode_jsonl_fetch(
            jsonl.decode_jsonl_submit(batch, lens))
    assert host["key_start"].shape[1] == jsonl.RESCUE_MAX_FIELDS
    assert bool(host["ok"][0]) and int(host["n_fields"][0]) == 15


def _norm(bs: bytes) -> bytes:
    """Mask now()-stamps (rows whose input lacked a timestamp differ
    between runs) and any syslen prefix their width perturbs."""
    def repl(m):
        try:
            v = float(m.group(2))
        except ValueError:
            return m.group(0)
        if abs(v - time.time()) < 86400:
            return m.group(1) + b"NOW"
        return m.group(0)

    out = re.sub(rb'("timestamp":|time:)([0-9.e+-]+)', repl, bs)
    if b"NOW" in out:
        out = re.sub(rb"^[0-9]+ ", b"LEN ", out)
    return out


def _run_block(lines, enc_cls, merger, cfg=CFG, fmt="jsonl"):
    dec = JSONLDecoder(cfg)
    enc = enc_cls(cfg)
    want = []
    for ln in lines:
        try:
            want.append(merger.frame(enc.encode(dec.decode(
                ln.decode("utf-8")))))
        except Exception:
            continue
    tx = queue.Queue()
    with jax.disable_jit():
        h = BatchHandler(tx, dec, enc, cfg, fmt=fmt, start_timer=False,
                         merger=merger)
        for ln in lines:
            h.handle_bytes(ln)
        h.flush()
        h.close()
    got = []
    while not tx.empty():
        item = tx.get_nowait()
        if isinstance(item, EncodedBlock):
            got.extend(item.iter_framed())
        else:
            got.append(merger.frame(item))
    return [_norm(x) for x in got], [_norm(x) for x in want]


BLOCK_CORPUS = [ln.encode("utf-8") for ln in CORPUS]


@pytest.mark.parametrize("merger_cls", [LineMerger, NulMerger,
                                        SyslenMerger])
def test_jsonl_gelf_block_matches_scalar(merger_cls):
    got, want = _run_block(BLOCK_CORPUS, GelfEncoder, merger_cls())
    assert got == want


@pytest.mark.parametrize("merger_cls", [LineMerger, NulMerger,
                                        SyslenMerger])
def test_jsonl_ltsv_block_matches_scalar(merger_cls):
    got, want = _run_block(BLOCK_CORPUS, LTSVEncoder, merger_cls())
    assert got == want


@pytest.mark.slow
def test_jsonl_two_lane_identity():
    # slow-marked for the tier-1 wall budget; ci.sh's new-format step
    # runs it (that step filters on faults only), and the filtered
    # deep fuzz randomizes 1/2 lanes besides
    """2-lane dispatch emits the same bytes in the same order as the
    scalar pipeline (the LaneSet sequencer keeps batch order)."""
    cfg = Config.from_string("[input]\ntpu_lanes = 2\n"
                             "tpu_batch_size = 8\n"
                             "tpu_max_line_len = 160\n")
    lines = BLOCK_CORPUS
    got, want = _run_block(lines, GelfEncoder, LineMerger(), cfg=cfg)
    assert got == want


@pytest.mark.faults
def test_jsonl_device_fault_fallback_splicing():
    """A device_decode fault mid-stream re-decodes the batch through
    the scalar oracle at its sequenced position — byte-identical."""
    from flowgger_tpu.utils import faultinject

    faultinject.reset()
    try:
        cfg = Config.from_string(
            "[input]\ntpu_batch_size = 8\ntpu_breaker_failures = 99\n"
            "tpu_max_line_len = 160\n")
        clean_got, want = _run_block(BLOCK_CORPUS * 2, GelfEncoder,
                                     LineMerger(), cfg=cfg)
        faultinject.configure({"device_decode": "every:2"})
        faulty_got, _ = _run_block(BLOCK_CORPUS * 2, GelfEncoder,
                                   LineMerger(), cfg=cfg)
        assert faulty_got == clean_got == want
    finally:
        faultinject.reset()


def test_auto_extra_formats_leg(monkeypatch):
    """input.auto_extra_formats = ["jsonl"] re-routes the '{' signature
    to the JSON-lines leg inside auto_tpu."""
    from flowgger_tpu.tpu.autodetect import (F_GELF, F_JSONL, classify)

    raw = b'{"timestamp":1,"message":"m"}'
    assert classify(raw) == F_GELF
    assert classify(raw, ("jsonl",)) == F_JSONL
    # the classic legs' device-encode tiers are not under test here —
    # eagerly computing them dominates the wall on small hosts
    monkeypatch.setenv("FLOWGGER_DEVICE_ENCODE", "0")
    cfg = Config.from_string(
        '[input]\nauto_extra_formats = ["jsonl"]\n'
        'tpu_max_line_len = 96\n')
    lines = [b'{"timestamp":1,"host":"h","message":"json line"}',
             b'host:h\ttime:1438790025\tmessage:ltsv']
    from flowgger_tpu.decoders import (LTSVDecoder, RFC5424Decoder)

    enc = GelfEncoder(cfg)
    merger = LineMerger()
    per_cls = {2: LTSVDecoder(cfg), 4: JSONLDecoder(cfg)}
    want = [merger.frame(enc.encode(
        per_cls[classify(ln, ("jsonl",))].decode(ln.decode())))
        for ln in lines]
    tx = queue.Queue()
    with jax.disable_jit():
        h = BatchHandler(tx, RFC5424Decoder(cfg), enc, cfg, fmt="auto",
                         start_timer=False, merger=merger)
        for ln in lines:
            h.handle_bytes(ln)
        h.flush()
        h.close()
    got = []
    while not tx.empty():
        item = tx.get_nowait()
        if isinstance(item, EncodedBlock):
            got.extend(item.iter_framed())
        else:
            got.append(merger.frame(item))
    assert got == want


def test_auto_extra_formats_validation():
    from flowgger_tpu.config import ConfigError
    from flowgger_tpu.tpu.autodetect import auto_extra_formats

    with pytest.raises(ConfigError):
        auto_extra_formats(Config.from_string(
            '[input]\nauto_extra_formats = ["bogus"]\n'))
    with pytest.raises(ConfigError):
        auto_extra_formats(Config.from_string(
            '[input]\nauto_extra_formats = "jsonl"\n'))
    assert auto_extra_formats(CFG) == ()


def test_jsonl_aot_decode_artifact_roundtrip(tmp_path):
    """``aot.py build --families decode --formats jsonl`` exports a
    loadable artifact whose channels match the jit kernel."""
    import numpy as np
    import jax.numpy as jnp

    from flowgger_tpu.tpu import aot, jsonl, pack

    out_dir = str(tmp_path / "art")
    aot.build_artifacts(out_dir, platforms=("cpu",),
                        families=("decode",), formats=("jsonl",),
                        rows_grid=(256,), max_len=96, quiet=True)
    store = aot.AotStore.load(out_dir)
    lines = [b'{"timestamp":1,"host":"h","message":"m"}'] * 4
    batch, lens, *_ = pack.pack_lines_2d(lines, 96)
    b, ln = jnp.asarray(batch), jnp.asarray(lens)
    call = store.find("decode_jsonl", aot.decode_statics("jsonl"),
                      (b, ln))
    assert call is not None
    got = call(b, ln)
    want = jsonl.decode_jsonl_jit(b, ln)
    with jax.disable_jit():
        eager = jsonl.decode_jsonl(b, ln)
    for k in eager:
        # one compile does triple duty: exported == jit == eager
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k
        assert np.array_equal(np.asarray(want[k]), np.asarray(eager[k])), k
