"""Differential tests: fixed-grammar columnar DNS decoder + block
routes vs the scalar oracle (flowgger_tpu/decoders/dns.py)."""

import queue

import jax
import pytest

from flowgger_tpu.block import EncodedBlock
from flowgger_tpu.config import Config
from flowgger_tpu.decoders import DecodeError, DNSDecoder
from flowgger_tpu.encoders.gelf import GelfEncoder
from flowgger_tpu.encoders.ltsv import LTSVEncoder
from flowgger_tpu.mergers import LineMerger, NulMerger, SyslenMerger
from flowgger_tpu.tpu.batch import BatchHandler, _decode_dns_batch

CFG = Config.from_string("[input]\ntpu_max_line_len = 160\n")
ORACLE = DNSDecoder()

CORPUS = [
    b"1438790025.123\t10.0.0.9\texample.com.\tA\tNOERROR\t523",
    b"1438790025\t192.168.1.1\tfoo.bar.baz.\tAAAA\tNXDOMAIN\t10923",
    b"1438790026.5\t2001:db8::1\twww.test.\tTXT\tSERVFAIL\t0",
    b"1438790026\t10.0.0.9\texample.com.\t28\t3\t99",
    b"1438790027.25\thost-a\tcache.hit.\tPTR\tNOERROR\t1200000",
    b"1\tc\tq.\t\t\t7",                          # empty qtype/rcode ok
    b"bad\t10.0.0.9\texample.com.\tA\tNOERROR\t1",
    b".5\tc\tq.\tA\tNOERROR\t1",                 # dot-first ts
    b"5.\tc\tq.\tA\tNOERROR\t1",                 # dot-last ts
    b"1.2.3\tc\tq.\tA\tNOERROR\t1",              # two dots
    b"-1\tc\tq.\tA\tNOERROR\t1",                 # signed ts
    b"1e5\tc\tq.\tA\tNOERROR\t1",                # exponent ts
    b"1\t\tq.\tA\tNOERROR\t1",                   # empty client
    b"1\tc\t\tA\tNOERROR\t1",                    # empty qname
    b"1\tc\tq.\tA\tNOERROR\t007",                # leading-zero latency
    b"1\tc\tq.\tA\tNOERROR\t18446744073709551615",  # u64 max (20 digits)
    b"1\tc\tq.\tA\tNOERROR\t18446744073709551616",  # > u64
    b"1\tc\tq.\tA\tNOERROR\t-1",
    b"1\tc\tq.\tA\tNOERROR",                     # 5 fields
    b"1\tc\tq.\tA\tNOERROR\t1\textra",           # 7 fields
    b'1.5\tc\tq"x\tA\tNOERROR\t4',               # quote (GELF escape)
    b"1.5\tc\tq\xc3\xa9\tA\tNOERROR\t4",         # non-ASCII
    b"not a dns line at all",
    b"",
]


def test_corpus_differential():
    with jax.disable_jit():
        results = _decode_dns_batch(list(CORPUS), 160)
    for ln, res in zip(CORPUS, results):
        kernel = ("rec", res.record) if res.record is not None else \
            ("err", res.error)
        try:
            oracle = ("rec", ORACLE.decode(ln.decode("utf-8")))
        except DecodeError as e:
            oracle = ("err", str(e))
        assert kernel == oracle, (
            f"divergence on {ln!r}:\n  kernel: {kernel}\n  oracle: {oracle}")


def test_mm_scan_impl_matches_lax():
    """scan_impl='mm' (MXU tri-matmul scans, what every TPU run lowers)
    against 'lax' (the CPU's cumsum), channel for channel."""
    import jax.numpy as jnp
    import numpy as np

    from flowgger_tpu.tpu import dns, pack

    batch, lens, *_ = pack.pack_lines_2d(list(CORPUS), 256)
    a, b = (jax.jit(lambda bt, ln, impl=impl: dns.decode_dns(
        bt, ln, scan_impl=impl))(jnp.asarray(batch), jnp.asarray(lens))
        for impl in ("lax", "mm"))
    assert np.asarray(a["ok"]).any()
    for k in a:
        assert (np.asarray(a[k]) == np.asarray(b[k])).all(), k


def _run_block(lines, enc_cls, merger, cfg=CFG):
    dec = DNSDecoder(cfg)
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
        h = BatchHandler(tx, dec, enc, cfg, fmt="dns", start_timer=False,
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
    return got, want


@pytest.mark.parametrize("merger_cls", [LineMerger, NulMerger,
                                        SyslenMerger])
def test_dns_gelf_block_matches_scalar(merger_cls):
    got, want = _run_block(list(CORPUS), GelfEncoder, merger_cls())
    assert got == want


@pytest.mark.parametrize("merger_cls", [LineMerger, NulMerger,
                                        SyslenMerger])
def test_dns_ltsv_block_matches_scalar(merger_cls):
    got, want = _run_block(list(CORPUS), LTSVEncoder, merger_cls())
    assert got == want


@pytest.mark.slow
def test_dns_two_lane_identity():
    # slow-marked for the tier-1 wall budget; ci.sh's new-format step
    # runs it (that step filters on faults only)
    cfg = Config.from_string("[input]\ntpu_lanes = 2\n"
                             "tpu_batch_size = 8\n"
                             "tpu_max_line_len = 160\n")
    got, want = _run_block(list(CORPUS), GelfEncoder, LineMerger(),
                           cfg=cfg)
    assert got == want


@pytest.mark.faults
def test_dns_device_fault_fallback_splicing():
    from flowgger_tpu.utils import faultinject

    faultinject.reset()
    try:
        cfg = Config.from_string(
            "[input]\ntpu_batch_size = 8\ntpu_breaker_failures = 99\n"
            "tpu_max_line_len = 160\n")
        clean_got, want = _run_block(list(CORPUS) * 2, GelfEncoder,
                                     LineMerger(), cfg=cfg)
        faultinject.configure({"device_decode": "every:2"})
        faulty_got, _ = _run_block(list(CORPUS) * 2, GelfEncoder,
                                   LineMerger(), cfg=cfg)
        assert faulty_got == clean_got == want
    finally:
        faultinject.reset()


def test_dns_auto_leg_signature():
    from flowgger_tpu.tpu.autodetect import (F_DNS, F_LTSV, F_RFC3164,
                                             classify)

    dns_line = b"1438790025.5\t10.0.0.1\texample.com.\tA\tNOERROR\t523"
    assert classify(dns_line) == F_RFC3164       # classic table
    assert classify(dns_line, ("dns",)) == F_DNS
    # an ltsv line keeps its class even with the dns leg on
    ltsv_line = b"host:h\ttime:1\tmessage:m"
    assert classify(ltsv_line, ("dns",)) == F_LTSV
    # colon somewhere (ipv6 client) no longer misroutes to ltsv
    v6 = b"1\t2001:db8::1\tq.\tA\tNOERROR\t1"
    assert classify(v6) == F_LTSV
    assert classify(v6, ("dns",)) == F_DNS
    # a BOM'd first field is not a clean timestamp: both the scalar and
    # the vectorized classifier must keep the row OFF the dns leg
    bom = b"\xef\xbb\xbf" + dns_line
    assert classify(bom, ("dns",)) == F_RFC3164


def test_dns_vectorized_classify_matches_scalar():
    """classify_packed's numpy/device overlays agree with per-row
    classify for the dns/jsonl legs."""
    import numpy as np

    from flowgger_tpu.tpu import pack
    from flowgger_tpu.tpu.autodetect import classify, classify_packed

    lines = list(CORPUS) + [
        b'{"timestamp":1}',
        b"<13>1 2015-08-05T15:53:45Z h a 1 m - x",
        b"host:h\ttime:1\tmessage:m",
        b"plain text",
        b"\xef\xbb\xbf" + CORPUS[0],   # BOM'd dns line: off the leg
        b"\xef\xbb\xbf" + b'{"timestamp":1}',
    ]
    extras = ("jsonl", "dns")
    packed = pack.pack_lines_2d(lines, 160)
    got = classify_packed(packed, extras=extras)[:len(lines)]
    want = np.array([classify(ln, extras) for ln in lines])
    assert got.tolist() == want.tolist()


def test_dns_aot_decode_artifact_roundtrip(tmp_path):
    import numpy as np
    import jax.numpy as jnp

    from flowgger_tpu.tpu import aot, dns, pack

    out_dir = str(tmp_path / "art")
    aot.build_artifacts(out_dir, platforms=("cpu",),
                        families=("decode",), formats=("dns",),
                        rows_grid=(256,), max_len=96, quiet=True)
    store = aot.AotStore.load(out_dir)
    lines = [CORPUS[0]] * 4
    batch, lens, *_ = pack.pack_lines_2d(lines, 96)
    b, ln = jnp.asarray(batch), jnp.asarray(lens)
    call = store.find("decode_dns", aot.decode_statics("dns"), (b, ln))
    assert call is not None
    got = call(b, ln)
    want = dns.decode_dns_jit(b, ln)
    with jax.disable_jit():
        eager = dns.decode_dns(b, ln)
    for k in eager:
        # one compile does triple duty: exported == jit == eager
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k
        assert np.array_equal(np.asarray(want[k]), np.asarray(eager[k])), k
