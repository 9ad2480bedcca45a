"""Differential tests: columnar LTSV kernel vs the scalar oracle."""

import random

import pytest

from flowgger_tpu.config import Config
from flowgger_tpu.decoders import DecodeError
from flowgger_tpu.decoders.ltsv import LTSVDecoder
from flowgger_tpu.tpu.batch import _decode_ltsv_batch

_SCHEMA_CFG = (
    '[input]\n[input.ltsv_schema]\ncounter = "u64"\nscore = "i64"\n'
    'mean = "f64"\ndone = "bool"\n'
)

CORPUS = [
    "time:1438790025.99\thost:h\tname1:value1",
    "time:1438790025\thost:h\tk:v",
    "time:-5\thost:h\tk:v",
    "time:+12.5\thost:h\tk:v",
    "time:[2015-08-05T15:53:45.637824Z]\thost:h\tn:v",
    "time:2015-08-05T15:53:45Z\thost:h\tn:v",
    "time:[10/Oct/2000:13:55:36.3 -0700]\thost:h\tmessage:m",   # english -> fallback
    "time:1.5\thost:testhostname\tname 2: value 2\tn3:v3\tmessage:this is a test",
    "time:1.5\thost:h\tlevel:3\tmessage:hi",
    "time:1.5\thost:h\tlevel:9",            # error via fallback
    "time:1.5\thost:h\tlevel:abc",          # error via fallback
    "time:1.5\thost:h\tcounter:42\tscore:-1\tmean:0.42\tdone:true",
    "time:1.5\thost:h\tcounter:-1",         # schema type error
    "time:1.5\thost:h\tnocolonpart\tk:v",   # missing value print
    "host:h\tk:v",                          # missing timestamp
    "time:1.5\tk:v",                        # missing hostname
    "time:1.5\thost:h\t" + "\t".join(f"k{i}:{i}" for i in range(30)),  # >cap
    "time:1.5\thost:h\tmessage:ünïcode msg\tk:vàl",
    "time:1.5\thost:h\ttime:2.5",           # later time wins
    "time:1e5\thost:h",                     # exponent float -> fallback
    "time:inf\thost:h",                     # inf -> fallback path
    "time:.\thost:h",                       # bare dot -> error
    "",                                      # empty line
    "justtext",
    "time:1.5\thost:\tk:v",                 # empty hostname value
    "time:[1.5]\thost:h",                   # bracketed float
    "xtime:1.5\ttime:2.5\thost:h",          # key containing 'time' not special
    "time:1.5\thost:h\ttimex:9",
]


def run_both(lines, config_str=""):
    decoder = LTSVDecoder(Config.from_string(config_str))
    raw = [ln.encode("utf-8") for ln in lines]
    results = _decode_ltsv_batch(raw, 512, decoder)
    pairs = []
    for ln, res in zip(lines, results):
        kernel = ("rec", res.record) if res.record is not None else ("err", res.error)
        try:
            oracle = ("rec", decoder.decode(ln))
        except DecodeError as e:
            oracle = ("err", str(e))
        pairs.append((ln, kernel, oracle))
    return pairs


def assert_identical(lines, config_str=""):
    for ln, kernel, oracle in run_both(lines, config_str):
        assert kernel == oracle, (
            f"divergence on {ln!r}:\n  kernel: {kernel}\n  oracle: {oracle}")


def test_corpus_plain():
    assert_identical(CORPUS)


def test_corpus_with_schema():
    assert_identical(CORPUS, _SCHEMA_CFG)


def test_corpus_differential_under_the_tpus_scan_lowering(monkeypatch):
    """The oracle differential again with the scans lowered as every TPU
    run lowers them (MXU tri-matmul), which the CPU backend never picks
    of itself; eagerly, so that no cached CPU trace answers."""
    import jax

    from flowgger_tpu.tpu import aot

    monkeypatch.setattr(aot, "_scan_impl_for", lambda platform: "mm")
    with jax.disable_jit():
        assert_identical(CORPUS, _SCHEMA_CFG)


def test_suffixes():
    cfg = _SCHEMA_CFG + '[input.ltsv_suffixes]\nu64 = "_u64"\ni64 = "_i64"\n'
    assert_identical(CORPUS, cfg)


def test_fast_path_coverage():
    import jax.numpy as jnp
    import numpy as np

    from flowgger_tpu.tpu import ltsv, pack

    clean = [ln for ln in CORPUS if ln.startswith("time:1") or ln.startswith("time:[2015")]
    raw = [ln.encode() for ln in clean]
    batch, lens, chunk, starts, orig, n = pack.pack_lines_2d(raw, 256)
    out = ltsv.decode_ltsv_jit(jnp.asarray(batch), jnp.asarray(lens))
    okf = np.asarray(out["ok"])[:n]
    assert okf.mean() >= 0.7, list(zip(clean, okf))


def test_mm_scan_impl_matches_lax():
    """scan_impl='mm' (MXU tri-matmul scans, what every TPU run lowers)
    against 'lax' (the CPU's cumsum), channel for channel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flowgger_tpu.tpu import ltsv, pack

    batch, lens, *_ = pack.pack_lines_2d([ln.encode() for ln in CORPUS], 256)
    a, b = (jax.jit(lambda bt, ln, impl=impl: ltsv.decode_ltsv(
        bt, ln, scan_impl=impl))(jnp.asarray(batch), jnp.asarray(lens))
        for impl in ("lax", "mm"))
    assert np.asarray(a["ok"]).any()
    for k in a:
        assert (np.asarray(a[k]) == np.asarray(b[k])).all(), k


def test_fuzz_differential():
    rng = random.Random(77)
    alphabet = list("\t:timehoslvcabd0123456789.[]- Z")
    base = "time:1438790025.5\thost:abc\tlevel:3\tcounter:42\tmessage:hello there"
    lines = []
    for _ in range(300):
        chars = list(base)
        for _ in range(rng.randint(1, 5)):
            op = rng.random()
            pos = rng.randrange(len(chars)) if chars else 0
            if op < 0.4 and chars:
                chars[pos] = rng.choice(alphabet)
            elif op < 0.7:
                chars.insert(pos, rng.choice(alphabet))
            elif chars:
                del chars[pos]
        lines.append("".join(chars))
    assert_identical(lines, _SCHEMA_CFG)


def test_missing_value_notice(capsys):
    assert_identical(["time:1.5\thost:h\torphan\tk:v"])
    out = capsys.readouterr().out
    # both kernel and oracle printed the notice once each
    assert out.count("Missing value for name 'orphan'") == 2
