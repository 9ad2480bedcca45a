"""Differential tests: the columnar RFC5424 kernel must produce Records
byte-identical to the scalar oracle for every input — kernel-ok rows by
direct comparison, fallback rows trivially (they re-run the oracle).
SURVEY.md §4's "CPU-vs-TPU differential test" requirement.

Runs on the CPU backend (conftest forces JAX_PLATFORMS=cpu)."""

import random

import numpy as np
import pytest

from flowgger_tpu.decoders import DecodeError
from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
from flowgger_tpu.tpu import pack
from flowgger_tpu.tpu.batch import _decode_rfc5424_batch

ORACLE = RFC5424Decoder()

CORPUS = [
    # golden lines (reference rfc5424_decoder.rs tests)
    '<23>1 2015-08-05T15:53:45.637824Z testhostname appname 69 42 '
    '[origin@123 software="te\\st sc\\"ript" swVersion="0.0.1"] test message',
    '<23>1 2015-08-05T15:53:45.637824Z testhostname appname 69 42 '
    '[origin@123 software="te\\st sc\\"ript" swVersion="0.0.1"]'
    '[master@456 key="value" key2="value2"] test message',
    # plain
    "<13>1 2015-08-05T15:53:45Z host app 1 2 - hello world",
    "<0>1 1970-01-01T00:00:00Z h a p m - x",
    "<191>1 2038-01-19T03:14:07Z h a p m - end of i32 time",
    # timestamps
    "<13>1 2015-08-05T15:53:45+02:00 host app 1 2 - offset",
    "<13>1 2015-08-05T15:53:45-11:30 host app 1 2 - negative offset",
    "<13>1 2015-08-05t15:53:45z host app 1 2 - lowercase",
    "<13>1 2016-02-29T23:59:59.5Z host app 1 2 - leap day",
    "<13>1 2015-08-05T15:53:45.123456789Z host app 1 2 - nine digits",
    "<13>1 2015-12-31T23:59:59.999Z host app 1 2 - year end",
    # BOM
    "\ufeff<13>1 2015-08-05T15:53:45Z host app 1 2 - bom line",
    # msg variants
    "<13>1 2015-08-05T15:53:45Z host app 1 2 -",
    "<13>1 2015-08-05T15:53:45Z host app 1 2 - ",
    "<13>1 2015-08-05T15:53:45Z host app 1 2 -   padded   ",
    "<13>1 2015-08-05T15:53:45Z host app 1 2 - msg with [brackets] and \"quotes\"",
    "<13>1 2015-08-05T15:53:45Z host app 1 2 - unicode méssage ünïcode",
    # sd variants
    '<13>1 2015-08-05T15:53:45Z h a p m [id ] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="v"] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="v"]',          # error: no msg after sd
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="v"] ',
    '<13>1 2015-08-05T15:53:45Z h a p m [a@1 x="1"][b@2 y="2"][c@3 z="3"] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="val [1] nested"] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="a\\"b\\\\c\\]d\\xe"] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="" empty=""] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id many="1" k2="2" k3="3" k4="4" k5="5"] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [ anon="1"] m',       # empty sd-id
    '<13>1 2015-08-05T15:53:45Z h a p m [id  spaced = bogus', # malformed
    '<13>1 2015-08-05T15:53:45Z h a p m [id una="unterminated',
    '<13>1 2015-08-05T15:53:45Z h a p m [id "bogus extra quote" k="v"] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="v" ] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id] m',              # error: id swallows ]
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="ünïcode vél"] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id\tk="v"] m',
    # five+ SD blocks (over MAX_SD cap -> fallback must still be exact)
    '<13>1 2015-08-05T15:53:45Z h a p m '
    '[a x="1"][b x="2"][c x="3"][d x="4"][e x="5"][f x="6"] m',
    # >16 pairs (over MAX_PAIRS cap)
    '<13>1 2015-08-05T15:53:45Z h a p m [id ' +
    " ".join(f'k{i}="{i}"' for i in range(20)) + '] m',
    # backslash runs around the ESC_RUN_CAP ladder bound (15/16/17 and a
    # high-even run): parity must be exact below the cap and the >= cap
    # rows must fall back to the oracle, not mis-parse
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="a' + "\\" * 14 + '" x="y"] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="a' + "\\" * 15 + '\\"tail"] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="a' + "\\" * 16 + '" x="y"] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="a' + "\\" * 17 + '\\"t"] m',
    '<13>1 2015-08-05T15:53:45Z h a p m [id k="a' + "\\" * 24 + '" x="y"] m',
    '<13>1 2015-08-05T15:53:45Z h a p m - msg with ' + "\\" * 40 + ' run',
    # header errors
    "13>1 2015-08-05T15:53:45Z h a p m - x",
    "<13>2 2015-08-05T15:53:45Z h a p m - x",
    "<13>11 2015-08-05T15:53:45Z h a p m - x",
    "<999>1 2015-08-05T15:53:45Z h a p m - x",
    "<256>1 2015-08-05T15:53:45Z h a p m - x",
    "<255>1 2015-08-05T15:53:45Z h a p m - x",
    "<>1 2015-08-05T15:53:45Z h a p m - x",
    "<13> 2015-08-05T15:53:45Z h a p m - x",
    "<13>1 - h a p m - nil timestamp",
    "<13>1 2015-08-05T15:53:45Z h a p m x not dash",
    "<13>1 2015-08-05T15:53:45Z h a p",
    "<13>1 2015-08-05T15:53:45Z",
    "<13>1",
    "",
    "-",
    # quotes and backslashes in header fields (legal PRINTUSASCII): the
    # rest-relative parity subtraction and the parity-derived pair
    # ordinals must not be perturbed (negative pre-rest q_excl)
    '<34>1 2003-01-01T00:00:00Z host \\x"a"b" pid mid '
    '[id a="v1" b="v2" c="v3" d="v4" e="v5" f="v6"] hello',
    '<34>1 2003-01-01T00:00:00Z ho"st app" "1 "2" [id k="\\\\v"] m',
    '<34>1 2003-01-01T00:00:00Z h"""" a p m [id k="v"] m',
    # empty header fields (double spaces)
    "<13>1 2015-08-05T15:53:45Z  a p m - empty hostname",
    "<13>1 2015-08-05T15:53:45Z h  p m - empty appname",
    # timestamp errors
    "<13>1 2015-08-05T15:53:45 h a p m - no offset",
    "<13>1 2015-08-05T15:53:45.Z h a p m - empty frac",
    "<13>1 2015-08-05T15:53:45.0123456789Z h a p m - ten digits",
    "<13>1 2015-13-05T15:53:45Z h a p m - bad month",
    "<13>1 2015-02-30T15:53:45Z h a p m - bad day",
    "<13>1 2015-08-05T24:53:45Z h a p m - bad hour",
    "<13>1 2015-08-05T15:53:45+25:00 h a p m - bad offset",
    "<13>1 2015-08-05X15:53:45Z h a p m - bad sep",
]


def run_both(lines):
    """Feed lines through the batched kernel path and the oracle; return
    list of (kernel_result, oracle_result) as comparable tuples."""
    raw = [ln.encode("utf-8") for ln in lines]
    results = _decode_rfc5424_batch(raw, max_len=512)
    assert len(results) == len(lines)
    pairs = []
    for ln, res in zip(lines, results):
        kernel = ("rec", res.record) if res.record is not None else ("err", res.error)
        try:
            oracle = ("rec", ORACLE.decode(ln))
        except DecodeError as e:
            oracle = ("err", str(e))
        pairs.append((ln, kernel, oracle))
    return pairs


def assert_identical(lines):
    for ln, kernel, oracle in run_both(lines):
        assert kernel == oracle, (
            f"divergence on {ln!r}:\n  kernel: {kernel}\n  oracle: {oracle}"
        )


def test_corpus_differential():
    assert_identical(CORPUS)


def test_wide_line_scan_packing():
    """L > 1022 drops the scan packing from 3 ordinals per word to 2
    (scan_bits > 10): the wide-geometry branch must stay differential-
    identical and keep clean rows on the fast path."""
    from flowgger_tpu.tpu import rfc5424

    filler = "x" * 900
    lines = [
        f'<13>1 2015-08-05T15:53:45Z h a p m [id k="v{i}" w="{filler}"] '
        f"tail {filler}{i}"
        for i in range(8)
    ] + CORPUS[:30]
    raw = [ln.encode() for ln in lines]
    batch, lens, *_ = pack.pack_lines_2d(raw, 2048)
    out = rfc5424.decode_rfc5424_host(batch, lens)
    assert np.asarray(out["ok"])[:8].all(), "wide rows left the fast path"
    # full record-level differential through the batch path
    results = _decode_rfc5424_batch(raw, max_len=2048)
    for ln, res in zip(lines, results):
        kernel = ("rec", res.record) if res.record is not None else ("err", res.error)
        try:
            oracle = ("rec", ORACLE.decode(ln))
        except DecodeError as e:
            oracle = ("err", str(e))
        assert kernel == oracle, f"wide-L divergence on {ln!r}"


def test_escape_cap_rows_fall_back():
    """Rows with >= ESC_RUN_CAP backslashes feeding a quote must be
    flagged ok=False (oracle fallback), and sub-cap runs must stay on
    the fast path with exact parity."""
    from flowgger_tpu.tpu import rfc5424

    under = ('<13>1 2015-08-05T15:53:45Z h a p m [id k="a'
             + "\\" * (rfc5424.ESC_RUN_CAP - 2) + '" x="y"] m')
    over = ('<13>1 2015-08-05T15:53:45Z h a p m [id k="a'
            + "\\" * rfc5424.ESC_RUN_CAP + '" x="y"] m')
    batch, lens, *_ = pack.pack_lines_2d([under.encode(), over.encode()], 256)
    out = rfc5424.decode_rfc5424_host(batch, lens)
    ok = np.asarray(out["ok"])
    assert ok[0], "sub-cap escape run should stay on the fast path"
    assert not ok[1], "cap-length escape run must fall back to the oracle"


def test_fast_path_coverage():
    """The clean subset must actually take the kernel path (ok=True), not
    silently fall back to scalar for everything."""
    import jax.numpy as jnp

    from flowgger_tpu.tpu import rfc5424

    clean = [ln for ln in CORPUS[:26] if ln.startswith("<")]
    raw = [ln.encode() for ln in clean]
    buf, starts, lens, n_real = pack.pack_lines(raw)
    out = rfc5424.decode_chunk_jit(jnp.asarray(buf), jnp.asarray(starts),
                                   jnp.asarray(lens), max_len=512)
    ok = np.asarray(out["ok"])[:n_real]
    # at least 80% of clean lines stay on the fast path
    assert ok.mean() >= 0.8, f"fast-path coverage too low: {ok.mean():.2f} ({list(zip(clean, ok))})"


def test_fuzz_differential():
    rng = random.Random(1234)
    alphabet = list(' <>[]"\\=-:.TZ0123456789abchmp\t\u00e9')
    base = '<13>1 2015-08-05T15:53:45.637824Z host app 1 2 [id k="v" k2="v2"] msg body'
    lines = []
    for _ in range(400):
        chars = list(base)
        for _ in range(rng.randint(1, 6)):
            op = rng.random()
            pos = rng.randrange(len(chars)) if chars else 0
            if op < 0.4 and chars:
                chars[pos] = rng.choice(alphabet)
            elif op < 0.7:
                chars.insert(pos, rng.choice(alphabet))
            elif chars:
                del chars[pos]
        lines.append("".join(chars))
    # plus fully random short strings
    for _ in range(200):
        lines.append("".join(rng.choice(alphabet)
                             for _ in range(rng.randint(0, 40))))
    assert_identical(lines)


def test_random_structured_lines():
    """Generator-based: random well-formed lines must all match and mostly
    stay on the fast path."""
    rng = random.Random(99)
    lines = []
    for _ in range(300):
        pri = rng.randrange(0, 192)
        frac = rng.choice(["", f".{rng.randrange(1, 999999)}"])
        off = rng.choice(["Z", "z", "+02:00", "-07:30", "+00:00"])
        ts = (f"20{rng.randrange(10, 38):02d}-{rng.randrange(1, 13):02d}-"
              f"{rng.randrange(1, 29):02d}T{rng.randrange(24):02d}:"
              f"{rng.randrange(60):02d}:{rng.randrange(60):02d}{frac}{off}")
        nsd = rng.randrange(0, 3)
        if nsd == 0:
            sd = "-"
        else:
            blocks = []
            values = ["v", "a b", "x=y", "[8]", 'q\\"q', "b\\\\b"]
            for b in range(nsd):
                pairs = " ".join(
                    f'k{j}="{rng.choice(values)}"'
                    for j in range(rng.randrange(0, 4)))
                blocks.append(f"[id@{b}{' ' + pairs if pairs else ' '}]")
            sd = "".join(blocks)
        msg = rng.choice(["", " short msg", " msg with \" quote", " trailing  "])
        lines.append(f"<{pri}>1 {ts} host-{rng.randrange(9)} app {rng.randrange(99)} "
                     f"ID{rng.randrange(9)} {sd}{msg}")
    assert_identical(lines)


def test_corpus_differential_under_the_tpus_scan_lowering(monkeypatch):
    """The oracle differential again with the scans lowered as every TPU
    run lowers them (MXU tri-matmul), which the CPU backend never picks
    of itself; eagerly, so that no cached CPU trace answers."""
    import jax

    from flowgger_tpu.tpu import aot

    monkeypatch.setattr(aot, "_scan_impl_for", lambda platform: "mm")
    with jax.disable_jit():
        assert_identical(CORPUS)


def test_long_line_fallback():
    long_msg = "x" * 2000
    lines = [f"<13>1 2015-08-05T15:53:45Z h a p m - {long_msg}"]
    assert_identical(lines)


def test_batch_handler_end_to_end():
    import queue

    from flowgger_tpu.config import Config
    from flowgger_tpu.encoders import GelfEncoder
    from flowgger_tpu.tpu.batch import BatchHandler

    tx = queue.Queue()
    handler = BatchHandler(tx, ORACLE, GelfEncoder(Config.from_string("")),
                           start_timer=False)
    for ln in CORPUS:
        handler.handle_bytes(ln.encode("utf-8"))
    handler.flush()
    # compare against the scalar handler output
    from flowgger_tpu.splitters import ScalarHandler

    tx2 = queue.Queue()
    scalar = ScalarHandler(tx2, ORACLE, GelfEncoder(Config.from_string("")))
    for ln in CORPUS:
        scalar.handle_bytes(ln.encode("utf-8"))
    got = []
    while not tx.empty():
        got.append(tx.get_nowait())
    want = []
    while not tx2.empty():
        want.append(tx2.get_nowait())
    assert got == want


def test_a_toml_that_still_sets_the_removed_tier_key_starts_the_block_route():
    """``input.tpu_pallas`` left the namespace with the tier it chose.
    A config that still carries it is treated as any key the program
    does not read: ``--check`` names it, and the handler starts on the
    block route and writes what it writes without the key."""
    import queue

    from flowgger_tpu.block import EncodedBlock
    from flowgger_tpu.config import Config
    from flowgger_tpu.encoders import GelfEncoder
    from flowgger_tpu.lint import lint_config
    from flowgger_tpu.mergers import NulMerger
    from flowgger_tpu.tpu.batch import BatchHandler

    def run(toml):
        cfg = Config.from_string(toml)
        tx = queue.Queue()
        h = BatchHandler(tx, ORACLE, GelfEncoder(cfg), cfg, fmt="rfc5424",
                         start_timer=False, merger=NulMerger())
        assert h._block_route_ok()
        for ln in CORPUS:
            h.handle_bytes(ln.encode("utf-8"))
        h.flush()
        h.close()
        out = []
        while not tx.empty():
            item = tx.get_nowait()
            assert isinstance(item, EncodedBlock)
            out.append(bytes(item.data))
        return cfg, b"".join(out)

    cfg, got = run('[input]\ntpu_pallas = "auto"\n')
    _, want = run("")
    assert got and got == want
    warns = lint_config(cfg)
    assert len(warns) == 1 and "input.tpu_pallas" in warns[0]


@pytest.mark.parametrize("max_len", [512, 2048, 4608])
def test_mm_scan_impl_matches_lax(max_len):
    """scan_impl='mm' (MXU tri-matmul scans, what every TPU run lowers)
    must be numerically identical to the lax scans, channel for channel:
    at the default width, at the wide-L geometry where the f32 packing
    uses more slot bits, and past L = 4094 where two channels no longer
    share one f32 matmul and each takes an int8 one."""
    import jax.numpy as jnp

    from flowgger_tpu.tpu import rfc5424

    lines = [ln.encode("utf-8") for ln in CORPUS]
    batch, lens, *_ = pack.pack_lines_2d(lines, max_len)
    a = rfc5424.decode_rfc5424(jnp.asarray(batch), jnp.asarray(lens),
                               scan_impl="lax")
    b = rfc5424.decode_rfc5424(jnp.asarray(batch), jnp.asarray(lens),
                               scan_impl="mm")
    for k in a:
        assert (np.asarray(a[k]) == np.asarray(b[k])).all(), k


def test_scatter_extract_impl_matches_sum():
    """extract_impl='scatter' (CPU fast path) must agree with the
    bit-packed sums (TPU path): 'ok' everywhere, every channel on
    accepted rows.  On rejected rows the ordinal-keyed channels
    (sid_end, name_start since round 4) may hold impl-defined garbage —
    those rows always take the scalar oracle, so no consumer ever reads
    them (production never mixes impls within one batch)."""
    import jax.numpy as jnp

    from flowgger_tpu.tpu import rfc5424

    lines = [ln.encode("utf-8") for ln in CORPUS]
    batch, lens, chunk, starts, orig, n = pack.pack_lines_2d(lines, 512)
    a = rfc5424.decode_rfc5424(jnp.asarray(batch), jnp.asarray(lens))
    b = rfc5424.decode_rfc5424(jnp.asarray(batch), jnp.asarray(lens),
                               extract_impl="scatter")
    ok_a = np.asarray(a["ok"])
    ok_b = np.asarray(b["ok"])
    assert (ok_a == ok_b).all()
    for k in a:
        va, vb = np.asarray(a[k]), np.asarray(b[k])
        assert (va[ok_a] == vb[ok_a]).all(), k


def test_two_tier_pair_dispatch():
    """Rows with DEFAULT_MAX_PAIRS < pairs <= RESCUE_MAX_PAIRS decode
    on-device via the tier-2 kernel (not the scalar fallback), with pair
    channels widened; beyond RESCUE they stay flagged for the oracle."""
    from flowgger_tpu.tpu import rfc5424

    def sd(npairs):
        pairs = " ".join(f'k{i:02d}="{i}"' for i in range(npairs))
        return f"<13>1 2015-08-05T15:53:45Z h a p m [id {pairs}] m"

    lines = [sd(2).encode(), sd(10).encode(), sd(16).encode(),
             sd(20).encode()]
    batch, lens, chunk, starts, orig, n = pack.pack_lines_2d(lines, 512)
    host = rfc5424.decode_rfc5424_host(batch, lens)
    ok = host["ok"][:n]
    assert ok[0] and ok[1] and ok[2]          # tier-2 rescued rows 1-2
    assert not ok[3]                          # > rescue cap: oracle row
    assert host["name_start"].shape[1] == rfc5424.RESCUE_MAX_PAIRS
    assert host["pair_count"][1] == 10 and host["pair_count"][2] == 16
    # spans of the rescued row must match the oracle record
    rec = ORACLE.decode(lines[1].decode())
    line = lines[1].decode()
    got = [(line[host["name_start"][1][j]:host["name_end"][1][j]],
            line[host["val_start"][1][j]:host["val_end"][1][j]])
           for j in range(10)]
    want = [(name[1:], val.value) for name, val in rec.sd[0].pairs]
    assert got == want
