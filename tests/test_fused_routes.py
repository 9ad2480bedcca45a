"""Fused decode→encode routes (tpu/fused_routes.py): byte identity vs
the scalar oracle across the route matrix and framings, the
decline-to-split degradation ladder, demand-mask completeness, the
fused arm of the route economics, and the KERNEL_ABI cache layout.

The fused programs cannot be compiled by every host's XLA (this
container's declines them via the watchdog), so byte identity is
enforced EAGERLY (``jax.disable_jit()`` + watchdog off) — the same
numeric ops XLA would compile, minus the compile.  Compiled-path
engagement carries the ``requires_device_encode_compile`` marker and
must pass on capable hosts.
"""

import os
import queue

import jax
import numpy as np
import pytest

from flowgger_tpu.block import EncodedBlock
from flowgger_tpu.config import Config
from flowgger_tpu.decoders.gelf import GelfDecoder
from flowgger_tpu.decoders.ltsv import LTSVDecoder
from flowgger_tpu.decoders.rfc3164 import RFC3164Decoder
from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
from flowgger_tpu.encoders.gelf import GelfEncoder
from flowgger_tpu.mergers import LineMerger, NulMerger, SyslenMerger
from flowgger_tpu.tpu import fused_routes, pack
from flowgger_tpu.tpu.batch import BatchHandler
from flowgger_tpu.utils.metrics import registry as _metrics

CFG = Config.from_string("")

DECODERS = {"rfc5424": RFC5424Decoder, "rfc3164": RFC3164Decoder,
            "ltsv": LTSVDecoder, "gelf": GelfDecoder}


def corpus(fmt, n=48):
    if fmt == "rfc5424":
        return [f'<34>1 2015-08-05T15:53:45.8Z host{i % 3} app 42 m '
                f'[x@9 a="v{i}"] hello {i}'.encode() for i in range(n)]
    if fmt == "rfc3164":
        return [f'<34>Aug  5 15:53:45 host{i % 3} app[42]: legacy '
                f'{i}'.encode() for i in range(n)]
    if fmt == "ltsv":
        return [f'host:h{i % 3}\ttime:2015-08-05T15:53:45Z\tk1:v{i}\t'
                f'message:m {i}'.encode() for i in range(n)]
    return [('{"version":"1.1","host":"h%d","short_message":"m %d",'
             '"timestamp":1438790025.5,"_k":"v%d"}'
             % (i % 3, i, i)).encode() for i in range(n)]


def scalar_bytes(fmt, lines, enc, merger):
    dec = DECODERS[fmt](CFG)
    return [merger.frame(enc.encode(dec.decode(ln.decode())))
            for ln in lines]


def run_fused_eager(fmt, lines, enc, merger, monkeypatch,
                    route_state=None):
    """Submit + fetch one fused batch eagerly (watchdog off so guarded
    calls run inline — safe under disable_jit, nothing can hang)."""
    monkeypatch.setenv("FLOWGGER_COMPILE_TIMEOUT_MS", "0")
    monkeypatch.setenv("FLOWGGER_FUSED_COMPILE_TIMEOUT_MS", "0")
    dec = DECODERS[fmt](CFG)
    ltsv_dec = dec if fmt == "ltsv" else None
    route = fused_routes.route_for(fmt, enc, merger, ltsv_dec)
    assert route is not None
    packed = pack.pack_lines_2d(lines, 256)
    with jax.disable_jit():
        handle = fused_routes.submit(route, packed)
        res, _ = fused_routes.fetch_encode(
            handle, packed, enc, merger, ltsv_dec,
            route_state if route_state is not None else {})
    return route, res


@pytest.mark.parametrize("fmt", ["rfc5424", "rfc3164", "ltsv", "gelf"])
@pytest.mark.parametrize("merger", [LineMerger(), NulMerger(),
                                    SyslenMerger()],
                         ids=["line", "nul", "syslen"])
def test_fused_matches_scalar_oracle_all_routes(fmt, merger, monkeypatch):
    """DIFF_TEST anchor: every fused route × framing is byte-identical
    to its scalar oracle, eagerly."""
    enc = GelfEncoder(CFG)
    lines = corpus(fmt)
    route, res = run_fused_eager(fmt, lines, enc, merger, monkeypatch)
    assert res is not None, "fused tier declined a clean corpus"
    assert res.fallback_rows == 0
    assert list(res.block.iter_framed()) == scalar_bytes(
        fmt, lines, enc, merger)


@pytest.mark.parametrize("fmt", ["rfc5424", "rfc3164", "ltsv", "gelf"])
def test_fused_route_fuzz_vs_scalar(fmt, monkeypatch):
    """DIFF_TEST anchor: light per-route fuzz — broken rows splice
    through the scalar fallback inside fused blocks, in order.  The
    large-budget version is tools/deep_fuzz.py --routes fused."""
    import random

    rng = random.Random(7)
    enc = GelfEncoder(CFG)
    merger = LineMerger()
    lines = corpus(fmt, 64)
    for i in rng.sample(range(len(lines)), 2):
        b = bytearray(lines[i])
        b[rng.randrange(len(b))] = rng.randrange(256)
        lines[i] = bytes(b)
    dec = DECODERS[fmt](CFG)
    want = []
    for ln in lines:
        try:
            want.append(merger.frame(enc.encode(dec.decode(
                ln.decode("utf-8")))))
        except Exception:  # noqa: BLE001 - mirrored per-line error drop
            continue
    route, res = run_fused_eager(fmt, lines, enc, merger, monkeypatch)
    assert res is not None
    assert list(res.block.iter_framed()) == want


def test_fused_fetch_under_emit_gauges(monkeypatch):
    """The per-route gauges exist and fetch < emit at an amortizing
    batch size (the tentpole's output-sized-fetch claim; the bench
    asserts it on every route — one route here keeps the test cheap)."""
    enc = GelfEncoder(CFG)
    lines = corpus("rfc3164", 256)
    route, res = run_fused_eager("rfc3164", lines, enc, LineMerger(),
                                 monkeypatch)
    assert res is not None
    fetch = _metrics.get_gauge(f"fetch_bytes_per_row_{route.name}")
    emit = _metrics.get_gauge(f"emit_bytes_per_row_{route.name}")
    assert fetch > 0 and emit > 0
    assert fetch < emit
    assert _metrics.get(f"fused_rows_{route.name}") >= 256


def test_demand_masks_cover_and_prune(monkeypatch):
    """Every DEMAND set is a strict subset of its decoder's channel
    dict (so the mask genuinely prunes) and the fused programs run off
    the pruned dict alone (covered by the eager byte-identity tests —
    a missing key would KeyError there)."""
    monkeypatch.setenv("FLOWGGER_COMPILE_TIMEOUT_MS", "0")
    from flowgger_tpu.tpu import gelf, ltsv, rfc3164, rfc5424

    packed = pack.pack_lines_2d(corpus("rfc5424", 4), 256)
    b, ln = packed[0], packed[1]
    with jax.disable_jit():
        outs = {
            "rfc5424_gelf": rfc5424.decode_rfc5424_jit(b, ln),
            "rfc3164_gelf": rfc3164.decode_rfc3164_jit(
                b, ln, np.int32(2015)),
            "ltsv_gelf": ltsv.decode_ltsv_jit(b, ln),
            "gelf_gelf": gelf.decode_gelf_jit(b, ln),
        }
    for name, out in outs.items():
        demand = fused_routes.DEMAND[name]
        assert demand <= set(out), f"{name}: demand names unknown channels"
        if name != "gelf_gelf":  # the re-canonicalizer reads everything
            dropped = set(out) - demand
            assert dropped, f"{name}: demand mask prunes nothing"
    # threading the mask through the decoder drops exactly the
    # non-demanded channels
    with jax.disable_jit():
        pruned = rfc5424.decode_rfc5424_jit(
            b, ln, demand=fused_routes.DEMAND["rfc5424_gelf"])
    assert set(pruned) == set(fused_routes.DEMAND["rfc5424_gelf"])


def test_fused_declines_to_split_byte_identity(monkeypatch):
    """The full ladder under real jit: the fused probe times out on its
    first compile (1ms watchdog), the batch falls back to the split
    path, output stays byte-identical, and fused_fallbacks counts it."""
    monkeypatch.setenv("FLOWGGER_FUSED_COMPILE_TIMEOUT_MS", "1")
    enc = GelfEncoder(CFG)
    dec = RFC3164Decoder(CFG)
    merger = LineMerger()
    lines = corpus("rfc3164", 32)
    before = _metrics.get("fused_fallbacks")
    tx = queue.Queue()
    h = BatchHandler(tx, dec, enc, CFG, fmt="rfc3164",
                     start_timer=False, merger=merger)
    try:
        for ln in lines:
            h.handle_bytes(ln)
        h.flush()
    finally:
        h.close()
    got = []
    while not tx.empty():
        item = tx.get_nowait()
        got.extend(item.iter_framed() if isinstance(item, EncodedBlock)
                   else [merger.frame(item)])
    assert got == scalar_bytes("rfc3164", lines, enc, merger)
    assert _metrics.get("fused_fallbacks") > before


def test_tpu_fuse_off_pins_split_path(monkeypatch):
    """input.tpu_fuse = "off": the handler never builds a fused route
    and submits the split decode directly."""
    cfg = Config.from_string('[input]\ntpu_fuse = "off"\n')
    h = BatchHandler(queue.Queue(), RFC5424Decoder(cfg), GelfEncoder(cfg),
                     cfg, fmt="rfc5424", start_timer=False,
                     merger=LineMerger())
    try:
        assert h._fuse_mode == "off"
        assert h._fused_route() is None
    finally:
        h.close()


def test_tpu_fuse_validation():
    from flowgger_tpu.config import ConfigError

    cfg = Config.from_string('[input]\ntpu_fuse = "sideways"\n')
    with pytest.raises(ConfigError):
        BatchHandler(queue.Queue(), RFC5424Decoder(cfg),
                     GelfEncoder(cfg), cfg, fmt="rfc5424",
                     start_timer=False, merger=LineMerger())


def test_route_for_respects_split_gates(monkeypatch):
    """No fused program without the split tier's applicability: the
    device-encode kill switch gates every leg, and unregistered input
    formats stay split."""
    from flowgger_tpu.encoders.rfc5424 import RFC5424Encoder

    enc = GelfEncoder(CFG)
    enc5424 = RFC5424Encoder(CFG)
    assert fused_routes.route_for("rfc5424", enc, LineMerger()) is not None
    # PR 19: the rfc5424→rfc5424 output leg is a fused route now
    route = fused_routes.route_for("rfc5424", enc5424, LineMerger())
    assert route is not None and route.name == "rfc5424_rfc5424"
    monkeypatch.setenv("FLOWGGER_DEVICE_ENCODE", "0")
    assert fused_routes.route_for("rfc5424", enc, LineMerger()) is None
    assert fused_routes.route_for("rfc5424", enc5424,
                                  LineMerger()) is None
    monkeypatch.delenv("FLOWGGER_DEVICE_ENCODE")
    # capnp is an output leg, never an input format
    assert fused_routes.route_for("capnp", enc, LineMerger()) is None


def test_route_economics_fused_arm():
    """allow_fused probes fused first, buys a split comparison only
    when fused measures slow, and re-probes the loser on schedule."""
    from flowgger_tpu.tpu.overlap import RouteEconomics

    econ = RouteEconomics(probe_every=4, ok_spr=1e-5)
    assert econ.allow_fused()          # no sample: probe fused
    econ.observe("fused", 1000, 0.001)  # 1e-6 s/row: accelerator-fast
    assert econ.allow_fused()          # healthy: split never paid
    econ.observe("fused", 1000, 10.0)   # EWMA degrades well over ok_spr
    econ.observe("fused", 1000, 10.0)
    assert not econ.allow_fused()      # buy the split comparison
    econ.observe("host", 1000, 0.0001)  # split measures much cheaper
    allowed = [econ.allow_fused() for _ in range(8)]
    assert not all(allowed)            # split winning: mostly split...
    assert any(allowed)                # ...with scheduled fused re-probes
    assert econ.snapshot()["fused_s_per_row"] is not None


@pytest.fixture
def cache_config_restored():
    """Put the process-global persistent-cache config back after a test
    that pointed it somewhere."""
    from jax._src import compilation_cache as _cc

    from flowgger_tpu.tpu.device_common import CACHE_KNOBS

    saved = {k: getattr(jax.config, k) for k in CACHE_KNOBS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    _cc.reset_cache()


def test_kernel_abi_versions_cache_dir(tmp_path, monkeypatch,
                                       cache_config_restored):
    """setup_compile_cache folds the KERNEL_ABI rev into the layout of
    every directory the code chooses itself, so kernel-signature
    changes can't poison or silently invalidate old entries (the PR 4
    _encode_kernel footgun)."""
    from flowgger_tpu.tpu import device_common

    monkeypatch.delenv(device_common.CACHE_DIR_ENV, raising=False)
    cfg = Config.from_string(
        f'[input]\ntpu_compile_cache_dir = "{tmp_path}"\n')
    installed = device_common.setup_compile_cache(cfg)
    assert installed == os.path.join(
        str(tmp_path), f"kabi-{device_common.KERNEL_ABI}")
    assert os.path.isdir(installed)
    assert jax.config.jax_compilation_cache_dir == installed


def test_default_cache_is_one_fixed_dir_in_the_checkout(
        monkeypatch, cache_config_restored):
    """No key, no environment: off the CPU backend the cache goes to
    one fixed, git-ignored path inside the checkout — the same for
    every run, because the path is part of the cache key.  On the CPU
    backend nothing is switched on."""
    from flowgger_tpu.tpu import device_common

    monkeypatch.delenv(device_common.CACHE_DIR_ENV, raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    assert device_common.setup_compile_cache(Config.from_string("")) is None
    assert jax.config.jax_compilation_cache_dir is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache",
                        f"kabi-{device_common.KERNEL_ABI}")
    for _ in range(2):
        assert device_common.setup_compile_cache(
            Config.from_string("")) == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_default_cache_displaces_nothing_and_survives_a_readonly_install(
        tmp_path, monkeypatch, cache_config_restored, capsys):
    """Off the CPU backend the in-checkout default is only for a process
    nobody placed a cache for: a directory already in force (an AOT
    store's warmed xla-cache, an earlier handler's key) stays, and a
    checkout the default cannot be created in boots without a cache
    instead of crashing.  A directory someone named still raises."""
    from flowgger_tpu.tpu import device_common

    monkeypatch.delenv(device_common.CACHE_DIR_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_compilation_cache_dir", None)
    blocker = tmp_path / "a-file"
    blocker.write_text("")
    monkeypatch.setattr(device_common, "DEFAULT_CACHE_DIR",
                        str(blocker / ".jax_cache"))
    assert device_common.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None
    assert "cannot create" in capsys.readouterr().err
    with pytest.raises(OSError):
        device_common.enable_compile_cache(str(blocker / "named"))
    named = device_common.enable_compile_cache(str(tmp_path / "named"))
    assert jax.config.jax_compilation_cache_dir == named
    for _ in range(2):
        assert device_common.enable_compile_cache() == named
        assert device_common.setup_compile_cache(
            Config.from_string("")) == named
    assert jax.config.jax_compilation_cache_dir == named


def test_cache_placed_from_outside_is_used_as_it_is(
        tmp_path, monkeypatch, cache_config_restored):
    """JAX_COMPILATION_CACHE_DIR wins over every other source, gets no
    kabi suffix, and jax_compilation_cache_dir is never updated in code
    (JAX read the variable itself when it was imported)."""
    from flowgger_tpu.tpu import device_common

    outside = str(tmp_path / "placed")
    monkeypatch.setenv(device_common.CACHE_DIR_ENV, outside)
    before = jax.config.jax_compilation_cache_dir
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v))[1])
    cfg = Config.from_string(
        f'[input]\ntpu_compile_cache_dir = "{tmp_path / "key"}"\n')
    assert device_common.setup_compile_cache(cfg) == outside
    assert device_common.enable_compile_cache(
        str(tmp_path / "aot" / "xla-cache")) == outside
    assert "jax_compilation_cache_dir" not in updates
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "key").exists()
    assert not (tmp_path / "aot").exists()


@pytest.mark.requires_device_encode_compile
def test_fused_route_engages_compiled(monkeypatch):
    """Compiled-path engagement: on a host whose XLA can compile the
    fused program inside the watchdog, a clean rfc3164 batch rides the
    fused tier (fused_rows advances) with byte-identical output.  On
    hosts where the compile declines, the conftest marker hook turns
    the engagement failure into an informative xfail."""
    monkeypatch.delenv("FLOWGGER_FUSED_COMPILE_TIMEOUT_MS",
                       raising=False)
    enc = GelfEncoder(CFG)
    merger = LineMerger()
    lines = corpus("rfc3164", 32)
    dec = RFC3164Decoder(CFG)
    route = fused_routes.route_for("rfc3164", enc, merger)
    packed = pack.pack_lines_2d(lines, 256)
    before = _metrics.get("fused_rows")
    handle = fused_routes.submit(route, packed)
    res, _ = fused_routes.fetch_encode(handle, packed, enc, merger,
                                       None, {})
    assert res is not None, "fused compile declined by the watchdog"
    assert list(res.block.iter_framed()) == scalar_bytes(
        "rfc3164", lines, enc, merger)
    assert _metrics.get("fused_rows") > before


@pytest.mark.parametrize("lanes", [1, 2])
def test_fused_eager_lane_dispatch_byte_identity(lanes, monkeypatch):
    """Acceptance: fused output through the real BatchHandler + LaneSet
    sequencer is byte-identical across 1/2-lane dispatch (eager so the
    fused tier actually engages on this host)."""
    monkeypatch.setenv("FLOWGGER_COMPILE_TIMEOUT_MS", "0")
    monkeypatch.setenv("FLOWGGER_FUSED_COMPILE_TIMEOUT_MS", "0")
    cfg = Config.from_string(f'[input]\ntpu_lanes = {lanes}\n')
    enc = GelfEncoder(cfg)
    dec = RFC3164Decoder(cfg)
    merger = LineMerger()
    lines = corpus("rfc3164", 40)
    before = _metrics.get("fused_rows")
    tx = queue.Queue()
    with jax.disable_jit():
        h = BatchHandler(tx, dec, enc, cfg, fmt="rfc3164",
                         start_timer=False, merger=merger)
        try:
            # two batches so 2-lane dispatch actually uses both lanes
            for ln in lines[:20]:
                h.handle_bytes(ln)
            h.flush()
            for ln in lines[20:]:
                h.handle_bytes(ln)
            h.flush()
        finally:
            h.close()
    got = []
    while not tx.empty():
        item = tx.get_nowait()
        got.extend(item.iter_framed() if isinstance(item, EncodedBlock)
                   else [merger.frame(item)])
    assert got == scalar_bytes("rfc3164", lines, enc, merger)
    assert _metrics.get("fused_rows") > before  # fused tier engaged


@pytest.mark.slow
def test_fused_deep_fuzz_bounded():
    """ci.sh's slow step in-suite: one bounded pass of the fused-route
    fuzzer against the scalar oracle."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "tools",
                      "deep_fuzz.py"), "--routes", "fused", "3", "1"],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
