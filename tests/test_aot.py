"""Zero-JIT boot (tpu/aot.py): builder/validator round trips, the
strict-validating loader and its counted rejection ladder, byte
identity of AOT-loaded programs vs the live jit across framings and
lane counts, prewarm skip on artifact-booted processes, and the
cold-subprocess zero-compile acceptance.

The decode programs compile in seconds on this host, so their AOT hit
path runs for real (exported program executed, counters asserted).
The fused/encode programs cannot be compiled by every host's XLA (the
watchdog declines them here), so their AOT coverage is exercised at
the store/lookup level — the wrapped closures decline to the jit
ladder exactly like a cold jit compile, and the existing fused/device
differential tests seal that ladder's byte identity.
"""

import json
import os
import queue
import shutil
import subprocess
import sys

import numpy as np
import pytest

from flowgger_tpu.config import Config, ConfigError
from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
from flowgger_tpu.encoders.gelf import GelfEncoder
from flowgger_tpu.encoders.passthrough import PassthroughEncoder
from flowgger_tpu.mergers import LineMerger, NulMerger, SyslenMerger
from flowgger_tpu.tpu import aot, fused_routes, pack
from flowgger_tpu.tpu.batch import BatchHandler
from flowgger_tpu.utils.metrics import registry

CFG = Config.from_string("")
# 256 = pack._MIN_ROWS: every <=256-line flush packs to this bucket,
# so one built row bucket covers the whole suite's batches.  112 is a
# max_len no other test file uses — test_lanes' prewarm test needs its
# own width (96) to stay a FRESH compile in-process, and sharing it
# would warm the jit cache from here and break that test's persistence
# assert.
ROWS, MAX_LEN = 256, 112

LINES = {
    "rfc5424": [f'<34>1 2015-08-05T15:53:45.8Z host{i % 3} app 42 m '
                f'[x@9 a="v{i}"] hi {i}'.encode() for i in range(48)],
    "rfc3164": [f'<34>Aug  5 15:53:45 host{i % 3} app[42]: legacy '
                f'{i}'.encode() for i in range(48)],
    "ltsv": [f'host:h{i % 3}\ttime:2015-08-05T15:53:45Z\tk:v{i}\t'
             f'message:m {i}'.encode() for i in range(48)],
    "gelf": [('{"version":"1.1","host":"h%d","short_message":"m %d",'
              '"timestamp":1438790025.5}' % (i % 3, i)).encode()
             for i in range(48)],
}


# ---------------------------------------------------------------------------
# fixtures: one session artifact dir (decode matrix for all formats +
# the full rfc3164 family set so fused/encode coverage is checkable),
# loaded once; per-test activation with guaranteed deactivation


@pytest.fixture(scope="session")
def art_dir(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("aot") / "artifacts")
    aot.build_artifacts(out, platforms=("cpu",), families=("decode",),
                        rows_grid=(ROWS,), max_len=MAX_LEN,
                        framings=("line",), quiet=True)
    # one full family column (decode+fused+encode) so prewarm coverage
    # and the fused/encode key recipes are exercised against real
    # entries without exporting the whole (4x) encode matrix
    aot.build_artifacts(out, platforms=("cpu",),
                        families=("fused", "encode"),
                        formats=("rfc3164",), rows_grid=(ROWS,),
                        max_len=MAX_LEN, framings=("line",), quiet=True)
    # mark the dir warmed (per-platform marker in the kabi-versioned
    # xla-cache) without paying a real --warm pass: prewarm coverage
    # only skips for a warmed store, and the setup_aot tests that need
    # an UN-warmed dir strip this from their clone
    marker = aot._warm_marker_path(out, "cpu")
    os.makedirs(os.path.dirname(marker), exist_ok=True)
    open(marker, "w").close()
    return out


@pytest.fixture(scope="session")
def session_store(art_dir):
    store = aot.AotStore.load(art_dir)
    assert store is not None
    return store


@pytest.fixture
def active_store(session_store):
    aot.activate_store(session_store)
    yield session_store
    aot.activate_store(None)


@pytest.fixture
def no_store():
    aot.activate_store(None)
    yield
    aot.activate_store(None)


@pytest.fixture
def restore_jax_cache():
    """setup_aot auto-points JAX's persistent cache at the artifact
    dir; a leaked cache config taxes every later compile in the suite
    (PR 5 lesson), so snapshot + restore and reset the latch."""
    import jax

    from flowgger_tpu.tpu.device_common import CACHE_KNOBS

    old = {k: getattr(jax.config, k) for k in CACHE_KNOBS}
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    from jax._src import compilation_cache as _cc

    _cc.reset_cache()
    aot.activate_store(None)
    # reset the auto-point latch so test order can't leak a stale
    # displaced-config snapshot into a later unpoint
    with aot._active_lock:
        aot._auto_cache_root[0] = None
        aot._displaced_cache[0] = None


def _decode_ref(fmt, packed):
    import jax.numpy as jnp

    b, ln = jnp.asarray(packed[0]), jnp.asarray(packed[1])
    fn = aot._decode_fn(fmt)
    if fmt == "rfc3164":
        from flowgger_tpu.utils.timeparse import current_year_utc

        return fn(b, ln, jnp.int32(current_year_utc()))
    return fn(b, ln)


def _decode_submit(fmt, packed):
    if fmt == "rfc5424":
        from flowgger_tpu.tpu.rfc5424 import decode_rfc5424_submit

        return decode_rfc5424_submit(packed[0], packed[1])[0]
    if fmt == "rfc3164":
        from flowgger_tpu.tpu.rfc3164 import decode_rfc3164_submit

        return decode_rfc3164_submit(packed[0], packed[1])[0]
    if fmt == "ltsv":
        from flowgger_tpu.tpu.ltsv import decode_ltsv_submit

        return decode_ltsv_submit(packed[0], packed[1])[0]
    from flowgger_tpu.tpu.gelf import decode_gelf_submit

    return decode_gelf_submit(packed[0], packed[1])[0]


def _channels_equal(got, ref):
    assert set(got) == set(ref)
    for k in ref:
        assert (np.asarray(got[k]) == np.asarray(ref[k])).all(), k


# ---------------------------------------------------------------------------
# builder / validator


def test_build_validate_and_manifest_fields(art_dir):
    summary = aot.validate_artifacts(art_dir, quiet=True)
    assert summary["cpu/decode_rfc5424"] == 1
    assert summary["cpu/fused_rfc3164_gelf"] == 2   # probe + assemble
    assert summary["cpu/device_rfc3164"] == 2
    with open(os.path.join(art_dir, aot.MANIFEST_NAME)) as f:
        manifest = json.load(f)
    from flowgger_tpu.tpu.device_common import KERNEL_ABI

    assert manifest["kernel_abi"] == KERNEL_ABI
    assert manifest["rows_grid"] == [ROWS]
    assert manifest["max_len"] == MAX_LEN
    for entry in manifest["entries"].values():
        assert entry["sha256"] and entry["file"].endswith(".jaxexport")
        assert "statics" in entry and "spec" in entry


def test_builder_refuses_mixed_abi_or_shape_merge(art_dir, tmp_path):
    clone = tmp_path / "clone"
    shutil.copytree(art_dir, clone)
    mpath = clone / aot.MANIFEST_NAME
    manifest = json.loads(mpath.read_text())
    manifest["kernel_abi"] = 999
    mpath.write_text(json.dumps(manifest))
    with pytest.raises(RuntimeError, match="rebuild into a fresh"):
        aot.build_artifacts(str(clone), platforms=("cpu",),
                            families=("decode",), formats=("rfc5424",),
                            rows_grid=(ROWS,), max_len=MAX_LEN,
                            quiet=True)
    # shape mismatch is a separate, explicit error
    shutil.rmtree(clone)
    shutil.copytree(art_dir, clone)
    with pytest.raises(RuntimeError, match="same shape arguments"):
        aot.build_artifacts(str(clone), platforms=("cpu",),
                            families=("decode",), formats=("rfc5424",),
                            rows_grid=(128,), max_len=MAX_LEN,
                            quiet=True)


def test_tpu_fused_routes_serialize_and_roundtrip(tmp_path):
    """ISSUE acceptance: TPU-platform artifacts for all four fused
    routes serialize from this (non-TPU) host and survive deserialize
    + manifest validation."""
    out = str(tmp_path / "tpu-art")
    aot.build_artifacts(out, platforms=("tpu",), families=("fused",),
                        rows_grid=(ROWS,), max_len=MAX_LEN,
                        framings=("line",), quiet=True)
    summary = aot.validate_artifacts(out, quiet=True)
    for route in aot.FUSED_ROUTES:
        assert summary[f"tpu/fused_{route}"] == 2  # probe + assemble
    # the runtime loader must NOT accept tpu artifacts on this cpu host
    before = registry.get("aot_rejects_platform")
    assert aot.AotStore.load(out) is None
    assert registry.get("aot_rejects_platform") == before + 1


# ---------------------------------------------------------------------------
# loader: hit path byte identity


@pytest.mark.parametrize("fmt", ["rfc5424", "rfc3164", "ltsv", "gelf"])
def test_aot_decode_hit_identical_channels(fmt, active_store):
    packed = pack.pack_lines_2d(LINES[fmt], MAX_LEN)
    hits = registry.get("aot_hits")
    out = _decode_submit(fmt, packed)
    assert registry.get("aot_hits") == hits + 1
    aot.activate_store(None)
    _channels_equal(out, _decode_ref(fmt, packed))


@pytest.mark.parametrize("merger", [LineMerger(), NulMerger(),
                                    SyslenMerger()],
                         ids=["line", "nul", "syslen"])
@pytest.mark.parametrize("lanes", [1, 2])
def test_aot_boot_byte_identity_and_hits(merger, lanes, active_store):
    """DIFF_TEST anchor (FC03): an artifact-booted BatchHandler emits
    byte-identical output to the JIT path across line/nul/syslen
    framing and 1/2-lane dispatch, with aot_hits counted."""
    cfg = Config.from_string(
        f"[input]\ntpu_batch_size = {ROWS}\n"
        f"tpu_max_line_len = {MAX_LEN}\ntpu_lanes = {lanes}\n")
    lines = LINES["rfc5424"]

    def run():
        tx = queue.Queue()
        h = BatchHandler(tx, RFC5424Decoder(cfg), PassthroughEncoder(cfg),
                         cfg, fmt="rfc5424", start_timer=False,
                         merger=merger)
        try:
            for _ in range(2):   # two batches so 2 lanes both engage
                for ln in lines:
                    h.handle_bytes(ln)
                h.flush()
        finally:
            h.close()
        out = b""
        while not tx.empty():
            from flowgger_tpu.outputs import stream_bytes

            data, _ = stream_bytes(tx.get_nowait(), merger)
            out += data
        return out

    hits = registry.get("aot_hits")
    got = run()
    assert registry.get("aot_hits") > hits
    aot.activate_store(None)
    assert got == run()   # JIT-booted process bytes
    assert got == b"".join(merger.frame(ln) for ln in lines) * 2


# ---------------------------------------------------------------------------
# loader: every rejection path declines to the JIT ladder, counted,
# byte-identical


def _tamper(art_dir, tmp_path, fn):
    clone = str(tmp_path / "tampered")
    shutil.copytree(art_dir, clone)
    mpath = os.path.join(clone, aot.MANIFEST_NAME)
    with open(mpath) as f:
        manifest = json.load(f)
    fn(clone, manifest)
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    return clone


@pytest.mark.parametrize("field,value,reason", [
    ("aot_format", 99, "manifest_format"),
    ("kernel_abi", 999, "kernel_abi"),
    ("jax_version", "0.0.0", "jax_version"),
])
def test_aot_rejects_decline_to_jit_byte_identical(
        field, value, reason, art_dir, tmp_path, no_store):
    """DIFF_TEST anchor (FC03): a manifest the loader must refuse
    (wrong ABI/jax/format) declines the WHOLE boot to the JIT ladder —
    counted reject, no store, byte-identical output."""
    clone = _tamper(art_dir, tmp_path,
                    lambda d, m: m.__setitem__(field, value))
    before = registry.get(f"aot_rejects_{reason}")
    store = aot.AotStore.load(clone)
    assert store is None
    assert registry.get(f"aot_rejects_{reason}") == before + 1
    # the boot proceeds on the JIT ladder, byte-identical
    packed = pack.pack_lines_2d(LINES["rfc5424"], MAX_LEN)
    _channels_equal(_decode_submit("rfc5424", packed),
                    _decode_ref("rfc5424", packed))


def test_aot_reject_wrong_bucket_grid(art_dir, no_store):
    before = registry.get("aot_rejects_bucket_grid")
    assert aot.AotStore.load(art_dir, expect_grid=(ROWS, 4096)) is None
    assert registry.get("aot_rejects_bucket_grid") == before + 1
    # max_len mismatch counts the same reason (shape expectations)
    assert aot.AotStore.load(art_dir, expect_max_len=MAX_LEN + 32) is None
    assert registry.get("aot_rejects_bucket_grid") == before + 2


def test_aot_reject_corrupted_blob(art_dir, tmp_path, no_store):
    def corrupt(clone, manifest):
        key = next(k for k, e in manifest["entries"].items()
                   if e["family"] == "decode_gelf")
        path = os.path.join(clone, manifest["entries"][key]["file"])
        blob = bytearray(open(path, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        with open(path, "wb") as f:
            f.write(bytes(blob))

    clone = _tamper(art_dir, tmp_path, corrupt)
    store = aot.AotStore.load(clone)
    assert store is not None     # manifest itself is fine
    aot.activate_store(store)
    try:
        before = registry.get("aot_rejects_corrupt")
        packed = pack.pack_lines_2d(LINES["gelf"], MAX_LEN)
        out = _decode_submit("gelf", packed)       # declines to jit
        assert registry.get("aot_rejects_corrupt") == before + 1
    finally:
        aot.activate_store(None)
    _channels_equal(out, _decode_ref("gelf", packed))
    # the other formats' blobs are untouched and still hit
    aot.activate_store(store)
    try:
        hits = registry.get("aot_hits")
        _decode_submit("rfc5424", pack.pack_lines_2d(
            LINES["rfc5424"], MAX_LEN))
        assert registry.get("aot_hits") == hits + 1
    finally:
        aot.activate_store(None)


def test_aot_reject_manifest_without_entries(art_dir, tmp_path,
                                             no_store):
    """A parseable-but-truncated manifest (no entries table) must
    decline like any other mismatch, not KeyError out of the boot."""
    clone = _tamper(art_dir, tmp_path,
                    lambda d, m: m.pop("entries"))
    before = registry.get("aot_rejects_corrupt")
    assert aot.AotStore.load(clone) is None
    assert registry.get("aot_rejects_corrupt") == before + 1


def test_setup_aot_failed_load_counted_once(tmp_path, no_store):
    """Pipeline and BatchHandler both wire setup_aot on a boot; a bad
    dir's rejection must be counted/logged once, not per wiring pass."""
    bad = tmp_path / "bad-art"
    bad.mkdir()
    (bad / aot.MANIFEST_NAME).write_text("{\"aot_format\": 99}")
    cfg = Config.from_string(f'[input]\ntpu_aot_dir = "{bad}"\n')
    before = registry.get("aot_rejects")
    assert aot.setup_aot(cfg) is None                      # Pipeline
    assert registry.get("aot_rejects") == before + 1
    assert aot.setup_aot(cfg, max_len=64, grid=(256,)) is None  # handler
    assert registry.get("aot_rejects") == before + 1       # memoized


def test_scan_impl_single_source():
    """The builder's platform->impl mapping and the runtime's
    best_scan_impl must be the same function — drift = all-miss boot."""
    import jax

    from flowgger_tpu.tpu.rfc5424 import best_scan_impl

    assert best_scan_impl() == aot._scan_impl_for(jax.default_backend())


def test_fused_statics_and_key_recipe_name_no_kernel_tier():
    """One decode leg per fused route: the statics that key a fused
    artifact hold what the program is traced from and nothing that
    selects between tiers, and are every static the program takes
    besides ``assemble``.  An artifact keyed with the removed
    ``pallas`` entry misses (the store's ordinary miss path)."""
    import inspect

    from flowgger_tpu.tpu import fused_routes

    for route in aot.FUSED_ROUTES:
        statics = aot.fused_statics(route, b"\n", "mm", ())
        want = {"suffix", "impl", "extras", "demand", "elide"}
        if route.startswith("rfc5424_"):
            want.add("max_sd")
        assert set(statics) == want, route
        takes = set(inspect.signature(
            getattr(fused_routes, f"_fused_{route}").__wrapped__).parameters)
        assert "pallas" not in takes
        spec = [["uint8", [ROWS, MAX_LEN]], ["int32", [ROWS]]]
        key = aot.entry_key(f"fused_{route}", "tpu", statics, spec)
        assert key != aot.entry_key(f"fused_{route}", "tpu",
                                    {**statics, "pallas": "off"}, spec)


@pytest.mark.parametrize("family", ["pallas", "nonsense"])
def test_cli_refuses_a_family_that_is_not_there(family, tmp_path):
    assert aot.FAMILIES == ("decode", "fused", "encode", "framing")
    with pytest.raises(ValueError, match=f"unknown family.*{family}"):
        aot.main(["build", "--out", str(tmp_path / "art"),
                  "--families", family, "--rows", str(ROWS)])
    assert not (tmp_path / "art").exists()


def test_warm_artifacts_restores_cache_config(art_dir, tmp_path):
    """warm_artifacts must put the process-global persistent-cache
    config back (an in-process build-then-serve caller would otherwise
    write every later compile into the shipped artifact set)."""
    import jax

    clone = str(tmp_path / "warm-art")
    shutil.copytree(art_dir, clone)
    old = jax.config.jax_compilation_cache_dir
    warmed = aot.warm_artifacts(clone, keys=(), quiet=True)
    assert warmed == 0                       # keys=() warms nothing
    assert jax.config.jax_compilation_cache_dir == old


def test_warm_marker_platform_scoped(tmp_path, restore_jax_cache):
    """The warm marker is per platform and written only by a skip-free
    pass over EVERY entry of that platform: a tpu-only build warmed on
    this cpu box creates neither cache nor marker (the tpu fleet must
    not skip prewarm over executables that never compiled), a
    ``keys=`` subset or timed-out pass revokes warmth, and a complete
    pass claims it."""
    out = str(tmp_path / "tpu-art")
    aot.build_artifacts(out, platforms=("tpu",), families=("decode",),
                        formats=("rfc5424",), rows_grid=(ROWS,),
                        max_len=MAX_LEN, framings=("line",),
                        quiet=True, warm=True)
    assert not os.path.isdir(os.path.join(out, aot.XLA_CACHE_SUBDIR))
    cpu = str(tmp_path / "cpu-art")
    aot.build_artifacts(cpu, platforms=("cpu",), families=("decode",),
                        formats=("rfc5424", "gelf"), rows_grid=(ROWS,),
                        max_len=MAX_LEN, framings=("line",),
                        quiet=True, warm=True)
    store = aot.AotStore.load(cpu)
    assert store is not None and store.has_warm_cache()
    assert os.path.isfile(aot._warm_marker_path(cpu, "cpu"))
    # a subset pass revokes the marker up front and may not re-claim
    # it — the unselected entries' warmth is now unproven
    some = sorted(store.entries)[:1]
    assert aot.warm_artifacts(cpu, keys=some, quiet=True) == 1
    assert not store.has_warm_cache()
    # a timed-out (wedged) compile pass cannot claim warmth either
    assert aot.warm_artifacts(cpu, quiet=True, timeout_s=0.001) == 0
    assert not store.has_warm_cache()
    # a complete skip-free pass restores it (already-warm entries are
    # persistent-cache hits)
    assert aot.warm_artifacts(cpu, quiet=True) == 2
    assert store.has_warm_cache()
    # a manifest merge adding entries WITHOUT --warm revokes the claim
    # (the new entries never executed)
    aot.build_artifacts(cpu, platforms=("cpu",), families=("decode",),
                        formats=("rfc3164",), rows_grid=(ROWS,),
                        max_len=MAX_LEN, framings=("line",), quiet=True)
    assert not store.has_warm_cache()


def test_aot_reject_missing_route(art_dir, tmp_path, no_store):
    clone = _tamper(
        art_dir, tmp_path,
        lambda d, m: m.__setitem__("entries", {
            k: e for k, e in m["entries"].items()
            if e["family"] != "decode_ltsv"}))
    store = aot.AotStore.load(clone)
    assert store is not None
    aot.activate_store(store)
    try:
        before = registry.get("aot_rejects_missing_route")
        misses = registry.get("aot_misses")
        packed = pack.pack_lines_2d(LINES["ltsv"], MAX_LEN)
        out = _decode_submit("ltsv", packed)
        # missing_route is counted once per key; misses count each call
        assert registry.get("aot_rejects_missing_route") == before + 1
        assert registry.get("aot_misses") == misses + 1
        _decode_submit("ltsv", packed)
        assert registry.get("aot_rejects_missing_route") == before + 1
        assert registry.get("aot_misses") == misses + 2
    finally:
        aot.activate_store(None)
    _channels_equal(out, _decode_ref("ltsv", packed))


def test_non_default_statics_not_aot_addressable(active_store):
    """A non-default decode static (bigger max_sd) is not in the build
    recipe: the call skips the store entirely — no counters, plain jit."""
    from flowgger_tpu.tpu.rfc5424 import decode_rfc5424_submit

    packed = pack.pack_lines_2d(LINES["rfc5424"], MAX_LEN)
    hits = registry.get("aot_hits")
    misses = registry.get("aot_misses")
    decode_rfc5424_submit(packed[0], packed[1], max_sd=7)
    assert registry.get("aot_hits") == hits
    assert registry.get("aot_misses") == misses


def test_encode_and_fused_wrap_addressability(active_store):
    sentinel = object()
    # no store -> identity
    aot.activate_store(None)
    assert aot.encode_wrap("device_gelf", sentinel, None, None, {},
                           b"\n", "lax", ()) is sentinel
    assert aot.fused_wrap("rfc5424_gelf", sentinel, (None, None),
                          b"\n", "lax", ()) is sentinel
    # store active but non-default max_sd -> not addressable, identity
    aot.activate_store(active_store)
    assert aot.encode_wrap("device_gelf", sentinel, None, None, {},
                           b"\n", "lax", (), max_sd=7) is sentinel
    assert aot.fused_wrap("rfc5424_gelf", sentinel, (None, None),
                          b"\n", "lax", (), max_sd=7) is sentinel


# ---------------------------------------------------------------------------
# store coverage + prewarm skip


def test_store_covers_full_rfc3164_family(active_store):
    enc, merger = GelfEncoder(CFG), LineMerger()
    route = fused_routes.ROUTES["rfc3164"]
    assert aot.prewarm_covered("rfc3164", ROWS, MAX_LEN, encoder=enc,
                               merger=merger, fused_route=route)
    # decode-only coverage for the other formats
    assert aot.prewarm_covered("rfc5424", ROWS, MAX_LEN)
    # a bucket the grid never built is not covered
    assert not aot.prewarm_covered("rfc3164", 4 * ROWS, MAX_LEN,
                                   encoder=enc, merger=merger,
                                   fused_route=route)
    # rfc5424's encode family was not built -> full check is False
    assert not aot.prewarm_covered("rfc5424", ROWS, MAX_LEN,
                                   encoder=enc, merger=merger)
    # an un-warmed store never skips prewarm: the background pass pays
    # the exported programs' first-call compile instead of the stream
    from flowgger_tpu.tpu.device_common import KERNEL_ABI

    marker = os.path.join(active_store.xla_cache_dir,
                          f"kabi-{KERNEL_ABI}")
    os.rename(marker, marker + ".off")
    try:
        assert not aot.prewarm_covered("rfc3164", ROWS, MAX_LEN,
                                       encoder=enc, merger=merger,
                                       fused_route=route)
    finally:
        os.rename(marker + ".off", marker)


def test_prewarm_skips_aot_loaded_routes(active_store, capsys):
    from flowgger_tpu.tpu.device_common import prewarm_kernels

    skips = registry.get("prewarm_aot_skips")
    warmed = registry.get("prewarmed_shapes")
    t = prewarm_kernels("rfc3164", MAX_LEN, (ROWS,),
                        encoder=GelfEncoder(CFG), merger=LineMerger(),
                        fused_route=fused_routes.ROUTES["rfc3164"])
    t.join(timeout=60)
    assert not t.is_alive()
    assert registry.get("prewarm_aot_skips") == skips + 1
    assert registry.get("prewarmed_shapes") == warmed  # nothing compiled
    assert "AOT-loaded; skipping background compile" in \
        capsys.readouterr().err


# ---------------------------------------------------------------------------
# setup_aot wiring (config surface)


def test_setup_aot_modes_and_cache_pointing(art_dir, tmp_path,
                                            restore_jax_cache):
    import jax

    # a pristine clone: this test mutates the dir (strips then re-adds
    # the warm cache marker)
    clone = str(tmp_path / "art")
    shutil.copytree(art_dir, clone)
    shutil.rmtree(os.path.join(clone, aot.XLA_CACHE_SUBDIR),
                  ignore_errors=True)
    # no key: no-op, any active store untouched
    assert aot.setup_aot(Config.from_string("")) is None
    # auto + valid dir, NOT warmed: store active, but the persistent
    # cache is untouched (nothing to hit there; the dir may be a
    # read-only mount)
    old_cache = jax.config.jax_compilation_cache_dir
    cfg = Config.from_string(f'[input]\ntpu_aot_dir = "{clone}"\n')
    store = aot.setup_aot(cfg)
    assert store is not None and aot.active_store() is store
    assert jax.config.jax_compilation_cache_dir == old_cache
    # warmed dir (per-platform marker present): cache pointed inside
    # the artifact dir on the next wiring pass — displacing an
    # operator's stock cache config (plain env var, no flowgger key)
    marker = aot._warm_marker_path(clone, "cpu")
    os.makedirs(os.path.dirname(marker), exist_ok=True)
    open(marker, "w").close()
    stock = str(tmp_path / "stock-cache")
    jax.config.update("jax_compilation_cache_dir", stock)
    assert aot.setup_aot(cfg, max_len=MAX_LEN, grid=(ROWS,)) is store
    assert jax.config.jax_compilation_cache_dir.startswith(
        os.path.join(clone, aot.XLA_CACHE_SUBDIR))
    # shape mismatch on a later pass deactivates the store AND
    # un-points the cache (the JIT fallback must not write executables
    # into the shipped artifact dir) — RESTORING the displaced stock
    # config, not just switching persistent caching off
    before = registry.get("aot_rejects_bucket_grid")
    assert aot.setup_aot(cfg, max_len=MAX_LEN, grid=(ROWS, 4096)) is None
    assert aot.active_store() is None
    assert registry.get("aot_rejects_bucket_grid") == before + 1
    assert jax.config.jax_compilation_cache_dir == stock
    # off clears an active store AND restores stock persistent caching
    # when an earlier pass auto-pointed the cache into the artifact dir
    assert aot.setup_aot(cfg, max_len=MAX_LEN, grid=(ROWS,)) is not None
    assert jax.config.jax_compilation_cache_dir.startswith(
        os.path.join(clone, aot.XLA_CACHE_SUBDIR))
    assert aot.setup_aot(Config.from_string(
        f'[input]\ntpu_aot = "off"\ntpu_aot_dir = "{clone}"\n')) is None
    assert aot.active_store() is None
    assert jax.config.jax_compilation_cache_dir == stock


def test_setup_aot_explicit_cache_dir_wins(art_dir, tmp_path,
                                           restore_jax_cache):
    import jax

    mine = str(tmp_path / "my-cache")
    old = jax.config.jax_compilation_cache_dir
    aot.setup_aot(Config.from_string(
        f'[input]\ntpu_aot_dir = "{art_dir}"\n'
        f'tpu_compile_cache_dir = "{mine}"\n'))
    # setup_aot must NOT touch the cache when an explicit dir is
    # configured (setup_compile_cache installs it right after)
    assert jax.config.jax_compilation_cache_dir == old


def test_setup_aot_failed_new_root_keeps_active_store(
        session_store, tmp_path, no_store):
    """A handler configured with a bad artifact dir must not clobber
    another handler's working store (module invariant: only an
    explicit VALID config change swaps the active store)."""
    aot.activate_store(session_store)
    assert aot.setup_aot(Config.from_string(
        f'[input]\ntpu_aot_dir = "{tmp_path / "nope"}"\n')) is None
    assert aot.active_store() is session_store


def test_setup_aot_require_mode(art_dir, tmp_path, restore_jax_cache):
    with pytest.raises(ConfigError, match="needs input.tpu_aot_dir"):
        aot.setup_aot(Config.from_string('[input]\ntpu_aot = "require"\n'))
    with pytest.raises(ConfigError, match="failed validation"):
        aot.setup_aot(Config.from_string(
            f'[input]\ntpu_aot = "require"\n'
            f'tpu_aot_dir = "{tmp_path / "nope"}"\n'))
    with pytest.raises(ConfigError, match="auto, require or off"):
        aot.setup_aot(Config.from_string('[input]\ntpu_aot = "banana"\n'))


def test_batchhandler_boots_against_artifacts(art_dir,
                                              restore_jax_cache):
    """End-to-end config wiring: input.tpu_aot_dir on a BatchHandler
    config loads the store, the decode path hits it, and bytes match
    the framing contract."""
    cfg = Config.from_string(
        f"[input]\ntpu_batch_size = {ROWS}\n"
        f"tpu_max_line_len = {MAX_LEN}\n"
        f'tpu_aot_dir = "{art_dir}"\n')
    merger = LineMerger()
    tx = queue.Queue()
    hits = registry.get("aot_hits")
    h = BatchHandler(tx, RFC5424Decoder(cfg), PassthroughEncoder(cfg),
                     cfg, fmt="rfc5424", start_timer=False,
                     merger=merger)
    try:
        for ln in LINES["rfc5424"]:
            h.handle_bytes(ln)
        h.flush()
    finally:
        h.close()
    assert registry.get("aot_hits") > hits
    out = b""
    while not tx.empty():
        from flowgger_tpu.outputs import stream_bytes

        data, _ = stream_bytes(tx.get_nowait(), merger)
        out += data
    assert out == b"".join(merger.frame(ln) for ln in LINES["rfc5424"])


def test_batchhandler_boot_keeps_the_aot_cache_off_the_cpu_backend(
        art_dir, tmp_path, monkeypatch, restore_jax_cache):
    """setup_aot points the persistent cache at the artifact dir's
    warmed xla-cache; the handler's own cache wiring runs right after
    it, and off the CPU backend (where an unplaced cache defaults to
    <checkout>/.jax_cache) must leave that directory in force — or an
    input.tpu_aot_dir boot on a TPU recompiles everything it shipped."""
    import jax

    from flowgger_tpu.tpu import device_common

    class CacheWiringOnATpu:
        """device_common's view of jax alone: the store itself was
        built for, and must keep loading on, the CPU backend."""

        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def default_backend():
            return "tpu"

    monkeypatch.delenv(device_common.CACHE_DIR_ENV, raising=False)
    monkeypatch.setattr(device_common, "jax", CacheWiringOnATpu())
    monkeypatch.setattr(device_common, "DEFAULT_CACHE_DIR",
                        str(tmp_path / "default"))
    jax.config.update("jax_compilation_cache_dir", None)
    clone = str(tmp_path / "art")
    shutil.copytree(art_dir, clone)
    marker = aot._warm_marker_path(clone, "cpu")
    os.makedirs(os.path.dirname(marker), exist_ok=True)
    open(marker, "w").close()
    cfg = Config.from_string(
        f"[input]\ntpu_batch_size = {ROWS}\n"
        f"tpu_max_line_len = {MAX_LEN}\n"
        f'tpu_aot_dir = "{clone}"\n')
    h = BatchHandler(queue.Queue(), RFC5424Decoder(cfg),
                     PassthroughEncoder(cfg), cfg, fmt="rfc5424",
                     start_timer=False, merger=LineMerger())
    try:
        assert aot.active_store() is not None
        assert jax.config.jax_compilation_cache_dir == os.path.dirname(
            marker)
        assert not (tmp_path / "default").exists()
    finally:
        h.close()


# ---------------------------------------------------------------------------
# CLI


def test_aot_cli_build_and_validate(tmp_path):
    out = str(tmp_path / "cli-art")
    assert aot.main(["build", "--out", out, "--families", "decode",
                     "--formats", "rfc5424", "--rows", str(ROWS),
                     "--max-len", str(MAX_LEN),
                     "--framings", "line"]) == 0
    assert aot.main(["validate", out]) == 0


# ---------------------------------------------------------------------------
# cold-subprocess acceptance: zero fresh compiles on an artifact boot


@pytest.mark.slow
def test_aot_cold_boot_zero_compiles(tmp_path):
    """ISSUE acceptance: build + warm a CPU artifact set, then a cold
    subprocess booted with input.tpu_aot_dir performs ZERO fresh
    kernel compiles (compile_cache_misses == 0, aot_hits > 0) and its
    output is byte-identical to a JIT-booted process."""
    art = str(tmp_path / "art")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "FLOWGGER_DEVICE_ENCODE": "0"}

    def run(code):
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr
        return r.stdout.strip().splitlines()[-1]

    # builder host: export + warm (populates <art>/xla-cache)
    run(f"""
from flowgger_tpu.tpu import aot
aot.build_artifacts({art!r}, platforms=("cpu",), families=("decode",),
                    formats=("rfc5424",), rows_grid=(256,), max_len=64,
                    framings=("line",), warm=True, quiet=True)
print("built")
""")

    boot = """
import json, queue
from flowgger_tpu.config import Config
from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
from flowgger_tpu.encoders.passthrough import PassthroughEncoder
from flowgger_tpu.mergers import LineMerger
from flowgger_tpu.outputs import stream_bytes
from flowgger_tpu.tpu.batch import BatchHandler
from flowgger_tpu.utils.metrics import registry

cfg = Config.from_string(
    "[input]\\ntpu_batch_size = 64\\ntpu_max_line_len = 64\\n"
    "tpu_shape_buckets = 1\\ntpu_prewarm = false\\n" + EXTRA)
tx = queue.Queue()
merger = LineMerger()
h = BatchHandler(tx, RFC5424Decoder(cfg), PassthroughEncoder(cfg), cfg,
                 fmt="rfc5424", start_timer=False, merger=merger)
h.ingest_chunk(b"".join(
    b"<13>1 2024-01-01T00:00:00Z h a p m - msg %d\\n" % i
    for i in range(50)))
h.flush(); h.close()
out = b""
while not tx.empty():
    data, _ = stream_bytes(tx.get_nowait(), merger)
    out += data
print(json.dumps({"hits": registry.get("compile_cache_hits"),
                  "misses": registry.get("compile_cache_misses"),
                  "aot_hits": registry.get("aot_hits"),
                  "aot_rejects": registry.get("aot_rejects"),
                  "out": out.hex()}))
"""
    aot_boot = json.loads(run(
        f"EXTRA = 'tpu_aot_dir = \"{art}\"\\n'\n" + boot))
    jit_boot = json.loads(run("EXTRA = ''\n" + boot))

    assert aot_boot["out"] == jit_boot["out"]
    assert bytes.fromhex(aot_boot["out"]).count(b"\n") == 50
    assert aot_boot["aot_hits"] > 0
    assert aot_boot["aot_rejects"] == 0
    # THE acceptance: an artifact boot compiles nothing fresh — the
    # exported program's StableHLO->executable step hits the warmed
    # xla-cache shipped inside the artifact dir
    assert aot_boot["misses"] == 0
    assert aot_boot["hits"] > 0
