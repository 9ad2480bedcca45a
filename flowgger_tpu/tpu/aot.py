"""Zero-JIT boot: the versioned AOT kernel-artifact pipeline.

Every fresh process used to pay first-compile JIT for every (format,
encoder, bucket) it touched — on constrained hosts the device-encode
compiles never finish at all, and even the healthy compiles put minutes
between process start and the first emitted batch.  This module makes
startup a *load*, not a compile (the simdjson lesson, arxiv 1902.08318:
these decoders are fixed programs — precompile them, don't re-derive
them per process):

- **build** (``python -m flowgger_tpu.tpu.aot build --out DIR``): runs
  on any host, no accelerator needed.  Enumerates the live route
  matrix — the four block decoders, the four split device-encode
  kernels, and the four fused decode→encode programs
  (tpu/fused_routes.py) — across the configured shape-bucket grid
  (pack.shape_bucket_grid) and serializes each via ``jax.export``
  cross-platform lowering (TPU artifacts serialize from a CPU-only
  box).  A manifest records KERNEL_ABI, the jax version, platform,
  bucket grid, route name, the demand/elide static args, and a content
  hash per blob.  ``--warm`` additionally executes each CPU-platform
  program once with the persistent XLA compile cache pointed inside
  the artifact dir (``<out>/xla-cache``), so the *executable* ships
  alongside the portable StableHLO.

- **load** (``input.tpu_aot_dir``): BatchHandler installs the store
  before any kernel dispatch.  Decode submits, the fused-route tier,
  and the split device-encode kernels all consult the store first —
  a hit calls the deserialized exported program (``jax.jit(exp.call)``)
  instead of tracing + compiling; any mismatch (wrong KERNEL_ABI, jax
  version, bucket grid, platform, a corrupted blob, a missing route)
  declines to the existing JIT + watchdog + persistent-cache ladder
  with a counted reject reason.  ``aot_hits``/``aot_misses``/
  ``aot_rejects[_reason]`` counters let a production boot assert zero
  fresh compiles (``compile_cache_misses == 0`` with ``aot_hits > 0``).

The PR 5 persistent compile cache becomes the *fallback*, not the
plan: when the artifact dir carries a warmed ``xla-cache`` and no
explicit ``input.tpu_compile_cache_dir`` is configured, the loader
points JAX's cache there automatically, so even the one residual
compile per exported program (StableHLO → executable) is a cache hit.

Byte identity is unchanged at every rung: an AOT-loaded program IS the
jit program (same trace, same statics), and every decline lands on the
tiers whose identity the existing differential tests seal.
"""

from __future__ import annotations

# byte-identity contract (flowcheck FC03): AOT-loaded programs must be
# byte-identical to the JIT-booted pipeline (itself sealed against the
# scalar oracle); the differential tests run the same corpus through an
# artifact-booted handler and a plain one across line/nul/syslen
SCALAR_ORACLE = "flowgger_tpu.encoders.gelf:GelfEncoder"
DIFF_TEST = (
    "tests/test_aot.py::test_aot_boot_byte_identity_and_hits",
    "tests/test_aot.py::test_aot_rejects_decline_to_jit_byte_identical",
)

import hashlib
import json
import os
import sys
import threading
from typing import Dict, List, Optional, Tuple

MANIFEST_NAME = "manifest.json"
AOT_FORMAT = 1
XLA_CACHE_SUBDIR = "xla-cache"

DECODE_FORMATS = ("rfc5424", "rfc3164", "ltsv", "gelf", "jsonl", "dns")
ENCODE_MODULES = ("device_gelf", "device_rfc3164", "device_ltsv",
                  "device_gelf_gelf", "device_rfc5424_out",
                  "device_rfc5424_out_3164", "device_ltsv_out",
                  "device_capnp")
FUSED_ROUTES = ("rfc5424_gelf", "rfc3164_gelf", "ltsv_gelf", "gelf_gelf",
                "rfc5424_rfc5424", "rfc3164_rfc5424", "rfc5424_ltsv",
                "rfc5424_capnp")
# framing name -> block merger suffix; syslen shares "line"'s b"\n"
# (block_common.merger_suffix: the syslen prefix is a host-side splice)
FRAMINGS = {"line": b"\n", "nul": b"\x00"}
FAMILIES = ("decode", "fused", "encode", "framing")
# device-resident framing (tpu/framing.py): stage-A span kernels per
# input framing plus the shared stage-B gather
FRAMING_KINDS = ("line", "nul", "syslen")
# the byte-bucket each row bucket's framing artifact assumes (~128 B
# average records); other region sizes decline to the JIT ladder
FRAMING_AVG_BYTES = 128

# the active store is module state with the same contract as
# pack._SHAPE_BUCKETS: only an explicit config key (input.tpu_aot_dir /
# input.tpu_aot = "off") touches it, so a default-configured handler
# never silently drops another handler's artifacts
_active_lock = threading.Lock()
_active_store: List[Optional["AotStore"]] = [None]
# artifact root whose in-dir xla-cache setup_aot auto-pointed JAX's
# persistent cache at (None = setup_aot never touched the cache) — a
# later rejection of that same store must un-point it, or the JIT
# fallback ladder writes wrong-shape executables into the shipped
# artifact directory
_auto_cache_root: List[Optional[str]] = [None]
# the persistent-cache config enable_compile_cache displaced when
# setup_aot auto-pointed the cache (e.g. an operator's stock
# JAX_COMPILATION_CACHE_DIR): un-pointing must RESTORE it, not just
# clear the cache dir
_displaced_cache: List[Optional[Dict]] = [None]
# roots whose load already failed this process: Pipeline and
# BatchHandler both wire setup_aot on a normal boot, and re-loading a
# known-bad dir would count (and log) every boot-level rejection twice
_failed_roots: set = set()

_ABSENT = object()


def _snapshot_cache_config() -> Dict:
    """The current values of the persistent-cache knobs
    enable_compile_cache overwrites (``device_common.CACHE_KNOBS`` is
    the single source)."""
    import jax

    from .device_common import CACHE_KNOBS

    return {k: getattr(jax.config, k) for k in CACHE_KNOBS}


def _restore_cache_config(snapshot: Optional[Dict]) -> None:
    """Put back a ``_snapshot_cache_config`` snapshot (no snapshot =
    just clear the cache dir) and reset jax's latched cache state —
    the one restore dance shared by ``_unpoint_auto_cache`` and
    ``warm_artifacts``."""
    import jax
    from jax._src import compilation_cache as _cc

    for k, v in (snapshot
                 or {"jax_compilation_cache_dir": None}).items():
        jax.config.update(k, v)
    _cc.reset_cache()


def _metrics():
    from ..utils.metrics import registry

    return registry


def _scan_impl_for(platform: str) -> str:
    """THE platform->scan-impl mapping: plain cumsum on cpu, MXU
    tri-matmul elsewhere.  Single-sourced here — the builder stamps it
    into every fused/encode artifact key from the platform string
    (never the build host), and ``rfc5424.best_scan_impl`` delegates
    here at runtime, so the two sides cannot drift into a silent
    all-miss boot."""
    return "lax" if platform == "cpu" else "mm"


# ---------------------------------------------------------------------------
# canonical lookup keys: family + platform + static args + flattened
# input shapes/dtypes.  The builder and the loader both derive the key
# from the SAME helpers below, so a drift in either is a test failure,
# not a silent all-miss boot.

def _canon_static(v):
    if isinstance(v, bytes):
        return {"__bytes__": v.hex()}
    if isinstance(v, frozenset):
        return sorted(v)
    if isinstance(v, (tuple, list)):
        return [_canon_static(x) for x in v]
    if isinstance(v, dict):
        return {k: _canon_static(v[k]) for k in sorted(v)}
    return v


def canon_statics(statics: Dict) -> Dict:
    return {k: _canon_static(statics[k]) for k in sorted(statics)}


def args_spec(args) -> List:
    """Flattened (dtype, shape) list of an argument pytree — accepts
    arrays and ShapeDtypeStructs alike (dict leaves flatten in sorted
    key order on both sides)."""
    import jax

    return [[str(x.dtype), list(x.shape)]
            for x in jax.tree_util.tree_leaves(args)]


def entry_key(family: str, platform: str, statics: Dict,
              spec: List) -> str:
    blob = json.dumps({"family": family, "platform": platform,
                       "statics": canon_statics(statics), "spec": spec},
                      sort_keys=True, separators=(",", ":"))
    digest = hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]
    return f"{family.replace('/', '_')}--{platform}--{digest}"


# ---------------------------------------------------------------------------
# per-family static-arg recipes: ONE definition each, imported by the
# builder (export time) and by the call sites in rfc5424/rfc3164/ltsv/
# gelf/device_*/fused_routes (lookup time)

def decode_statics(fmt: str) -> Dict:
    if fmt == "rfc5424":
        from .rfc5424 import DEFAULT_MAX_SD

        return {"max_sd": DEFAULT_MAX_SD, "extract_impl": "sum"}
    if fmt == "ltsv":
        from .ltsv import DEFAULT_MAX_PARTS

        return {"max_parts": DEFAULT_MAX_PARTS}
    if fmt == "gelf":
        from .gelf import DEFAULT_MAX_FIELDS

        return {"max_fields": DEFAULT_MAX_FIELDS}
    if fmt == "jsonl":
        from .jsonl import DEFAULT_MAX_FIELDS

        return {"max_fields": DEFAULT_MAX_FIELDS}
    # rfc3164 (the year is a traced input, not a static) and dns (the
    # fixed grammar has no static knobs)
    return {}


def fused_statics(route_name: str, suffix: bytes, impl: str,
                  extras: Tuple) -> Dict:
    from .fused_routes import DEMAND

    statics = {"suffix": suffix, "impl": impl, "extras": extras,
               "demand": DEMAND[route_name], "elide": True}
    if route_name in ("rfc5424_gelf", "rfc5424_rfc5424", "rfc5424_ltsv",
                      "rfc5424_capnp"):
        from .rfc5424 import DEFAULT_MAX_SD

        statics["max_sd"] = DEFAULT_MAX_SD
    return statics


def framing_statics(kind: str, ncap: int, region_bytes: int) -> Dict:
    """Static-arg recipe for one framing stage-A kernel (kind in
    FRAMING_KINDS) or the stage-B gather (kind="gather", where ``ncap``
    carries max_len).  ONE definition shared by the builder and
    ``framing_call``'s call sites in tpu/framing.py."""
    if kind == "line":
        return {"sep": 10, "strip_cr": True, "ncap": ncap}
    if kind == "nul":
        return {"sep": 0, "strip_cr": False, "ncap": ncap}
    if kind == "syslen":
        from .framing import syslen_hops

        return {"ncap": ncap, "max_hops": syslen_hops(region_bytes)}
    if kind == "gather":
        return {"max_len": ncap}
    raise ValueError(f"unknown framing kind {kind!r}")


def encode_statics(module: str, suffix: bytes, impl: str,
                   extras: Tuple) -> Dict:
    if module == "device_gelf_gelf":
        return {"suffix": suffix, "elide": True}
    if module in ("device_rfc5424_out", "device_rfc5424_out_3164"):
        # the PR 19 output-leg kernels have no impl/extras statics; the
        # rfc5424 leg carries max_sd, the shared-core rfc3164 leg not
        statics = {"suffix": suffix, "elide": True}
        if module == "device_rfc5424_out":
            from .rfc5424 import DEFAULT_MAX_SD

            statics["max_sd"] = DEFAULT_MAX_SD
        return statics
    if module in ("device_ltsv_out", "device_capnp"):
        return {"suffix": suffix, "extras": extras, "elide": True}
    statics = {"suffix": suffix, "impl": impl, "extras": extras,
               "elide": True}
    if module == "device_gelf":
        from .rfc5424 import DEFAULT_MAX_SD

        statics["max_sd"] = DEFAULT_MAX_SD
    return statics


# ---------------------------------------------------------------------------
# loader / store

class AotStore:
    """A loaded artifact dir: validated manifest + lazily deserialized
    exported programs, each wrapped in ``jax.jit(exp.call)`` (the exact
    calling convention the builder's ``--warm`` used, so the warmed
    persistent-cache entries match)."""

    def __init__(self, root: str, manifest: Dict):
        self.root = root
        self.manifest = manifest
        self.entries: Dict[str, Dict] = manifest["entries"]
        self._calls: Dict[str, object] = {}
        self._bad: set = set()
        self._warned: set = set()
        self._lock = threading.Lock()

    @property
    def xla_cache_dir(self) -> str:
        return os.path.join(self.root, XLA_CACHE_SUBDIR)

    def has_warm_cache(self) -> bool:
        """True when a skip-free ``--warm`` pass populated the
        kabi-versioned xla-cache for THIS kernel ABI *and THIS host's
        platform* (the per-platform marker file) — a tpu-platform build
        warmed on a cpu box creates no ``warmed-tpu`` marker, so a tpu
        fleet host must not skip prewarm against executables that were
        never compiled."""
        return os.path.isfile(_warm_marker_path(self.root,
                                                self._platform()))

    @staticmethod
    def _platform() -> str:
        import jax

        return jax.default_backend()

    # -- load-time validation ---------------------------------------------
    @classmethod
    def load(cls, root: str, expect_grid=None,
             expect_max_len: Optional[int] = None) -> Optional["AotStore"]:
        """Load + strictly validate an artifact dir; None (with a
        counted ``aot_rejects_<reason>``) sends the boot down the JIT +
        persistent-cache ladder instead."""
        reg = _metrics()

        def reject(reason: str, msg: str) -> None:
            from ..obs import events as _events

            reg.inc("aot_rejects")
            reg.inc(f"aot_rejects_{reason}")
            _events.emit(
                "aot", "aot_reject", detail=f"{reason}: {msg}",
                route=root,
                msg=f"aot: rejecting artifact dir {root} ({msg}); "
                    "kernels use the JIT + persistent-cache ladder")

        try:
            with open(os.path.join(root, MANIFEST_NAME), "rb") as f:
                manifest = json.load(f)
        except Exception as e:  # noqa: BLE001 - any unreadable manifest declines
            reject("corrupt", f"unreadable manifest: {type(e).__name__}: {e}")
            return None
        if manifest.get("aot_format") != AOT_FORMAT:
            reject("manifest_format",
                   f"manifest format {manifest.get('aot_format')!r} != "
                   f"{AOT_FORMAT}")
            return None
        from .device_common import KERNEL_ABI

        if manifest.get("kernel_abi") != KERNEL_ABI:
            reject("kernel_abi",
                   f"artifact KERNEL_ABI {manifest.get('kernel_abi')!r} != "
                   f"running {KERNEL_ABI}")
            return None
        import jax

        if manifest.get("jax_version") != jax.__version__:
            reject("jax_version",
                   f"artifact jax {manifest.get('jax_version')!r} != "
                   f"running {jax.__version__}")
            return None
        platform = cls._platform()
        if platform not in manifest.get("platforms", []):
            reject("platform",
                   f"no artifacts for runtime platform '{platform}' "
                   f"(built: {manifest.get('platforms')})")
            return None
        shape_msg = cls._shape_mismatch(manifest, expect_grid,
                                        expect_max_len)
        if shape_msg:
            reject("bucket_grid", shape_msg)
            return None
        if not isinstance(manifest.get("entries"), dict):
            # a parseable-but-truncated manifest must decline like any
            # other mismatch, not KeyError out of the boot
            reject("corrupt", "manifest has no entries table")
            return None
        store = cls(root, manifest)
        n_here = sum(1 for e in store.entries.values()
                     if isinstance(e, dict)
                     and e.get("platform") == platform)
        print(f"aot: loaded {n_here} artifacts for platform "
              f"'{platform}' from {root} "
              f"(grid {manifest.get('rows_grid')}, "
              f"kabi {manifest.get('kernel_abi')})", file=sys.stderr)
        return store

    @staticmethod
    def _shape_mismatch(manifest: Dict, expect_grid,
                        expect_max_len: Optional[int]) -> Optional[str]:
        if (expect_max_len is not None
                and manifest.get("max_len") != expect_max_len):
            return (f"artifact max_len {manifest.get('max_len')} != "
                    f"configured {expect_max_len}")
        if expect_grid is not None:
            built = set(manifest.get("rows_grid", ()))
            missing = sorted(set(int(g) for g in expect_grid) - built)
            if missing:
                return (f"configured row buckets {missing} not in the "
                        f"artifact grid {sorted(built)}")
        return None

    def revalidate(self, expect_grid=None,
                   expect_max_len: Optional[int] = None) -> bool:
        """Re-check an already-loaded store against shape expectations
        learned after load (BatchHandler's max_len + bucket grid);
        False = reject (counted) and the caller deactivates it."""
        msg = self._shape_mismatch(self.manifest, expect_grid,
                                   expect_max_len)
        if msg is None:
            return True
        reg = _metrics()
        from ..obs import events as _events

        reg.inc("aot_rejects")
        reg.inc("aot_rejects_bucket_grid")
        _events.emit(
            "aot", "aot_reject", detail=f"bucket_grid: {msg}",
            route=self.root,
            msg=f"aot: rejecting artifact dir {self.root} ({msg}); "
                "kernels use the JIT + persistent-cache ladder")
        return False

    # -- lookup ------------------------------------------------------------
    def covers(self, family: str, statics: Dict, spec: List) -> bool:
        key = entry_key(family, self._platform(), statics, spec)
        return key in self.entries and key not in self._bad

    def find(self, family: str, statics: Dict, args):
        """The exported program's callable, or None (counted as a miss;
        a missing entry additionally counts the ``missing_route``
        reject reason the loader tests pin — once per key, while
        ``aot_misses`` counts every missed call)."""
        reg = _metrics()
        key = entry_key(family, self._platform(), statics,
                        args_spec(args))
        entry = self.entries.get(key)
        if entry is None:
            reg.inc("aot_misses")
            with self._lock:
                first = key not in self._warned
                self._warned.add(key)
            if first:
                reg.inc("aot_rejects")
                reg.inc("aot_rejects_missing_route")
            return None
        if key in self._bad:
            reg.inc("aot_misses")
            return None
        call = self._get_call(key, entry)
        if call is None:
            reg.inc("aot_misses")
        return call

    def _get_call(self, key: str, entry: Dict):
        with self._lock:
            call = self._calls.get(key)
        if call is not None:
            return call
        try:
            path = os.path.join(self.root, entry["file"])
            with open(path, "rb") as f:
                blob = f.read()
            if hashlib.sha256(blob).hexdigest() != entry["sha256"]:
                raise ValueError("content hash mismatch")
            import jax
            from jax import export as jexport

            call = jax.jit(jexport.deserialize(blob).call)
        except Exception as e:  # noqa: BLE001 - a bad blob must decline, not crash
            self.reject_entry(key, "corrupt",
                              f"{type(e).__name__}: {e}")
            return None
        with self._lock:
            self._calls[key] = call
        return call

    def reject_entry(self, key: str, reason: str, detail: str) -> None:
        reg = _metrics()
        with self._lock:
            self._bad.add(key)
            first = key not in self._warned
            self._warned.add(key)
        from ..obs import events as _events

        reg.inc("aot_rejects")
        reg.inc(f"aot_rejects_{reason}")
        _events.emit(
            "aot", "aot_reject", detail=f"{reason}: {detail}", route=key,
            msg=(f"aot: artifact [{key}] rejected ({reason}: {detail}); "
                 "that kernel uses the JIT ladder") if first else None)


def active_store() -> Optional[AotStore]:
    with _active_lock:
        return _active_store[0]


def activate_store(store: Optional[AotStore]) -> None:
    """Install (or clear, with None) the process-wide store — exposed
    for tests; production goes through setup_aot."""
    with _active_lock:
        _active_store[0] = store


def setup_aot(config, max_len: Optional[int] = None,
              grid=None) -> Optional[AotStore]:
    """Wire ``input.tpu_aot_dir`` / ``input.tpu_aot``.  No key = no-op
    (an already-active store from another handler stays).  ``require``
    turns a failed load into a startup ConfigError instead of a silent
    JIT boot — the production assert for artifact fleets.

    Called twice on a normal boot — Pipeline (before any device op,
    shape expectations unknown) and BatchHandler (max_len + bucket grid
    known): the second call revalidates the already-active store's
    manifest against the shape expectations without re-reading blobs.

    When the store loads and no explicit ``input.tpu_compile_cache_dir``
    is configured, JAX's persistent cache is pointed at the artifact
    dir's own ``xla-cache`` — the builder's ``--warm`` populated it, so
    even the residual StableHLO→executable compile of each exported
    program is a cache hit and the PR 5 cache becomes the fallback
    tier, not the plan."""
    mode = config.lookup_str(
        "input.tpu_aot",
        "input.tpu_aot must be a string (auto, require or off)", "auto")
    if mode not in ("auto", "require", "off"):
        from ..config import ConfigError

        raise ConfigError("input.tpu_aot must be auto, require or off")
    aot_dir = config.lookup_str(
        "input.tpu_aot_dir",
        "input.tpu_aot_dir must be a string (artifact directory)", None)
    if mode == "off":
        if aot_dir:
            activate_store(None)
            # clearing the store must also restore stock persistent
            # caching if an earlier wiring pass auto-pointed JAX's
            # cache inside an artifact dir — the JIT ladder this
            # config now runs on must not write executables into a
            # shipped artifact set
            with _active_lock:
                pointed = _auto_cache_root[0]
            if pointed is not None:
                _unpoint_auto_cache(pointed)
        return None
    if not aot_dir:
        if mode == "require":
            from ..config import ConfigError

            raise ConfigError(
                'input.tpu_aot = "require" needs input.tpu_aot_dir')
        return None
    root = os.path.expanduser(aot_dir)
    store = active_store()
    with _active_lock:
        already_failed = root in _failed_roots
    if store is not None and store.root == root:
        # second wiring pass (BatchHandler): revalidate the manifest
        # against the now-known shape expectations only
        if not store.revalidate(expect_grid=grid,
                                expect_max_len=max_len):
            activate_store(None)
            _unpoint_auto_cache(root)
            store = None
    elif already_failed:
        # this dir's rejection was already counted + logged by the
        # earlier wiring pass (Pipeline); don't double-count the boot
        store = None
    else:
        store = AotStore.load(root, expect_grid=grid,
                              expect_max_len=max_len)
        if store is not None:
            activate_store(store)
        else:
            # a failed load of a NEW root must not clobber another
            # handler's working store (module invariant above); this
            # handler simply boots on the JIT ladder
            with _active_lock:
                _failed_roots.add(root)
    if store is None:
        if mode == "require":
            from ..config import ConfigError

            raise ConfigError(
                f"input.tpu_aot = \"require\" but the artifact dir "
                f"{aot_dir} failed validation (see stderr)")
        return None
    explicit_cache = config.lookup_str(
        "input.tpu_compile_cache_dir",
        "input.tpu_compile_cache_dir must be a string (directory)", None)
    from .device_common import cache_placed_outside, enable_compile_cache

    if (not explicit_cache and not cache_placed_outside()
            and store.has_warm_cache()):
        # only a dir the builder actually warmed (kabi subdir present)
        # is worth pointing the persistent cache at, and never over a
        # place the environment chose; artifact dirs can live on
        # read-only mounts, so a failed install (EROFS, perms)
        # declines to stock cache behavior instead of crashing the boot
        displaced = _snapshot_cache_config()
        try:
            enable_compile_cache(store.xla_cache_dir)
        except OSError as e:
            print(f"aot: cannot use the artifact xla-cache at "
                  f"{store.xla_cache_dir} ({type(e).__name__}: {e}); "
                  "persistent caching keeps the stock configuration",
                  file=sys.stderr)
        else:
            with _active_lock:
                if _auto_cache_root[0] is None:
                    # first point: remember what we displaced (a
                    # re-point keeps the ORIGINAL stock config)
                    _displaced_cache[0] = displaced
                _auto_cache_root[0] = root
    return store


def _unpoint_auto_cache(root: str) -> None:
    """Restore the persistent-cache config setup_aot displaced when it
    pointed JAX's cache inside ``root``'s artifact dir (no-op
    otherwise) — an operator's stock cache (e.g. the plain
    JAX_COMPILATION_CACHE_DIR env var) comes back, it is not just
    switched off."""
    with _active_lock:
        if _auto_cache_root[0] != root:
            return
        _auto_cache_root[0] = None
        displaced = _displaced_cache[0]
        _displaced_cache[0] = None
    _restore_cache_config(displaced)


# ---------------------------------------------------------------------------
# call-site helpers (the loader half of each family recipe)

def decode_call(fmt: str, args, statics: Optional[Dict] = None
                ) -> Optional[Dict]:
    """AOT decode for one packed batch: the exported program's channel
    dict, or None → the caller runs its decode_*_jit as before.  Called
    from the decode submit fns (rfc5424/rfc3164/ltsv/gelf).  ``statics``
    is the caller's actual static-arg dict — when it differs from the
    canonical build recipe (a non-default max_sd, a forced impl) the
    configuration is not AOT-addressable and this returns None without
    touching the counters."""
    store = active_store()
    if store is None:
        return None
    recipe = decode_statics(fmt)
    if statics is not None and dict(statics) != recipe:
        return None
    call = store.find(f"decode_{fmt}", recipe, args)
    if call is None:
        return None
    try:
        out = call(*args)
    except Exception as e:  # noqa: BLE001 - decline to JIT, never lose the batch
        key = entry_key(f"decode_{fmt}", store._platform(),
                        decode_statics(fmt), args_spec(args))
        store.reject_entry(key, "call_error", f"{type(e).__name__}: {e}")
        return None
    _metrics().inc("aot_hits")
    return out


def framing_call(kind: str, args, statics: Dict):
    """AOT lookup for one framing kernel call (stage-A spans for a
    framing in FRAMING_KINDS, or kind="gather" for stage B): the
    exported program's output, or None → the caller runs its jit under
    the framing watchdog slot as before.  Same decline contract as
    decode_call: a call error rejects the entry and falls back, never
    losing the region."""
    store = active_store()
    if store is None:
        return None
    call = store.find(f"framing_{kind}", dict(statics), args)
    if call is None:
        return None
    try:
        out = call(*args)
    except Exception as e:  # noqa: BLE001 - decline to JIT, never lose the region
        key = entry_key(f"framing_{kind}", store._platform(),
                        dict(statics), args_spec(args))
        store.reject_entry(key, "call_error", f"{type(e).__name__}: {e}")
        return None
    _metrics().inc("aot_hits")
    return out


def wrap_kernel(family: str, kernel, args, statics: Dict):
    """Wrap a device-encode/fused kernel closure (``kernel(ts_text,
    ts_len, assemble)``) so each call consults the store first and
    declines to the jit closure on any miss/reject.  The wrapped call
    still runs under the driver's compile watchdog, so a cold
    xla-cache (exported program not yet compiled on this machine)
    degrades exactly like a cold jit compile."""
    store = active_store()
    if store is None:
        return kernel

    def wrapped(ts_text, ts_len, assemble):
        full = {**statics, "assemble": bool(assemble)}
        call_args = (*args, ts_text, ts_len)
        call = store.find(family, full, call_args)
        if call is not None:
            try:
                out = call(*call_args)
            except Exception as e:  # noqa: BLE001 - decline to JIT, never lose the batch
                key = entry_key(family, store._platform(), full,
                                args_spec(call_args))
                store.reject_entry(key, "call_error",
                                   f"{type(e).__name__}: {e}")
            else:
                _metrics().inc("aot_hits")
                return out
        return kernel(ts_text, ts_len, assemble)

    return wrapped


def encode_wrap(module: str, kernel, batch_dev, lens_dev, dec,
                suffix: bytes, impl: str, extras, max_sd=None):
    """Wrap a split device-encode kernel closure with the AOT lookup
    when this config is AOT-addressable — the statics must equal the
    canonical build recipe (``encode_statics``); a non-default
    ``max_sd`` is not addressable and keeps the plain jit closure
    (never touching the counters)."""
    store = active_store()
    if store is None:
        return kernel
    recipe = encode_statics(module, suffix, impl, extras)
    if max_sd is not None and recipe.get("max_sd") != max_sd:
        return kernel
    return wrap_kernel(module, kernel, (batch_dev, lens_dev, dec),
                       recipe)


def fused_wrap(route_name: str, kernel, args, suffix: bytes, impl: str,
               extras, max_sd=None):
    """Wrap a fused decode→encode kernel closure (``args`` = the
    committed device inputs, ``(b, ln)`` or ``(b, ln, year)`` for
    rfc3164) with the AOT lookup; same addressability contract as
    ``encode_wrap``."""
    store = active_store()
    if store is None:
        return kernel
    recipe = fused_statics(route_name, suffix, impl, extras)
    if max_sd is not None and recipe.get("max_sd") != max_sd:
        return kernel
    return wrap_kernel(f"fused_{route_name}", kernel, args, recipe)


def _shape_spec(rows: int, max_len: int, fmt: Optional[str] = None,
                ts_w: Optional[int] = None, dec_spec=None) -> List:
    """args_spec for a family at one bucket shape without building
    arrays (prewarm coverage checks)."""
    spec = [["uint8", [rows, max_len]], ["int32", [rows]]]
    if fmt == "rfc3164":
        spec.append(["int32", []])
    if dec_spec is not None:
        spec.extend(dec_spec)
    if ts_w is not None:
        spec.extend([["uint8", [rows, ts_w]], ["int32", [rows]]])
    return spec


def prewarm_covered(fmt: str, rows: int, max_len: int, encoder=None,
                    merger=None, fused_route=None,
                    ltsv_decoder=None) -> bool:
    """True when every program prewarm would compile for this (fmt,
    rows) bucket is already AOT-loaded — decode always, plus the fused
    probe/assemble pair when a fused route is engaged, plus the split
    device-encode pair when the split device tier applies.  Partial
    coverage returns False: the prewarm pass still runs (its decode
    submit hits the store anyway) so the uncovered programs warm.  An
    un-warmed store (built without ``--warm``) also returns False —
    loaded-but-cold exported programs still pay StableHLO→executable
    on first call, and the prewarm pass pays it in the background
    instead of the first real batch."""
    store = active_store()
    if (store is None or fmt not in DECODE_FORMATS
            or not store.has_warm_cache()):
        return False
    from .device_common import TS_W

    if not store.covers(f"decode_{fmt}", decode_statics(fmt),
                        _shape_spec(rows, max_len, fmt)):
        return False
    if encoder is None or merger is None:
        return True
    from .block_common import merger_suffix

    ms = merger_suffix(merger)
    if ms is None:
        return True
    suffix, _syslen = ms
    from .rfc5424 import best_scan_impl

    impl = best_scan_impl()
    extras = tuple((k, v) for k, v in getattr(encoder, "extra", ()))
    if fused_route is not None:
        statics = fused_statics(fused_route.name, suffix, impl, extras)
        for assemble, ts_w in ((False, 0), (True, TS_W)):
            if not store.covers(
                    f"fused_{fused_route.name}",
                    {**statics, "assemble": assemble},
                    _shape_spec(rows, max_len, fmt, ts_w=ts_w)):
                return False
        # prewarm warms the split pair too (the fused tier's decline
        # fallback), so coverage must include it — fall through
    for module in _ENCODE_MODULES_FOR_FMT.get(fmt, ()):
        # jsonl/dns have no entries (host block path is the only tier);
        # per-encoder route gates mean at most one module engages
        if not _split_route_ok(module, encoder, merger, ltsv_decoder):
            continue
        statics = encode_statics(module, suffix, impl, extras)
        dec_spec = _dec_spec_for(module, rows, max_len)
        for assemble, ts_w in ((False, 0), (True, TS_W)):
            if not store.covers(module,
                                {**statics, "assemble": assemble},
                                _shape_spec(rows, max_len, ts_w=ts_w,
                                            dec_spec=dec_spec)):
                return False
        break
    return True


# split device-encode legs per input format: the →GELF module first
# (the original tier), then the PR 19 output legs; batch.py engages at
# most one per batch (the route gates key on concrete encoder type)
_ENCODE_MODULES_FOR_FMT = {
    "rfc5424": ("device_gelf", "device_rfc5424_out", "device_ltsv_out",
                "device_capnp"),
    "rfc3164": ("device_rfc3164", "device_rfc5424_out_3164"),
    "ltsv": ("device_ltsv",),
    "gelf": ("device_gelf_gelf",),
}
_MODULE_FMT = {m: f for f, ms in _ENCODE_MODULES_FOR_FMT.items()
               for m in ms}
# AOT module name -> python module (the rfc3164→rfc5424 leg shares the
# SD-assembly core module under a distinct artifact family)
_MODULE_IMPORT = {"device_rfc5424_out_3164": "device_rfc5424_out"}


def _split_route_ok(module: str, encoder, merger,
                    ltsv_decoder=None) -> bool:
    import importlib

    mod = importlib.import_module(
        "." + _MODULE_IMPORT.get(module, module), __package__)
    if module == "device_ltsv":
        # the real dispatch gate sees the decoder: a schema'd LTSV
        # route is host work, so demanding split-encode coverage for
        # it would keep prewarm busy on a fully-covered boot
        return mod.route_ok(encoder, merger, ltsv_decoder)
    return mod.route_ok(encoder, merger)


def _dec_spec_for(module: str, rows: int, max_len: int) -> List:
    """Flattened decode-channel spec feeding one split encode kernel —
    via jax.eval_shape over the same decode jit the runtime handle
    carries (no compile, no arrays)."""
    import jax
    import jax.numpy as jnp

    b = jax.ShapeDtypeStruct((rows, max_len), jnp.uint8)
    ln = jax.ShapeDtypeStruct((rows,), jnp.int32)
    fmt = _MODULE_FMT[module]
    if fmt == "rfc3164":
        yr = jax.ShapeDtypeStruct((), jnp.int32)
        dec = jax.eval_shape(_decode_fn(fmt), b, ln, yr)
    else:
        dec = jax.eval_shape(_decode_fn(fmt), b, ln)
    return args_spec(dec)


# ---------------------------------------------------------------------------
# builder

def _decode_fn(fmt: str):
    statics = decode_statics(fmt)
    if fmt == "rfc5424":
        from .rfc5424 import decode_rfc5424_jit

        return lambda b, ln: decode_rfc5424_jit(b, ln, **statics)
    if fmt == "rfc3164":
        from .rfc3164 import decode_rfc3164_jit

        return lambda b, ln, yr: decode_rfc3164_jit(b, ln, yr)
    if fmt == "ltsv":
        from .ltsv import decode_ltsv_jit

        return lambda b, ln: decode_ltsv_jit(b, ln, **statics)
    if fmt == "jsonl":
        from .jsonl import decode_jsonl_jit

        return lambda b, ln: decode_jsonl_jit(b, ln, **statics)
    if fmt == "dns":
        from .dns import decode_dns_jit

        return lambda b, ln: decode_dns_jit(b, ln)
    from .gelf import decode_gelf_jit

    return lambda b, ln: decode_gelf_jit(b, ln, **statics)


def _fused_fn(route_name: str, statics: Dict):
    from . import fused_routes as _fr

    demand = statics["demand"]
    suffix, impl, extras = (statics["suffix"], statics["impl"],
                            statics["extras"])
    assemble = statics["assemble"]
    if route_name == "rfc5424_gelf":
        max_sd = statics["max_sd"]
        return lambda b, ln, ts, tl: _fr._fused_rfc5424_gelf(
            b, ln, ts, tl, max_sd=max_sd, suffix=suffix, impl=impl,
            assemble=assemble, extras=extras, demand=demand)
    if route_name == "rfc3164_gelf":
        return lambda b, ln, yr, ts, tl: _fr._fused_rfc3164_gelf(
            b, ln, yr, ts, tl, suffix=suffix, impl=impl,
            assemble=assemble, extras=extras, demand=demand)
    if route_name == "ltsv_gelf":
        return lambda b, ln, ts, tl: _fr._fused_ltsv_gelf(
            b, ln, ts, tl, suffix=suffix, impl=impl,
            assemble=assemble, extras=extras, demand=demand)
    if route_name == "rfc5424_rfc5424":
        max_sd = statics["max_sd"]
        return lambda b, ln, ts, tl: _fr._fused_rfc5424_rfc5424(
            b, ln, ts, tl, max_sd=max_sd, suffix=suffix,
            assemble=assemble, demand=demand)
    if route_name == "rfc3164_rfc5424":
        return lambda b, ln, yr, ts, tl: _fr._fused_rfc3164_rfc5424(
            b, ln, yr, ts, tl, suffix=suffix, assemble=assemble,
            demand=demand)
    if route_name == "rfc5424_ltsv":
        max_sd = statics["max_sd"]
        return lambda b, ln, ts, tl: _fr._fused_rfc5424_ltsv(
            b, ln, ts, tl, max_sd=max_sd, suffix=suffix,
            extras=extras, assemble=assemble, demand=demand)
    if route_name == "rfc5424_capnp":
        max_sd = statics["max_sd"]
        return lambda b, ln, ts, tl: _fr._fused_rfc5424_capnp(
            b, ln, ts, tl, max_sd=max_sd, suffix=suffix,
            extras=extras, assemble=assemble, demand=demand)
    return lambda b, ln, ts, tl: _fr._fused_gelf_gelf(
        b, ln, ts, tl, suffix=statics["suffix"],
        assemble=assemble, demand=demand)


def _encode_fn(module: str, statics: Dict):
    import importlib

    mod = importlib.import_module(
        "." + _MODULE_IMPORT.get(module, module), __package__)
    kernel = (mod._encode_kernel_3164
              if module == "device_rfc5424_out_3164"
              else mod._encode_kernel)
    kw = {k: v for k, v in statics.items() if k != "demand"}
    return lambda b, ln, dec, ts, tl: kernel(
        b, ln, dec, ts, tl, **kw)


def _framing_fn(kind: str, statics: Dict):
    """Builder-side callable for one framing kernel (the loader half is
    ``framing_call``)."""
    from . import framing as _framing

    if kind == "gather":
        return lambda region, starts, lens: _framing.frame_gather_jit(
            region, starts, lens, **statics)
    if kind == "syslen":
        return lambda region, rlen: _framing.frame_syslen_spans_jit(
            region, rlen, **statics)
    return lambda region, rlen: _framing.frame_sep_spans_jit(
        region, rlen, **statics)


def _export_one(fn, example_args, platform: str):
    import jax
    from jax import export as jexport

    return jexport.export(jax.jit(fn), platforms=[platform])(*example_args)


def build_artifacts(out_dir: str, platforms=("cpu",),
                    families=FAMILIES, formats=DECODE_FORMATS,
                    framings=("line", "nul"), rows_grid=None,
                    n_buckets: int = 4, batch_size: int = 16384,
                    max_len: int = 512, extras=(), warm: bool = False,
                    warm_timeout_s: float = 900.0,
                    quiet: bool = False) -> Dict:
    """Export the route matrix into ``out_dir`` and write/merge the
    manifest.  Re-invoking with more platforms/families merges into an
    existing manifest when the KERNEL_ABI and jax version match (so cpu
    and tpu sets can build in separate passes); anything else is an
    error — mixed-ABI artifact dirs must not exist."""
    import jax
    import jax.numpy as jnp

    from . import pack as _pack
    from .device_common import KERNEL_ABI, TS_W

    bad = sorted(set(formats) - set(DECODE_FORMATS))
    if bad:
        raise ValueError(f"unknown format(s) {bad} "
                         f"(expected {sorted(DECODE_FORMATS)})")
    bad = sorted(set(families) - set(FAMILIES))
    if bad:
        raise ValueError(f"unknown family(ies) {bad} "
                         f"(expected {sorted(FAMILIES)})")
    if rows_grid is None:
        rows_grid = _pack.shape_bucket_grid(n_buckets, batch_size)
    rows_grid = tuple(sorted({int(r) for r in rows_grid}))
    extras = tuple(tuple(kv) for kv in extras)
    os.makedirs(out_dir, exist_ok=True)
    manifest_path = os.path.join(out_dir, MANIFEST_NAME)
    if os.path.exists(manifest_path):
        with open(manifest_path, "rb") as f:
            manifest = json.load(f)
        if (manifest.get("kernel_abi") != KERNEL_ABI
                or manifest.get("jax_version") != jax.__version__
                or manifest.get("aot_format") != AOT_FORMAT):
            raise RuntimeError(
                f"{manifest_path} was built for kabi="
                f"{manifest.get('kernel_abi')} jax="
                f"{manifest.get('jax_version')}; rebuild into a fresh "
                "directory instead of mixing ABIs")
        if (manifest.get("max_len") != max_len
                or tuple(manifest.get("rows_grid", ())) != rows_grid):
            raise RuntimeError(
                f"{manifest_path} covers max_len="
                f"{manifest.get('max_len')} grid="
                f"{manifest.get('rows_grid')}; pass the same shape "
                "arguments when merging")
    else:
        manifest = {"aot_format": AOT_FORMAT, "kernel_abi": KERNEL_ABI,
                    "jax_version": jax.__version__, "platforms": [],
                    "rows_grid": list(rows_grid), "max_len": max_len,
                    "batch_size": batch_size, "entries": {}}

    suffixes = {}
    for fr in framings:
        if fr not in FRAMINGS:
            raise ValueError(f"unknown framing {fr!r} "
                             f"(expected {sorted(FRAMINGS)})")
        suffixes[FRAMINGS[fr]] = fr
    built = []

    def note(msg):
        if not quiet:
            print(f"aot build: {msg}", file=sys.stderr)

    def add_entry(family, platform, rows, route, fn, example_args,
                  statics):
        spec = args_spec(example_args)
        key = entry_key(family, platform, statics, spec)
        if key in manifest["entries"]:
            note(f"skip {key} (already built)")
            return
        exp = _export_one(fn, example_args, platform)
        blob = exp.serialize()
        fname = key + ".jaxexport"
        with open(os.path.join(out_dir, fname), "wb") as f:
            f.write(blob)
        manifest["entries"][key] = {
            "family": family, "platform": platform, "rows": rows,
            "max_len": max_len, "route": route,
            "statics": canon_statics(statics), "spec": spec,
            "file": fname, "bytes": len(blob),
            "sha256": hashlib.sha256(blob).hexdigest(),
        }
        built.append(key)
        note(f"exported {key} ({len(blob)} bytes)")

    for platform in platforms:
        impl = _scan_impl_for(platform)
        for rows in rows_grid:
            b = jax.ShapeDtypeStruct((rows, max_len), jnp.uint8)
            ln = jax.ShapeDtypeStruct((rows,), jnp.int32)
            yr = jax.ShapeDtypeStruct((), jnp.int32)
            probe_ts = jax.ShapeDtypeStruct((rows, 0), jnp.uint8)
            full_ts = jax.ShapeDtypeStruct((rows, TS_W), jnp.uint8)
            tl = jax.ShapeDtypeStruct((rows,), jnp.int32)
            if "decode" in families:
                for fmt in formats:
                    args = (b, ln, yr) if fmt == "rfc3164" else (b, ln)
                    add_entry(f"decode_{fmt}", platform, rows, fmt,
                              _decode_fn(fmt), args, decode_statics(fmt))
            if "fused" in families:
                for route_name in FUSED_ROUTES:
                    if route_name.split("_", 1)[0] not in formats:
                        continue
                    for suffix in suffixes:
                        for assemble, ts in ((False, probe_ts),
                                             (True, full_ts)):
                            statics = {
                                **fused_statics(route_name, suffix,
                                                impl, extras),
                                "assemble": assemble}
                            args = ((b, ln, yr, ts, tl)
                                    if route_name in ("rfc3164_gelf",
                                                      "rfc3164_rfc5424")
                                    else (b, ln, ts, tl))
                            add_entry(f"fused_{route_name}", platform,
                                      rows, route_name,
                                      _fused_fn(route_name, statics),
                                      args, statics)
            if "framing" in families:
                # device-resident framing: one stage-A span kernel per
                # framing kind + the shared stage-B gather, at this row
                # bucket's assumed byte bucket (~FRAMING_AVG_BYTES per
                # record; other region sizes hit the JIT ladder).  The
                # kernels are small (cumsum/scatter/gather planes), so
                # the full enumeration stays cheap to export.
                from .framing import region_bucket

                rb = region_bucket(rows * FRAMING_AVG_BYTES)
                reg = jax.ShapeDtypeStruct((rb,), jnp.uint8)
                rl = jax.ShapeDtypeStruct((), jnp.int32)
                for kind in FRAMING_KINDS:
                    fst = framing_statics(kind, rows, rb)
                    add_entry(f"framing_{kind}", platform, rows, kind,
                              _framing_fn(kind, fst), (reg, rl), fst)
                gst = framing_statics("gather", max_len, rb)
                sl = jax.ShapeDtypeStruct((rows,), jnp.int32)
                add_entry("framing_gather", platform, rows, "gather",
                          _framing_fn("gather", gst), (reg, sl, sl),
                          gst)
            if "encode" in families:
                for fmt in formats:
                    # jsonl/dns: no device-encode kernel (empty tuple);
                    # the decode channels are shared by every split
                    # module of this input format
                    dec = None
                    for module in _ENCODE_MODULES_FOR_FMT.get(fmt, ()):
                        for suffix in suffixes:
                            for assemble, ts in ((False, probe_ts),
                                                 (True, full_ts)):
                                if dec is None:
                                    if fmt == "rfc3164":
                                        dec = jax.eval_shape(
                                            _decode_fn(fmt), b, ln, yr)
                                    else:
                                        dec = jax.eval_shape(
                                            _decode_fn(fmt), b, ln)
                                statics = {
                                    **encode_statics(module, suffix,
                                                     impl, extras),
                                    "assemble": assemble}
                                add_entry(module, platform, rows, fmt,
                                          _encode_fn(module, statics),
                                          (b, ln, dec, ts, tl),
                                          statics)
        if platform not in manifest["platforms"]:
            manifest["platforms"].append(platform)

    manifest["platforms"].sort()
    with open(manifest_path, "w", encoding="utf-8") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    note(f"manifest: {len(manifest['entries'])} entries "
         f"({len(built)} new) -> {manifest_path}")
    if warm:
        # warm EVERY entry, not just this invocation's new ones — a
        # merge into a previously-unwarmed dir must not write a warm
        # marker over cold entries (already-warm ones are cache hits)
        warm_artifacts(out_dir, quiet=quiet, timeout_s=warm_timeout_s)
    elif built:
        # new entries with no warm pass: an existing marker for their
        # platform now overclaims — revoke it so has_warm_cache()
        # cannot suppress prewarm over never-executed programs
        for p in sorted({manifest["entries"][k]["platform"]
                         for k in built}):
            mk = _warm_marker_path(out_dir, p)
            if os.path.exists(mk):
                os.unlink(mk)
                note(f"revoked warm marker for '{p}' (new entries "
                     "are unwarmed; re-run with --warm)")
    return manifest


def _warm_marker_path(out_dir: str, platform: str) -> str:
    """The per-platform warm marker: written only by a skip-free warm
    pass, read by ``AotStore.has_warm_cache`` on the serving host."""
    from .device_common import KERNEL_ABI

    return os.path.join(out_dir, XLA_CACHE_SUBDIR,
                        f"kabi-{KERNEL_ABI}", f"warmed-{platform}")


def warm_artifacts(out_dir: str, keys=None, quiet: bool = False,
                   timeout_s: float = 900.0) -> int:
    """Execute each runnable exported program once with the persistent
    XLA cache pointed at ``<out>/xla-cache`` — after this, a fleet boot
    against the artifact dir performs zero fresh compiles (StableHLO →
    executable is a cache hit).  Only entries for THIS host's platform
    can run (tpu artifacts warm on the first tpu boot instead — no
    runnable entry means no cache is created and no warm marker
    written, so ``has_warm_cache`` stays False on the fleet).  Each
    warm runs under ``timeout_s`` — a wedged XLA compile (this repo's
    documented failure mode) skips that entry with a note instead of
    hanging the build CLI.  The per-platform warm marker is revoked at
    the start of every pass and re-written only by a skip-free pass
    over EVERY entry of this platform (a ``keys=`` subset or an
    errored/killed pass leaves warmth unclaimed).  Returns the number
    of programs warmed."""
    import numpy as np

    import jax
    from jax import export as jexport

    from .device_common import enable_compile_cache

    with open(os.path.join(out_dir, MANIFEST_NAME), "rb") as f:
        manifest = json.load(f)
    platform = jax.default_backend()
    platform_keys = [key
                     for key, entry in sorted(manifest["entries"].items())
                     if entry["platform"] == platform]
    runnable = [(key, manifest["entries"][key]) for key in platform_keys
                if keys is None or key in keys]
    if not runnable:
        if not quiet:
            print(f"aot warm: no runnable entries for platform "
                  f"'{platform}' (cross-platform artifacts warm on "
                  "their own fleet's first boot)", file=sys.stderr)
        return 0
    # warmth is uncertain from here until the pass proves otherwise —
    # an error/kill mid-pass must not leave a stale marker claiming
    # the cache covers entries that never executed
    marker = _warm_marker_path(out_dir, platform)
    if os.path.exists(marker):
        os.unlink(marker)
    from .device_common import CACHE_DIR_ENV, cache_placed_outside

    if cache_placed_outside():
        # the environment placed the cache: the warm pass may not point
        # it at the artifact dir, and a marker over entries that landed
        # elsewhere would lie
        print(f"aot warm: {CACHE_DIR_ENV} is set, so the cache cannot be "
              f"pointed at {out_dir}; unset it to warm an artifact dir",
              file=sys.stderr)
        return 0
    # the warm loop must point the process-global persistent cache at
    # the artifact dir — and must put it back: an in-process caller
    # (library use, build-then-serve) would otherwise keep writing
    # every later compile into the shipped artifact set with zeroed
    # persist thresholds (the exact hazard _unpoint_auto_cache guards
    # on the load side)
    old_cache = _snapshot_cache_config()
    enable_compile_cache(os.path.join(out_dir, XLA_CACHE_SUBDIR))
    warmed, skipped = 0, 0
    try:
        for key, entry in runnable:
            with open(os.path.join(out_dir, entry["file"]), "rb") as f:
                exp = jexport.deserialize(f.read())
            leaves = [np.zeros(a.shape, a.dtype) for a in exp.in_avals]
            args, kwargs = jax.tree_util.tree_unflatten(exp.in_tree,
                                                        leaves)
            if not quiet:
                # named BEFORE the call so a wedged compile identifies
                # its entry even if the operator has to kill the build
                print(f"aot warm: {key} ...", file=sys.stderr)
            box: List = [None]

            def _run(exp=exp, args=args, kwargs=kwargs, box=box):
                try:
                    jax.block_until_ready(
                        jax.jit(exp.call)(*args, **kwargs))
                except BaseException as e:  # noqa: BLE001 - ferried to the caller
                    box[0] = e

            t = threading.Thread(target=_run, daemon=True,
                                 name=f"aot-warm:{key}")
            t.start()
            t.join(timeout_s)
            if t.is_alive():
                skipped += 1
                print(f"aot warm: {key} still compiling after "
                      f"{timeout_s:.0f}s; skipping (the fleet pays "
                      "this compile at first boot — prewarm stays on)",
                      file=sys.stderr)
                continue
            if box[0] is not None:
                raise box[0]
            warmed += 1
    finally:
        _restore_cache_config(old_cache)
    if skipped == 0 and len(runnable) == len(platform_keys):
        # only a skip-free pass over EVERY entry of this platform may
        # claim warmth — a keys= subset leaves the rest cold, and
        # has_warm_cache() suppressing prewarm over cold fused/encode
        # programs is exactly the first-batch stall this guards
        with open(marker, "w", encoding="utf-8") as f:
            f.write(f"{warmed}\n")
    return warmed


def validate_artifacts(out_dir: str, quiet: bool = False) -> Dict:
    """Deserialize + hash-verify EVERY entry of EVERY platform (the
    build-only acceptance for platforms this host cannot execute, e.g.
    tpu artifacts exported from a cpu box).  Raises on any failure;
    returns a per-platform/per-family summary."""
    from jax import export as jexport

    with open(os.path.join(out_dir, MANIFEST_NAME), "rb") as f:
        manifest = json.load(f)
    if manifest.get("aot_format") != AOT_FORMAT:
        raise RuntimeError(f"manifest format {manifest.get('aot_format')!r}"
                           f" != {AOT_FORMAT}")
    for field in ("kernel_abi", "jax_version", "rows_grid", "max_len",
                  "platforms", "entries"):
        if field not in manifest:
            raise RuntimeError(f"manifest missing field {field!r}")
    summary: Dict[str, int] = {}
    for key, entry in sorted(manifest["entries"].items()):
        path = os.path.join(out_dir, entry["file"])
        with open(path, "rb") as f:
            blob = f.read()
        if hashlib.sha256(blob).hexdigest() != entry["sha256"]:
            raise RuntimeError(f"{key}: content hash mismatch")
        exp = jexport.deserialize(blob)
        if entry["platform"] not in exp.platforms:
            raise RuntimeError(
                f"{key}: manifest platform {entry['platform']!r} not in "
                f"exported platforms {exp.platforms}")
        nspec = len(entry["spec"])
        if len(exp.in_avals) != nspec:
            raise RuntimeError(
                f"{key}: {len(exp.in_avals)} exported inputs != "
                f"{nspec} in the manifest spec")
        label = f"{entry['platform']}/{entry['family']}"
        summary[label] = summary.get(label, 0) + 1
    if not quiet:
        print(f"aot validate: {len(manifest['entries'])} entries OK "
              f"({json.dumps(summary, sort_keys=True)})", file=sys.stderr)
    return summary


# ---------------------------------------------------------------------------
# CLI

def _csv(s: str) -> Tuple[str, ...]:
    return tuple(x.strip() for x in s.split(",") if x.strip())


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m flowgger_tpu.tpu.aot",
        description="AOT kernel artifact pipeline (zero-JIT boot)")
    sub = ap.add_subparsers(dest="verb", required=True)

    b = sub.add_parser("build", help="export the route matrix")
    b.add_argument("--out", required=True)
    b.add_argument("--platforms", default="cpu", type=_csv)
    b.add_argument("--families", default=",".join(FAMILIES), type=_csv)
    b.add_argument("--formats", default=",".join(DECODE_FORMATS),
                   type=_csv)
    b.add_argument("--framings", default="line,nul", type=_csv)
    b.add_argument("--rows", default=None,
                   help="explicit row buckets, e.g. 256,2048 "
                        "(default: --buckets geometric grid)")
    b.add_argument("--buckets", type=int, default=4,
                   help="bucket count for pack.shape_bucket_grid")
    b.add_argument("--batch-size", type=int, default=16384)
    b.add_argument("--max-len", type=int, default=512)
    b.add_argument("--warm", action="store_true",
                   help="execute each runnable program once with the "
                        "XLA cache at <out>/xla-cache")
    b.add_argument("--warm-timeout-s", type=float, default=900.0,
                   help="per-program warm budget; a wedged XLA compile "
                        "skips the entry (and revokes the warm marker) "
                        "instead of hanging the build")

    v = sub.add_parser("validate",
                       help="deserialize + hash-verify every entry")
    v.add_argument("dir")

    args = ap.parse_args(argv)
    if args.verb == "build":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        rows = (tuple(int(r) for r in _csv(args.rows))
                if args.rows else None)
        build_artifacts(args.out, platforms=args.platforms,
                        families=args.families, formats=args.formats,
                        framings=args.framings, rows_grid=rows,
                        n_buckets=args.buckets,
                        batch_size=args.batch_size,
                        max_len=args.max_len, warm=args.warm,
                        warm_timeout_s=args.warm_timeout_s)
        return 0
    validate_artifacts(args.dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
