"""Shared machinery for device-side encode kernels (device_gelf,
device_rfc3164, ...): gather-free JSON escaping, per-row segment
assembly, on-device row compaction, and the host fetch driver with
tier gating, decline hysteresis, and output-sized D2H.

Every format-specific module contributes only (a) a jitted kernel
``kernel(ts_text, ts_len, assemble) -> tier | (acc, out_len, tier)``
built from these primitives plus its own segment table, and (b) a
``route_ok`` predicate; the fetch flow (phase-1 tier probe, timestamp
text upload, compaction, syslen prefixing, fallback splicing) is one
implementation here.

The reference fuses decode→encode per line in its hot loop
(line_splitter.rs:44-54 → encoder/mod.rs:54-56); this is the batched
TPU shape of that fusion, for every format pair that rides it.
"""

from __future__ import annotations

import atexit
import os
import sys
import threading
from functools import lru_cache, partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import tracer as _tracer
from ..utils.metrics import registry as _metrics
from .assemble import exclusive_cumsum
from .materialize import compute_ts

_I32 = jnp.int32
_U8 = jnp.uint8


# -- the link ----------------------------------------------------------------
# Every batch crosses the host-device link twice, and both crossings go
# through here so that they are counted where the bytes move (``h2d_bytes``
# with the lines' own share of it, ``packed_line_bytes``; ``d2h_bytes``,
# and ``d2h_calls``: the times a thread blocked on the link for them) and,
# while tracing is on, bounded as sub-spans of the stage they lie in
# (obs/trace.py).  A copy back costs by the call and hardly by the byte
# (PERF.md section 5), so a program whose outputs are all wanted has their
# copies begun together at its dispatch (``d2h_begin``, counted in
# ``d2h_prefetched``) and collected with one wait (``d2h_all``); ``d2h``
# stays for a copy that depends on the one before it.

def _put(batch, lens, device):
    if device is not None:
        return jax.device_put(batch, device), jax.device_put(lens, device)
    return jnp.asarray(batch), jnp.asarray(lens)


def h2d(batch, lens, device=None, parent="decode"):
    """Upload one packed batch and its row lengths: committed to
    ``device`` (lane dispatch), else onto the default device, uncommitted.
    Arrays that are on a device already (device framing, a lane's
    ``block_submit`` ahead of the format's own submit) pass through the
    same calls uncounted: nothing crosses the link for them.  ``parent``
    is the stage the caller is in (the rfc5424 rescue uploads its rows
    from inside ``fetch``)."""
    if not isinstance(batch, np.ndarray):
        return _put(batch, lens, device)
    nbytes = batch.nbytes + lens.nbytes
    with _tracer.sub(_tracer.bound(), "h2d", parent, nbytes=nbytes):
        on_device = _put(batch, lens, device)
    _metrics.inc("h2d_bytes", nbytes)
    # padding rows have length 0, so this is the real rows' bytes
    _metrics.inc("packed_line_bytes", int(lens.sum()))
    return on_device


def d2h(arr):
    """One blocking device-to-host copy (the first one after a dispatch
    also waits for the program)."""
    with _tracer.sub(_tracer.bound(), "d2h", "fetch",
                     nbytes=getattr(arr, "nbytes", None)):
        host = np.asarray(arr)
    _metrics.inc("d2h_calls")
    _metrics.inc("d2h_bytes", host.nbytes)
    return host


def d2h_begin(out):
    """Begin the device-to-host copy of every output array of a program
    that has just been dispatched.  Nothing blocks: each copy is queued
    behind the program and lands in a host buffer that the array keeps,
    so the ``d2h_all`` that comes for them later does not go to the
    device again."""
    leaves = jax.tree_util.tree_leaves(out)
    for leaf in leaves:
        leaf.copy_to_host_async()
    _metrics.inc("d2h_prefetched", len(leaves))
    return out


def d2h_all(out):
    """The host arrays of a program's output dict, in its order: one
    wait on the link for all of them where ``d2h_begin`` began their
    copies at dispatch (and one blocking copy after another where it
    did not)."""
    nbytes = sum(v.nbytes for v in out.values())
    with _tracer.sub(_tracer.bound(), "d2h", "fetch", nbytes=nbytes,
                     note=str(len(out))):
        host = {k: np.asarray(v) for k, v in out.items()}
    _metrics.inc("d2h_calls")
    _metrics.inc("d2h_bytes", nbytes)
    return host


# -- compile watchdog --------------------------------------------------------
# The device-encode kernels are large; on some hosts/backends their XLA
# compile can take minutes (observed: effectively unbounded on old CPU
# containers).  The fast path is optional — a compile must never stall
# the stream — so the first call of each kernel phase runs under a
# wall-clock deadline: on timeout the compile keeps warming the jit
# cache in a worker thread while every batch meanwhile declines to the
# host block-encode path (same bytes), and once the background compile
# lands the device tier engages normally.  The workers belong to this
# module: ``join_compile_workers`` (called at pipeline drain) waits for
# them, so the interpreter never finalises with a thread inside XLA.
COMPILE_TIMEOUT_ENV = "FLOWGGER_COMPILE_TIMEOUT_MS"
COMPILE_TIMEOUT_MS_DEFAULT = 15_000
# how long a drain waits for compiles still in flight before it gives
# up on them (they stay daemon threads, so a wedged compile can delay
# an exit but never block it)
COMPILE_JOIN_TIMEOUT_S = 120.0

_compile_slots: Dict[str, threading.Event] = {}
_compile_ready = set()  # names that have completed once: call inline
_compile_lock = threading.Lock()
_compile_warned = set()
# live compile worker threads (guarded by _compile_lock); each worker
# removes itself when its compile lands
_compile_workers: set = set()
# cumulative decline count, independent of the (resettable) metrics
# registry — tests/conftest.py reads it to turn a watchdog-declined
# differential test into an informative xfail
_decline_total = 0
# single-flight: at most ONE background kernel compile at a time.  The
# big device-encode compiles are multi-GB XLA jobs; running several
# concurrently (plus the foreground's own jit work) has crashed the
# process on constrained hosts.  Queued compiles wait here — their
# guarded callers decline instantly in the meantime.
_compile_sema = threading.Semaphore(1)
# slot name currently holding _compile_sema ("name" key present iff a
# compile is in flight).  A fresh guarded call observing an in-flight
# compile declines immediately instead of waiting out a deadline its
# own queued compile can never meet (the foreground used to stall a
# full FLOWGGER_COMPILE_TIMEOUT_MS per fresh kernel+shape behind one
# wedged compile).  A box rather than a bare global so each worker
# thread clears exactly the instance it marked — tests that swap in an
# isolated semaphore swap this box alongside it, and an in-flight
# worker from before the swap can neither corrupt the new box nor
# leave a stale name in the restored one.
_compile_active_box: Dict[str, str] = {}


class CompileTimeout(Exception):
    """A device-encode kernel is still compiling; decline this batch."""


def _compile_deadline_s() -> float:
    try:
        ms = int(os.environ.get(COMPILE_TIMEOUT_ENV,
                                COMPILE_TIMEOUT_MS_DEFAULT))
    except ValueError:
        ms = COMPILE_TIMEOUT_MS_DEFAULT
    return ms / 1000.0


def compile_decline_count() -> int:
    """Process-cumulative watchdog declines (never reset — unlike the
    metrics registry counter of the same event)."""
    return _decline_total


_decline_count_lock = threading.Lock()


def _count_decline() -> None:
    global _decline_total
    from ..utils.metrics import registry as _reg

    _reg.inc("device_encode_compile_declines")
    with _decline_count_lock:
        _decline_total += 1


def join_compile_workers(timeout_s: float = COMPILE_JOIN_TIMEOUT_S) -> int:
    """Wait (bounded) for every compile worker still in flight; returns
    how many were still alive at the deadline.  The pipeline calls this
    at drain, after the handlers closed, so a worker that outlived its
    watchdog-declined caller has landed before the process exits."""
    import time as _time

    deadline = _time.monotonic() + timeout_s
    while True:
        with _compile_lock:
            workers = [t for t in _compile_workers if t.is_alive()]
        if not workers:
            return 0
        left = deadline - _time.monotonic()
        if left <= 0:
            return len(workers)
        workers[0].join(left)


# processes that never reach a pipeline drain (tools, tests) still must
# not finalise the interpreter around a live compile
atexit.register(join_compile_workers)


def guarded_compile_call(name: str, fn, *args, timeout_s=None):
    """Run a (potentially compiling) jit call with a deadline.

    Raises CompileTimeout when the call exceeds the deadline — the call
    finishes in a background worker thread so the jit cache still warms
    — or instantly while that background run is still going.  A value
    of ``FLOWGGER_COMPILE_TIMEOUT_MS=0`` disables the watchdog.
    ``timeout_s`` overrides the deadline for this call (the fused-route
    tier runs its first-compile waits under a tighter budget)."""
    timeout = _compile_deadline_s() if timeout_s is None else timeout_s
    if timeout <= 0:
        return fn(*args)
    done = threading.Event()
    # pair the semaphore with its active-slot box at call time, so the
    # worker marks/clears the same instances the busy check reads even
    # if a test swaps the module globals mid-flight
    sema, active = _compile_sema, _compile_active_box
    declined = False
    with _compile_lock:
        if name in _compile_ready:
            # jit cache warm for this name+shape: call inline (also the
            # landing path for background compiles — the worker marks
            # readiness itself, so a landed kernel never re-queues
            # behind another kernel's compile on the semaphore)
            _compile_slots.pop(name, None)
            ready = True
        else:
            ready = False
            pending = _compile_slots.get(name)
            if pending is not None and not pending.is_set():
                # journal + raise AFTER the lock: the journal may write
                # a disk sink, and every caller probing the slot table
                # would serialize behind it
                declined = True
            else:
                # claim the slot inside this same critical section so
                # two threads can never spawn duplicate compiles of one
                # kernel (a finished-but-errored slot is replaced)
                _compile_slots[name] = done
                busy = active.get("name")
    if declined:
        _count_decline()
        from ..obs import events as _events

        _events.emit("compile", "watchdog_decline", detail=name)
        raise CompileTimeout(name)
    if ready:
        return fn(*args)
    box: dict = {}

    def run():
        try:
            with sema:
                with _compile_lock:
                    active["name"] = name
                try:
                    # trace + compile or cache load + first run; on
                    # this worker, so it belongs to no batch's thread
                    with _tracer.sub(None, "compile", None, note=name):
                        box["result"] = fn(*args)
                finally:
                    with _compile_lock:
                        active.pop("name", None)
        except BaseException as e:  # noqa: BLE001 - ferried to the caller
            box["error"] = e
        else:
            with _compile_lock:
                _compile_ready.add(name)
        finally:
            done.set()
            with _compile_lock:
                _compile_workers.discard(threading.current_thread())

    # the worker must outlive its (watchdog-declined) caller so the
    # compile lands for the next call: the caller never joins it, the
    # module does (join_compile_workers, at pipeline drain)
    worker = threading.Thread(target=run, daemon=True,
                              name=f"xla-compile:{name}")
    with _compile_lock:
        _compile_workers.add(worker)
    worker.start()
    if busy is not None:
        # another kernel's compile holds the single-flight semaphore
        # RIGHT NOW, so this one cannot even start XLA work before the
        # deadline — waiting it out is provably futile.  Decline
        # immediately (the queued thread still warms the cache once the
        # semaphore frees); the batch takes the host path meanwhile.
        # On healthy hosts the semaphore is almost always free, so this
        # path only engages while a compile is genuinely in flight.
        _count_decline()
        from ..obs import events as _events

        msg = None
        if name not in _compile_warned:
            _compile_warned.add(name)
            msg = (f"device-encode kernel [{name}] queued behind the "
                   f"in-flight [{busy}] compile; using the host encode "
                   "path until it lands")
        _events.emit("compile", "busy_decline", detail=name, msg=msg)
        raise CompileTimeout(name)
    if not done.wait(timeout):
        _count_decline()
        from ..obs import events as _events

        msg = None
        if name not in _compile_warned:
            _compile_warned.add(name)
            msg = (f"device-encode kernel [{name}] still compiling "
                   f"after {timeout:.0f}s; using the host encode path "
                   "until it lands")
        _events.emit("compile", "watchdog_decline", detail=name,
                     cost=timeout, cost_unit="deadline_s", msg=msg)
        raise CompileTimeout(name)
    with _compile_lock:
        _compile_slots.pop(name, None)
        if "error" not in box:
            _compile_ready.add(name)
    if "error" in box:
        raise box["error"]
    return box["result"]

# -- persistent compile cache + prewarm --------------------------------------
# A fresh (rows, max_len) shape costs a full XLA compile — about 11 s
# per fused program for a v5e — which the watchdog converts into
# host-path declines.  Two fixes compose: JAX's persistent compilation
# cache makes every compile a once-per-machine cost, and the background
# prewarm compiles the configured format's kernels for the shape-bucket
# grid at startup so the first real batch hits a warm jit cache.  Cache
# traffic is observable as ``compile_cache_hits``/``compile_cache_
# misses`` counters (a second cold process of the same config should
# report zero misses for the prewarmed kernels).
#
# Where the cache lives is decided in ONE function, enable_compile_cache,
# for the pipeline, bench.py and chip_smoke.py alike:
# ``JAX_COMPILATION_CACHE_DIR`` wins and is used as it is (JAX reads it
# itself; no code here sets another directory); else a directory a
# caller names (``input.tpu_compile_cache_dir``, an AOT store's
# xla-cache); else the one already in force; else DEFAULT_CACHE_DIR
# inside the checkout — on an accelerator backend.  On the CPU backend
# nobody asked and nothing is switched on: XLA:CPU logs two
# multi-kilobyte error lines for every entry it loads (its
# machine-feature check trips on the compiler's own pseudo-features)
# and a CPU compile of these kernels takes seconds.

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, git-ignored, inside the checkout: the path is part of the
# cache key, so one built from a pid, a temporary name or the time
# would never hit
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_cache_state_lock = threading.Lock()
_cache_listener_installed = False

# Kernel ABI revision folded into the layout of every directory this
# code chooses itself.  JAX's cache key covers the traced computation,
# NOT our kernel-level contracts: a signature/layout change leaves
# stale entries of the OLD kernels in the dir forever.  Bump this
# whenever a kernel signature, segment layout, or channel contract
# changes; old revisions keep their own subdirectory and die with
# ordinary cache cleanup.  (The AOT manifest pins the same number.)
KERNEL_ABI = 9


def _install_cache_listener() -> None:
    """Bridge JAX's compilation-cache monitoring events into the metrics
    registry (idempotent; the listener registry is process-global)."""
    global _cache_listener_installed
    with _cache_state_lock:
        if _cache_listener_installed:
            return
        _cache_listener_installed = True
    from jax import monitoring as _monitoring

    from ..utils.metrics import registry as _reg

    def _on_event(event, **_kw):
        if event.endswith("/cache_hits"):
            _reg.inc("compile_cache_hits")
        elif event.endswith("/cache_misses"):
            _reg.inc("compile_cache_misses")

    _monitoring.register_event_listener(_on_event)


# every persistent-cache knob enable_compile_cache mutates, paired
# with the value it sets — the ONE place both the enable loop and the
# snapshot/restore sites (tpu/aot.py, the test fixtures, via
# CACHE_KNOBS) derive from, so a knob added here is set AND restored
CACHE_KNOB_SETTINGS = (
    ("jax_persistent_cache_min_compile_time_secs", 0.0),
    ("jax_persistent_cache_min_entry_size_bytes", 0),
)
CACHE_KNOBS = (("jax_compilation_cache_dir",)
               + tuple(k for k, _ in CACHE_KNOB_SETTINGS))


def cache_placed_outside() -> bool:
    """``JAX_COMPILATION_CACHE_DIR`` is set: the operator placed the
    cache, and no code of this repo may point it anywhere else."""
    return bool(os.environ.get(CACHE_DIR_ENV))


def enable_compile_cache(cache_dir: Optional[str] = None
                         ) -> Optional[str]:
    """Switch JAX's persistent compilation cache on and start counting
    hits/misses; returns the directory in use.  Placement, in order:
    ``JAX_COMPILATION_CACHE_DIR`` as it is (``cache_dir`` is then
    ignored and ``jax_compilation_cache_dir`` is never updated here),
    else ``cache_dir``, else the directory already in force (an AOT
    store's warmed xla-cache, an earlier handler's key: the default
    displaces nothing), else — off the CPU backend — ``DEFAULT_CACHE_
    DIR``.  A directory chosen here is versioned by ``KERNEL_ABI``
    (``<dir>/kabi-<N>``).  Unplaced on the CPU backend, or where the
    default cannot be created (a read-only install), nothing is
    switched on and None comes back; a named directory that cannot be
    created raises.  Thresholds are dropped to zero so even the small
    decode kernels persist."""
    from jax._src import compilation_cache as _cc

    changed = False
    if cache_placed_outside():
        cache_dir = os.environ[CACHE_DIR_ENV]
    elif cache_dir is None and jax.config.jax_compilation_cache_dir:
        cache_dir = jax.config.jax_compilation_cache_dir
    elif cache_dir is None and jax.default_backend() == "cpu":
        return None
    else:
        named = cache_dir is not None
        cache_dir = os.path.join(
            os.path.expanduser(cache_dir or DEFAULT_CACHE_DIR),
            f"kabi-{KERNEL_ABI}")
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError as e:
            if named:
                raise
            print(f"compile cache: cannot create {cache_dir} "
                  f"({type(e).__name__}: {e}); running without a "
                  f"persistent cache (set {CACHE_DIR_ENV} or "
                  "input.tpu_compile_cache_dir to place one)",
                  file=sys.stderr)
            return None
        if jax.config.jax_compilation_cache_dir != cache_dir:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
            changed = True
    for knob, val in CACHE_KNOB_SETTINGS:
        if getattr(jax.config, knob) != val:
            jax.config.update(knob, val)
            changed = True
    if changed:
        # jax latches the use-the-cache decision at the first compile;
        # a process that already compiled something (tests, a handler
        # built before the config was read) must reset that memo or the
        # new settings are silently ignored
        _cc.reset_cache()
    _install_cache_listener()
    return cache_dir


def setup_compile_cache(config) -> Optional[str]:
    """The pipeline's cache wiring: ``input.tpu_compile_cache_dir`` names
    the directory unless the environment already placed it; with
    neither, the in-checkout default (none on the CPU backend).
    Returns the directory in use."""
    return enable_compile_cache(config.lookup_str(
        "input.tpu_compile_cache_dir",
        "input.tpu_compile_cache_dir must be a string (directory)", None))


def _zero_packed(rows: int, max_len: int):
    """A zero-row packed tuple of device shape [rows, max_len] — the
    cheapest input that still compiles every kernel phase (n_real = 0:
    nothing is emitted, fetched bodies are empty)."""
    return (np.zeros((rows, max_len), dtype=np.uint8),
            np.zeros(rows, dtype=np.int32), b"",
            np.zeros(rows, dtype=np.int32),
            np.zeros(0, dtype=np.int32), 0)


def prewarm_kernels(fmt: str, max_len: int, row_buckets, encoder=None,
                    merger=None, ltsv_decoder=None, supervisor=None,
                    devices=None, fused_route=None):
    """Background-compile ``fmt``'s decode kernel — and, when the
    device-encode route applies (encoder+merger given), its encode
    phases — for every shape in ``row_buckets``.

    Runs on one daemon thread (spawned through the pipeline Supervisor
    when given, so a crash restarts with backoff instead of silently
    losing the warmup).  The cheap decode compiles run directly on this
    thread — the prewarm worker IS the off-stream background the
    watchdog would otherwise provide, and queueing them on the
    watchdog's single-flight semaphore would starve them forever behind
    a stuck encode compile.  The huge device-encode compiles keep their
    existing ``FLOWGGER_COMPILE_TIMEOUT_MS`` watchdog + single-flight
    path inside ``fetch_encode_driver`` (a timeout there declines
    cleanly while the compile keeps warming).  ``devices`` (lane
    dispatch) warms one executable per lane device — jit caches key on
    placement, so a default-device warmup would leave lanes 1..N cold.
    With a persistent cache installed every landed compile also becomes
    a once-per-machine cost.  Returns the thread."""
    buckets = [int(b) for b in row_buckets]
    devs = list(devices) if devices else [None]

    def run():
        from ..utils.metrics import registry as _reg
        from .aot import prewarm_covered
        from .batch import block_fetch_encode, block_submit

        for rows in buckets:
            # zero-JIT boot: a bucket whose every program is already
            # AOT-loaded needs no background compile — the store's
            # exported programs replace trace+compile at dispatch.  On
            # a fully artifact-booted process the prewarm thread is
            # idle (one log line per skipped route)
            if prewarm_covered(fmt, rows, max_len, encoder=encoder,
                               merger=merger, fused_route=fused_route,
                               ltsv_decoder=ltsv_decoder):
                _reg.inc("prewarm_aot_skips")
                print(f"kernel prewarm: {fmt}@{rows}x{max_len} "
                      "AOT-loaded; skipping background compile",
                      file=sys.stderr)
                continue
            for di, dev in enumerate(devs):
                packed = _zero_packed(rows, max_len)
                name = f"prewarm:{fmt}:{rows}x{max_len}:d{di}"
                try:
                    # the jit *call* compiles synchronously, right here
                    # on the prewarm thread
                    handle = block_submit(fmt, packed, None, dev)
                    if encoder is not None and merger is not None:
                        # device-encode probe/assemble compiles are
                        # guarded inside fetch_encode_driver; a timeout
                        # there simply declines to the host block path
                        # while the compile keeps warming in background
                        block_fetch_encode(fmt, handle, packed, encoder,
                                           merger, ltsv_decoder,
                                           route_state={})
                        if fused_route is not None:
                            # warm the fused single-program route too —
                            # same guarded/decline semantics
                            from . import fused_routes as _fr

                            fh = _fr.submit(fused_route, packed, dev)
                            _fr.fetch_encode(fh, packed, encoder,
                                             merger, ltsv_decoder,
                                             route_state={})
                    _reg.inc("prewarmed_shapes")
                except CompileTimeout:
                    continue  # still compiling in the watchdog's worker
                except Exception as e:  # noqa: BLE001 - warmup must never kill ingest
                    print(f"kernel prewarm [{name}] failed: "
                          f"{type(e).__name__}: {e}", file=sys.stderr)

    if supervisor is not None:
        return supervisor.spawn(run, "tpu-prewarm", exhausted="return")
    t = threading.Thread(target=run, daemon=True, name="tpu-prewarm")
    t.start()
    return t


TS_W = 32          # timestamp text slot width (longest json_f64 ≈ 25)
E_CAP = 56         # max JSON escapes per row on the device tier

# group granularity (bytes) of on-device compaction: 8 keeps the mean
# per-row padding at ~G/2 = 4 bytes (it was 16 at the old G=32 — most
# of the gap between fetched and emitted bytes/row) for ~2 extra barrel
# stages, each a fused elementwise pass
COMPACT_G = 8
# skip compaction when padded size is within this factor of the real
# output (the extra device passes would not pay for the smaller fetch)
COMPACT_MIN_SAVING = 1.15


def _shr2d(arr, k):
    """Shift rows right by static k (drop tail, zero-fill head)."""
    if k == 0:
        return arr
    return jnp.pad(arr[:, :-k], ((0, 0), (k, 0)))


def _monotone_expand(vals, shifts, w_out, nbits):
    """Place vals[i,j] at column j + shifts[i,j]; shifts nondecreasing
    along each row, < 2**nbits. Vacated slots become 0 (vals must be 0
    where nothing is emitted). MSB-first barrel: collision-free because
    intermediate positions j + (s>>k<<k) stay strictly increasing."""
    x = jnp.pad(vals, ((0, 0), (0, w_out - vals.shape[1])))
    s = jnp.pad(shifts, ((0, 0), (0, w_out - shifts.shape[1])))
    for k in range(nbits - 1, -1, -1):
        d = 1 << k
        mv = s >= d
        xm = jnp.where(mv, x, 0)
        sm = jnp.where(mv, s - d, 0)
        x = jnp.where(mv, 0, x) | _shr2d(xm, d)
        s = jnp.where(mv, 0, s) + _shr2d(sm, d)
    return x


def _rot_rows(x, r, w: int):
    """Cyclic right-rotate each row of [N, w] by per-row r (w pow2)."""
    for k in range(w.bit_length() - 1):
        d = 1 << k
        bit = ((r >> k) & 1) == 1
        rolled = jnp.concatenate([x[:, -d:], x[:, :-d]], axis=1)
        x = jnp.where(bit[:, None], rolled, x)
    return x


def _out_width(L: int, src_width: int = 0) -> int:
    """Static output width: a power of two covering the concatenated
    source row (``src_width`` = escaped line + constant bank + ts text,
    which the rotate-assembly requires to fit) and typical GELF output
    for lines of width L."""
    w = 512
    while w < 2 * L or w < src_width:
        w *= 2
    return w


def escape_stage(batch, lens, iota, assemble: bool):
    """JSON-escape classification + (when assembling) the escaped row.

    Returns a dict with: ``esc_row`` ([N, L+E_CAP] u8 escaped bytes, or
    None when not assembling), ``esc_i`` (int [N, L] escape indicator),
    ``ne_total`` ([N] escapes per row), ``bad_ctl`` ([N, L] control
    bytes needing 6-byte \\u00XX escapes — off-tier), and ``dmap(a)``
    mapping raw offsets to escaped offsets."""
    bb = batch.astype(_I32)
    valid = iota < lens.astype(_I32)[:, None]
    two_ctl = ((bb == 8) | (bb == 9) | (bb == 10) | (bb == 12) | (bb == 13))
    esc = ((bb == 34) | (bb == 92) | two_ctl) & valid
    bad_ctl = (bb < 32) & ~two_ctl & valid
    esc_i = esc.astype(_I32)
    ne_incl = jnp.cumsum(esc_i, axis=1)
    ne_excl = ne_incl - esc_i
    ne_total = ne_incl[:, -1]

    esc_row = None
    if assemble:
        mapped = jnp.where(bb == 8, ord("b"),
                 jnp.where(bb == 9, ord("t"),
                 jnp.where(bb == 10, ord("n"),
                 jnp.where(bb == 12, ord("f"),
                 jnp.where(bb == 13, ord("r"), bb)))))
        mapped = jnp.where(valid, mapped, 0).astype(_I32)
        nbits = E_CAP.bit_length()
        EW = batch.shape[1] + E_CAP
        s_main = jnp.minimum(ne_excl + esc_i, E_CAP)
        s_pref = jnp.minimum(ne_excl, E_CAP)
        main = _monotone_expand(mapped, s_main, EW, nbits)
        pref = _monotone_expand(jnp.where(esc, ord("\\"), 0).astype(_I32),
                                s_pref, EW, nbits)
        esc_row = (main | pref).astype(_U8)

    def dmap(a):
        a = a.astype(_I32)
        ne_at = jnp.sum(esc_i * (iota < a[:, None]), axis=1)
        return a + ne_at

    return {"esc_row": esc_row, "esc_i": esc_i, "ne_total": ne_total,
            "bad_ctl": bad_ctl, "dmap": dmap, "valid": valid}


def assemble_rows(segs, esc_row, bank: bytes, ts_text, N: int, OW: int):
    """OR-accumulate the per-row segment table into the [N, OW] output.

    ``segs`` is a list of ``(src0 [N], seglen [N])`` in destination
    order; sources index the concatenated row ``escaped line ∥ constant
    bank ∥ timestamp text``.  Returns (acc, out_len).  The scan body
    compiles once (vs once per segment) while each step stays a handful
    of fused [N, OW] elementwise passes."""
    seg_src = jnp.stack([s for s, _ in segs])
    seg_len = jnp.stack([ln for _, ln in segs])
    seg_dst = jnp.cumsum(seg_len, axis=0) - seg_len
    out_len = seg_dst[-1] + seg_len[-1]

    const_row = jnp.asarray(np.frombuffer(bank, dtype=np.uint8))
    CB = len(bank)
    src2 = jnp.concatenate([
        esc_row,
        jnp.broadcast_to(const_row[None, :], (N, CB)),
        ts_text.astype(_U8),
    ], axis=1)
    if src2.shape[1] > OW:
        raise ValueError(f"source row {src2.shape[1]} exceeds OW {OW}")
    src2 = jnp.pad(src2, ((0, 0), (0, OW - src2.shape[1])))
    iow = jax.lax.broadcasted_iota(_I32, (N, OW), 1)

    def step(a, xs):
        src0, seglen, dst0 = xs
        m = (iow >= src0[:, None]) & (iow < (src0 + seglen)[:, None])
        contrib = jnp.where(m, src2, jnp.uint8(0))
        return a | _rot_rows(contrib, (dst0 - src0) % OW, OW), None

    acc, _ = jax.lax.scan(step, jnp.zeros((N, OW), dtype=_U8),
                          (seg_src, seg_len, seg_dst))
    return acc, out_len


@partial(jax.jit, static_argnames=("G",))
def _compact_kernel(acc, out_len, tier, *, G: int = COMPACT_G):
    """Row compaction on device: pack the tier rows' output bytes into a
    contiguous group-aligned buffer so the host fetches ~sum(out_len)
    bytes instead of the padded ``[N, OW]`` matrix.

    Rows are already left-aligned, so compaction is a pure left-shift of
    whole G-byte groups: row i's ``ceil(len/G)`` leading groups move to
    group offset ``base[i] = sum_j<i ceil(len_j/G)``.  The per-group
    shift ``i*(OW/G) - base[i]`` is row-constant and nondecreasing, and
    destinations are strictly increasing, so an LSB-first barrel shifter
    is collision-free: after applying bits 0..k, two valid groups a < b
    satisfy ``p_b - p_a = (b-a) - ((s_b&m)-(s_a&m)) >= (b-a)-(s_b-s_a)
    >= 1`` (low-bit differences never exceed the full difference when
    the high bits are monotone).  Non-tier and padding groups are zeroed
    and stay put (shift 0); moving groups OR over them harmlessly.

    Returns the flat byte buffer; the host slices the first
    ``sum(ceil(gated_len/G))*G`` bytes (it recomputes base from the
    fetched lengths with the same integer math)."""
    N, OW = acc.shape
    assert OW % G == 0
    ngr = OW // G
    gated = jnp.where(tier, out_len, 0)
    used = (gated + (G - 1)) // G                          # [N]
    base = jnp.cumsum(used) - used                         # exclusive
    gi = jax.lax.broadcasted_iota(_I32, (N, ngr), 1)
    row = jax.lax.broadcasted_iota(_I32, (N, ngr), 0)
    valid = gi < used[:, None]
    shift = jnp.where(valid, row * ngr - base[:, None], 0).reshape(-1)
    x = jnp.where(valid.reshape(-1)[:, None], acc.reshape(N * ngr, G),
                  jnp.uint8(0))
    s = shift
    T = N * ngr
    for k in range(max(T - 1, 1).bit_length()):
        d = 1 << k
        if d >= T:
            break
        mv = ((s >> k) & 1) == 1
        xm = jnp.where(mv[:, None], x, jnp.uint8(0))
        sm = jnp.where(mv, s - d, 0)
        x = jnp.where(mv[:, None], jnp.uint8(0), x)
        s = jnp.where(mv, 0, s)
        x = x | jnp.concatenate(
            [xm[d:], jnp.zeros((d, G), jnp.uint8)], axis=0)
        s = s + jnp.concatenate(
            [sm[d:], jnp.zeros((d,), s.dtype)], axis=0)
    return x.reshape(-1)


def splice_rows(body: np.ndarray, row_off: np.ndarray,
                ins_src: np.ndarray, ins_at: np.ndarray,
                ins_a: np.ndarray, ins_l: np.ndarray):
    """Generic per-row insertion splice for constant/computed elision.

    Every row gets K insertions: insertion k of row r takes
    ``ins_l[r, k]`` bytes from ``ins_src`` at offset ``ins_a[r, k]`` and
    lands at body-relative offset ``ins_at[r, k]`` (offsets ascending
    per row, measured in the elided body's coordinates).  One segment
    gather (2K+1 segments/row, native concat when available) rebuilds
    the full rows.  ``splice_elided_rows`` is the fixed
    head/ts-label/tail specialization; the →RFC5424/→LTSV/→capnp routes
    use this one because their elided constants sit at row-dependent
    offsets (mid-row gaps, per-row PRI digits, computed capnp headers).
    Returns (full body, full row_off)."""
    from .assemble import concat_segments, exclusive_cumsum

    R = row_off.size - 1
    K = ins_at.shape[1]
    lens = np.diff(row_off).astype(np.int64)
    B = int(np.asarray(body).size)
    src = np.concatenate([np.asarray(body, dtype=np.uint8),
                          np.asarray(ins_src, dtype=np.uint8)])
    seg_src = np.empty((R, 2 * K + 1), dtype=np.int64)
    seg_len = np.empty((R, 2 * K + 1), dtype=np.int64)
    r0 = row_off[:-1].astype(np.int64)
    prev = np.zeros(R, dtype=np.int64)
    for k in range(K):
        at = np.minimum(np.asarray(ins_at[:, k], dtype=np.int64), lens)
        seg_src[:, 2 * k] = r0 + prev
        seg_len[:, 2 * k] = np.maximum(at - prev, 0)
        seg_src[:, 2 * k + 1] = B + np.asarray(ins_a[:, k], dtype=np.int64)
        seg_len[:, 2 * k + 1] = np.asarray(ins_l[:, k], dtype=np.int64)
        prev = np.maximum(at, prev)
    seg_src[:, 2 * K] = r0 + prev
    seg_len[:, 2 * K] = lens - prev
    out = concat_segments(src, seg_src.ravel(), seg_len.ravel())
    new_lens = lens + np.asarray(ins_l, dtype=np.int64).sum(axis=1)
    return out, exclusive_cumsum(new_lens)


def splice_elided_rows(body: np.ndarray, row_off: np.ndarray,
                       ts_lens: np.ndarray, head: bytes, ts_label: bytes,
                       tail: bytes):
    """Rebuild full output rows from constant-elided device rows.

    Output compaction 2.0: the head constant, the timestamp-label
    constant, and the tail constant (+ framing suffix) are identical for
    every row and at host-computable positions — the head leads, the
    timestamp text is the row's final ``ts_lens[i]`` bytes, the tail
    trails — so the kernel skips assembling them and the D2H transfer
    ships only the variable bytes.  This splice restores the exact
    host-tier bytes with one segment gather (5 segments/row, native
    concat when available).  Returns (full body, full row_off)."""
    from .assemble import concat_segments, exclusive_cumsum

    R = row_off.size - 1
    lens = np.diff(row_off).astype(np.int64)
    deco = np.frombuffer(head + ts_label + tail, dtype=np.uint8)
    src = np.concatenate([np.asarray(body, dtype=np.uint8), deco])
    B = int(np.asarray(body).size)
    h, lb, tl = len(head), len(ts_label), len(tail)
    ts = np.asarray(ts_lens, dtype=np.int64)
    pre = lens - ts  # variable bytes before the timestamp text
    seg_src = np.stack([
        np.full(R, B, dtype=np.int64),
        row_off[:-1].astype(np.int64),
        np.full(R, B + h, dtype=np.int64),
        row_off[:-1].astype(np.int64) + pre,
        np.full(R, B + h + lb, dtype=np.int64),
    ], axis=1).ravel()
    seg_len = np.stack([
        np.full(R, h, dtype=np.int64), pre,
        np.full(R, lb, dtype=np.int64), ts,
        np.full(R, tl, dtype=np.int64),
    ], axis=1).ravel()
    out = concat_segments(src, seg_src, seg_len)
    return out, exclusive_cumsum(lens + h + lb + tl)


def ts_text_block(small: Dict[str, np.ndarray], ts_vals_fn=None,
                  render=None):
    """Format per-row timestamp digits host-side.  The native threaded
    formatter (fg_format_f64_json: to_chars shortest round-trip,
    json_f64 notation — differentially fuzzed in
    tests/test_native_and_chunks.py) handles near-unique real-stream
    stamps at full rate; without the library, fall back to dedup +
    per-unique json_f64 (only fast for repetitive streams).

    ``ts_vals_fn(small, ok_mask) -> float64 array`` overrides the
    default days/sod/off/nanos combine for formats whose device tier
    carries other timestamp channels (ltsv float spans).

    ``render(val) -> bytes`` overrides the json_f64 notation for
    output formats whose timestamp text is not serde_json's — the
    →RFC5424 routes' rfc3339-ms form, the →LTSV routes' Rust Display
    form, the →capnp route's raw little-endian f64 words — via the
    dedup path (those routes' stamps are either repetitive or cheap)."""
    from .. import native
    from ..utils.rustfmt import json_f64

    okh = small["ok"].astype(bool)
    if ts_vals_fn is not None:
        ts_vals = ts_vals_fn(small, okh)
    else:
        masked = {k: np.where(okh, small[k], 0)
                  for k in ("days", "sod", "off", "nanos")}
        ts_vals = compute_ts(masked)
    if render is None:
        res = native.format_f64_json_native(ts_vals, TS_W)
        if res is not None:
            return res

        def render(val):
            return json_f64(float(val)).encode("ascii")
    uniq, inv = np.unique(ts_vals, return_inverse=True)
    txt = np.zeros((uniq.size, TS_W), dtype=np.uint8)
    ulen = np.zeros(uniq.size, dtype=np.int32)
    for u, val in enumerate(uniq):
        s = render(float(val))[:TS_W]
        txt[u, :len(s)] = np.frombuffer(s, dtype=np.uint8)
        ulen[u] = len(s)
    return txt[inv], ulen[inv]


def build_bank(parts: Dict[str, bytes], suffix: bytes):
    """Concatenate a device encoder's segment constants into one bank
    (the framing suffix rides the tail constant); returns
    (bank_bytes, {name: offset})."""
    offs, bank = {}, b""
    for k, v in parts.items():
        if k == "tail":
            v = v + suffix
        offs[k] = len(bank)
        bank += v
    return bank, offs


_AMBIG_LEN = 8     # name-key bytes captured for sorting
_BIG = 0x7FFFFFFF  # sort key for absent pairs (names are ASCII < 0x7f)

# optimal 12-comparator sorting network for 6 elements
_NET6 = ((0, 5), (1, 3), (2, 4), (1, 2), (3, 4), (0, 3), (2, 5),
         (0, 1), (2, 3), (4, 5), (1, 2), (3, 4))


@lru_cache(maxsize=None)
def _sort_network(n: int):
    """Comparator list sorting ``n`` elements: the hand-tuned
    12-comparator network for the common 6-pair tier, Batcher
    odd-even mergesort for any other width (63 comparators at n=16 —
    the wide tier that keeps 7..16-pair streams on-device)."""
    if n == 6:
        return _NET6
    pairs = []
    p = 1
    while p < n:
        k = p
        while k >= 1:
            j = k % p
            while j <= n - 1 - k:
                for i in range(0, min(k, n - j - k)):
                    if (i + j) // (p * 2) == (i + j + k) // (p * 2):
                        pairs.append((i + j, i + j + k))
                j += 2 * k
            k //= 2
        p *= 2
    return tuple(pairs)


def sort_pairs_by_key8(bb, iota, cols, max_pairs: int, slot_valid=None):
    """Sort per-pair span columns by their names' first 8 bytes
    (serde_json BTreeMap order) with a 12-comparator network, and flag
    rows whose order the 8-byte prefix cannot decide.

    ``cols`` must carry lists keyed ``ns``/``ne`` (raw name spans used
    for the keys) plus any payload lists to ride the swaps; this adds
    ``hi``/``lo``/``nlen`` key lists, sorts everything in place, and
    returns the ambig mask: equal 8-byte prefixes are orderable only
    when exactly one name is ≤8 bytes (a strict prefix of the other) —
    equal-length or both-longer pairs (including duplicates, dict
    last-wins semantics) fall back to the host tiers.

    Slots are normally pre-compacted (valid pairs first, ``_pair_count``
    gating); ``slot_valid`` (per-slot [N] bool list) instead marks valid
    slots in place — invalid ones key to _BIG and the sort itself
    compacts them to the tail, saving callers the O(F^2) where-chain
    compaction (device_gelf_gelf feeds raw field order this way)."""
    import jax.numpy as jnp

    N = bb.shape[0]
    pair_count = cols.pop("_pair_count")
    cols["hi"], cols["lo"], cols["nlen"] = [], [], []
    for p in range(max_pairs):
        ns_r = cols["ns_raw"][p]
        ne_r = cols["ne_raw"][p]
        pv = (p < pair_count) if slot_valid is None else slot_valid[p]
        r = iota - ns_r[:, None]
        in_name = (r >= 0) & (iota < ne_r[:, None])
        z = jnp.where(in_name, bb, 0)
        hi = jnp.sum(z * ((r == 0) * (1 << 24) + (r == 1) * (1 << 16)
                          + (r == 2) * (1 << 8) + (r == 3)), axis=1)
        lo = jnp.sum(z * ((r == 4) * (1 << 24) + (r == 5) * (1 << 16)
                          + (r == 6) * (1 << 8) + (r == 7)), axis=1)
        cols["hi"].append(jnp.where(pv, hi, _BIG))
        cols["lo"].append(jnp.where(pv, lo, _BIG))
        cols["nlen"].append(jnp.where(pv, ne_r - ns_r, _BIG))

    payload = [k for k in cols if k not in ("hi", "lo", "nlen")]
    for i, j in _sort_network(max_pairs):
        ah, bh = cols["hi"][i], cols["hi"][j]
        al, bl = cols["lo"][i], cols["lo"][j]
        an, bn = cols["nlen"][i], cols["nlen"][j]
        swap = (bh < ah) | ((bh == ah) & ((bl < al)
                            | ((bl == al) & (bn < an))))
        for key in ("hi", "lo", "nlen", *payload):
            a, b = cols[key][i], cols[key][j]
            cols[key][i] = jnp.where(swap, b, a)
            cols[key][j] = jnp.where(swap, a, b)

    ambig = jnp.zeros((N,), dtype=bool)
    for p in range(max_pairs - 1):
        keq = ((cols["hi"][p] == cols["hi"][p + 1])
               & (cols["lo"][p] == cols["lo"][p + 1])
               & (cols["hi"][p] != _BIG))
        la, lb = cols["nlen"][p], cols["nlen"][p + 1]
        ambig |= keq & ((la == lb) | ((la > _AMBIG_LEN)
                                      & (lb > _AMBIG_LEN)))
    return ambig


def gelf_route_ok(encoder, merger, extras_placeable) -> bool:
    """Shared applicability predicate for the device GELF-encode routes:
    GELF output over line/nul/syslen framing, with the kill switch and
    merger allowlist in ONE place; ``extras_placeable(extra) -> bool``
    is the per-layout static-placement check."""
    import os

    from ..encoders.gelf import GelfEncoder
    from ..mergers import LineMerger, NulMerger, SyslenMerger

    if os.environ.get("FLOWGGER_DEVICE_ENCODE", "1") == "0":
        return False
    if type(encoder) is not GelfEncoder:
        return False
    if encoder.extra and not extras_placeable(encoder.extra):
        return False
    return merger is None or type(merger) in (LineMerger, NulMerger,
                                              SyslenMerger)


def encode_route_ok(encoder, merger, enc_cls) -> bool:
    """Applicability predicate shared by the non-GELF device encode
    routes (→RFC5424 / →LTSV / →capnp): exact encoder type over
    line/nul/syslen framing, honoring the same kill switch as the GELF
    legs.  Their extras are always statically placeable (LTSV/capnp
    extras render to one constant blob, RFC5424 has none), so unlike
    ``gelf_route_ok`` there is no placement check."""
    import os

    from ..mergers import LineMerger, NulMerger, SyslenMerger

    if os.environ.get("FLOWGGER_DEVICE_ENCODE", "1") == "0":
        return False
    if type(encoder) is not enc_cls:
        return False
    return merger is None or type(merger) in (LineMerger, NulMerger,
                                              SyslenMerger)


def fetch_encode_driver(kernel, out, batch_dev, lens_dev, packed, encoder,
                        merger, route_state, suffix: bytes, syslen: bool,
                        scalar_fn, fallback_frac: float,
                        decline_limit: int, cooldown: int,
                        ts_keys=("days", "sod", "off", "nanos"),
                        ts_vals_fn=None, ts_render=None, wide=None,
                        elide=None, kname_prefix=None,
                        compile_timeout_s=None, route_label=None,
                        small_fetch_fn=None, fused_counters=True):
    """Shared fetch flow for every device-encode format:

    1. phase-1 tier probe (``kernel(..., assemble=False)`` — XLA
       dead-code-eliminates the assembly) with a pessimistic TS_W
       timestamp width, so persistently declining streams never pay the
       assembly or the host timestamp formatting;
    2. decline hysteresis via ``route_state`` (caller-owned dict);
    3. timestamp text upload (native formatter), full kernel;
    4. on-device row compaction when it saves >15% of the fetch, with
       row lengths fetched as u16 and the uncompacted fallback trimmed
       on device to the batch's real row count and max row length;
    5. constant elision (``elide=(head, ts_label, tail)``): the kernel
       skipped those row-constant segments, the splice restores them
       host-side, and the D2H ships only variable bytes — the step that
       brings fetched bytes/row at or under emitted bytes/row;
    6. syslen prefixing (host splice over the output-sized body);
    7. fallback splicing through ``finish_block``.

    ``kname_prefix`` overrides the compile-watchdog slot namespace (the
    fused-route closures all live in one module — without it two routes
    at the same shape would share a slot and mask each other's pending
    compiles); ``compile_timeout_s`` overrides the watchdog deadline for
    every guarded call in this flow; ``route_label`` exports per-route
    ``fetch_bytes_per_row_{label}`` / ``emit_bytes_per_row_{label}``
    gauges, plus the ``fused_rows`` counters unless
    ``fused_counters=False`` (split-tier callers share a logical
    route's gauges without claiming its rows as fused).

    Returns (BlockResult | None, fetch_seconds); None = caller should
    use the span-fetch host path."""
    import time as _time

    from ..utils.metrics import registry as _metrics
    from .block_common import apply_syslen_prefix, finish_block

    batch, lens, chunk, starts, orig_lens, n_real = packed
    n = int(n_real)
    N = batch_dev.shape[0]

    if route_state is not None and route_state.get("cooldown", 0) > 0:
        route_state["cooldown"] -= 1
        return None, 0.0

    t_fetch = 0.0
    fetched = [0]

    def _fetch(arr):
        nonlocal t_fetch
        t0 = _time.perf_counter()
        h = d2h(arr)
        t_fetch += _time.perf_counter() - t0
        fetched[0] += h.nbytes
        return h

    empty_ts = jnp.zeros((N, 0), dtype=jnp.uint8)
    full_ts_len = jnp.full((N,), TS_W, dtype=jnp.int32)

    def probe(k):
        """Phase-1 tier probe.  A kernel may return a dict — ``tier``
        plus extra device channels (e.g. gelf→GELF's timestamp parse,
        which only exists encode-side); the extras merge into ``out``
        so the ts fetch below sees them like decode outputs."""
        t1 = k(empty_ts, full_ts_len, False)
        if isinstance(t1, dict):
            extra = {k2: v for k2, v in t1.items() if k2 != "tier"}
            return t1["tier"], extra
        return t1, None

    # compile-watchdog slot names: stable per kernel module + shape +
    # device (closures are rebuilt per batch; the jit cache underneath
    # is not; lane dispatch compiles one executable per device, so each
    # lane's compile needs its own watchdog slot)
    _devs = getattr(batch_dev, "devices", None)  # host arrays have none
    _dev = (",".join(sorted(str(d) for d in _devs())) if _devs
            else "default")
    kname = (f"{kname_prefix or getattr(kernel, '__module__', 'device')}:"
             f"{tuple(batch_dev.shape)}:{_dev}")

    def _guarded(slot, fn, *args):
        return guarded_compile_call(slot, fn, *args,
                                    timeout_s=compile_timeout_s)

    def _declined_compile():
        if route_state is not None:
            route_state["cooldown"] = cooldown
        return None, t_fetch

    wide_adopted = False
    try:
        tier1, extra1 = _guarded(f"{kname}:probe", probe, kernel)
    except CompileTimeout:
        return _declined_compile()
    if extra1:
        out = {**out, **extra1}
    tier1_np = _fetch(tier1)[:n]

    starts64 = np.asarray(starts[:n], dtype=np.int64)
    lens64 = np.asarray(orig_lens[:n], dtype=np.int64)
    max_len = batch.shape[1]
    cand1 = tier1_np & (lens64 <= max_len)

    # pair-budget escalation: when the base-width tier declines (e.g. a
    # 7+-pair stream) and the format has a wide kernel (the encode-side
    # analog of decode's 16-pair rescue), probe it before giving the
    # batch to the host path; wide batches pay the bigger sort network
    # and segment table only when the base width actually failed.  A
    # failed wide probe sets its own cooldown so streams declining for
    # non-pair reasons (escapes, bad stamps) don't pay a futile second
    # decode + probe every batch.
    if (n and wide is not None
            and (1.0 - cand1.mean()) > fallback_frac):
        wide_cd = 0 if route_state is None else \
            route_state.get("wide_cooldown", 0)
        if wide_cd > 0:
            route_state["wide_cooldown"] = wide_cd - 1
        else:
            out_w, kernel_w = wide()
            try:
                tier1w, extraw = _guarded(
                    f"{kname}:probe-wide", probe, kernel_w)
            except CompileTimeout:
                tier1w = None
            if tier1w is None:
                if route_state is not None:
                    route_state["wide_cooldown"] = cooldown
            else:
                cand1w = _fetch(tier1w)[:n] & (lens64 <= max_len)
                if (1.0 - cand1w.mean()) <= fallback_frac:
                    _metrics.inc("device_encode_wide_batches")
                    kernel, out, cand1 = kernel_w, out_w, cand1w
                    wide_adopted = True
                    if extraw:
                        out = {**out, **extraw}
                elif route_state is not None:
                    route_state["wide_cooldown"] = cooldown

    if n and (1.0 - cand1.mean()) > fallback_frac:
        _metrics.inc("device_encode_declined")
        _metrics.inc("device_encode_fetch_bytes", fetched[0])
        if route_state is not None:
            route_state["declines"] = route_state.get("declines", 0) + 1
            if route_state["declines"] >= decline_limit:
                route_state["cooldown"] = cooldown
                route_state["declines"] = 0
        return None, t_fetch
    if route_state is not None:
        route_state["declines"] = 0

    if small_fetch_fn is not None:
        # route-provided small-channel fetch (fused ltsv): narrowed
        # dtypes and kind-conditional channel skips keep the fixed
        # per-row D2H overhead under the elided-constant savings
        small = small_fetch_fn(out, _fetch)
    else:
        small = {k: _fetch(out[k]) for k in ("ok",) + tuple(ts_keys)}
    # only phase-1 candidates get host timestamp formatting (ADVICE r4):
    # tier-rejected rows (e.g. LTSV float-stamp rows) may hold garbage
    # days/sod and their text is discarded anyway.  Phase-2 acceptance
    # is intersected with cand1 below so a non-candidate can never ride
    # the device tier with the placeholder text.
    cand1_full = np.zeros(small["ok"].shape[0], dtype=bool)
    cand1_full[:n] = cand1
    small["ok"] = small["ok"].astype(bool) & cand1_full
    ts_text, ts_len = ts_text_block(small, ts_vals_fn, render=ts_render)
    # wide kernels get their own watchdog slot: the narrow assemble
    # being warm says nothing about the (bigger) wide compile
    asm_slot = f"{kname}:assemble-wide" if wide_adopted else \
        f"{kname}:assemble"
    try:
        acc, out_len, tier = _guarded(
            asm_slot, kernel, jnp.asarray(ts_text),
            jnp.asarray(ts_len), True)
    except CompileTimeout:
        return _declined_compile()

    # full-N fetches (tiny): the host must recompute the compaction
    # layout with the exact integer math the device used, including any
    # dp-padding rows beyond n.  Lengths ride D2H as u16 (they are
    # bounded by OW) — half the width of the old i32 fetch.
    N_acc, OW = acc.shape
    tier_full = _fetch(tier)
    len_full = _fetch(out_len.astype(jnp.uint16) if OW <= 0xFFFF
                      else out_len).astype(np.int64)
    tier_np = tier_full[:n]
    len_np = len_full[:n]

    # the real (shorter) timestamp text can only widen the tier vs the
    # pessimistic phase-1 gate, but rows outside cand1 carry placeholder
    # ts text (masked above), so the decision set is the intersection
    cand = tier_np & cand1
    ridx = np.flatnonzero(cand)

    G = COMPACT_G
    gated = np.where(tier_full, len_full, 0)
    total_bytes = int(gated.sum())
    flat = None
    if (total_bytes and ridx.size
            and N_acc * OW > total_bytes * COMPACT_MIN_SAVING):
        # device-side row compaction: D2H ≈ sum(out_len), G-aligned
        try:
            flat = _guarded(
                f"{kname}:compact-wide" if wide_adopted
                else f"{kname}:compact", _compact_kernel, acc, out_len, tier)
        except CompileTimeout:
            flat = None  # trimmed-width fetch below until the compile lands
    if flat is not None:
        used = (gated + (G - 1)) // G
        base = np.cumsum(used) - used
        total_groups = int(used.sum())
        comp = _fetch(flat[: total_groups * G]).reshape(-1, G)
        u = used[ridx]
        ucum = np.cumsum(u) - u
        pos = np.arange(int(u.sum()), dtype=np.int64) - np.repeat(ucum, u)
        gidx = np.repeat(base[ridx], u) + pos
        gv = np.minimum(G, np.repeat(len_np[ridx], u) - pos * G)
        grp = comp[gidx]
        body = grp[np.arange(G)[None, :] < gv[:, None]]
        row_off = exclusive_cumsum(len_np[ridx])
        _metrics.inc("fetch_bytes_saved",
                     max(0, N_acc * OW - total_groups * G))
    elif ridx.size:
        # compaction skipped (or its compile pending): still trim the
        # fetched matrix on device to the real row count and the batch's
        # max gated row length instead of shipping the padded [N, OW].
        # maxw quantizes up to 128 so the slice program count stays
        # bounded, and the slice itself runs under the compile watchdog
        # (a data-dependent shape is a fresh XLA program; on a hung
        # compile the plain full-matrix transfer below cannot stall —
        # it is a pure copy of an existing buffer)
        maxw = min(OW, -(-max(int(gated[:n].max()), 1) // 128) * 128)
        try:
            trimmed = _guarded(
                f"{kname}:trim:{maxw}", lambda: acc[:n, :maxw])
        except CompileTimeout:
            trimmed = None
        if trimmed is not None:
            out_np = _fetch(trimmed)
            _metrics.inc("fetch_bytes_saved",
                         max(0, N_acc * OW - n * maxw))
        else:
            out_np = _fetch(acc)[:n]
        rows = out_np[ridx]
        m = np.arange(rows.shape[1])[None, :] < len_np[ridx, None]
        body = rows[m]
        row_off = exclusive_cumsum(len_np[ridx])
    else:
        body = np.zeros(0, dtype=np.uint8)
        row_off = np.zeros(1, dtype=np.int64)

    if elide is not None and ridx.size:
        # restore the head / timestamp-label / tail constants the kernel
        # left out of the transfer (byte-identical by construction); a
        # callable elide owns the whole splice — the →RFC5424/→LTSV/
        # →capnp routes' elided segments carry row-dependent bytes (PRI
        # digits, computed capnp headers) or sit at mid-row offsets
        if callable(elide):
            body, row_off = elide(
                body, row_off, small, np.asarray(ts_text),
                np.asarray(ts_len, dtype=np.int64), ridx)
        else:
            body, row_off = splice_elided_rows(
                body, row_off, np.asarray(ts_len, dtype=np.int64)[ridx],
                *elide)

    prefix_lens_tier = None
    if syslen and ridx.size:
        final_buf, row_off, prefix_lens_tier = apply_syslen_prefix(
            body, row_off, np.diff(row_off))
    else:
        final_buf = body.tobytes()

    _metrics.inc("device_encode_rows", int(ridx.size))
    _metrics.inc("device_encode_scalar_rows", int(n - ridx.size))
    _metrics.inc("device_encode_fetch_bytes", fetched[0])
    _metrics.inc("device_encode_out_bytes", len(final_buf))
    if route_label is not None:
        if fused_counters:
            _metrics.inc("fused_rows", int(ridx.size))
            _metrics.inc(f"fused_rows_{route_label}", int(ridx.size))
        if ridx.size:
            # ONE denominator for both gauges (tier rows): dividing
            # fetch by all n rows diluted it whenever fallback rows
            # existed, reporting fetch<emit even when per-tier-row
            # fetch exceeded emit.  Tier-row fetch is the conservative
            # reading — the batch-wide small fetches are all charged to
            # the tier rows.
            _metrics.set_gauge(f"fetch_bytes_per_row_{route_label}",
                               round(fetched[0] / int(ridx.size), 1))
            # tier-row emitted width (splice constants included), the
            # number the fetch gauge must stay under
            _metrics.set_gauge(
                f"emit_bytes_per_row_{route_label}",
                round(float(row_off[-1]) / int(ridx.size), 1))
    res = finish_block(chunk, starts64, lens64, n, cand, ridx, final_buf,
                       row_off, prefix_lens_tier, suffix, syslen, merger,
                       encoder, scalar_fn=scalar_fn, max_len=max_len)
    return res, t_fetch
