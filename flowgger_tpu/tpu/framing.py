"""Device-resident framing: ParPaRaw-style delimiter-parallel record
splitting over raw transport regions (arxiv 1905.13415).

Every device route used to start *after* the host did the slow part:
per-connection splitter threads found record boundaries byte-by-byte
and ``pack.py`` copied each line into the padded arena before a kernel
ever saw data — and the overlap-executor measurements showed those host
stages dominating wall time.  ParPaRaw's observation is that framing
itself is massively parallel: delimiter detection over a raw buffer is
a byte-classification plane plus a prefix sum, exactly the machinery
``tpu/jsonidx.py`` already runs *inside* the decode kernels (simdjson
stage 1, arxiv 1902.08318).  This module lifts it in front of them:

- **stage A (spans)** — ``frame_sep_spans_jit`` (line/nul framing):
  delimiter cumsum over the region + packed-ordinal scatter extraction
  of each record's end; CR strip is an elementwise lookback.
  ``frame_syslen_spans_jit`` (RFC5425 octet counting): the digit-prefix
  *value* at every position comes from a right-to-left weighted suffix
  sum (exact in wrapping int32 arithmetic — each frame's window sum is
  < 1e9, so the mod-2^32 difference of two wrapped cumsum samples is
  the true value), and the data-dependent frame *chain* from offset 0
  resolves with pointer doubling (log2(B) scatter/gather hops) — the
  parallel-scan shape ParPaRaw uses for its escape/quote automata.
- **stage B (pack)** — ``frame_gather_jit``: one [rows, max_len]
  gather from the device-resident region replaces the host arena
  memcpy; the batch never exists host-side.  Only the span *metadata*
  (two i32 vectors, 8 bytes/row) crosses D2H — the block encoders
  splice oversized/fallback rows from the raw region bytes the host
  already owns, exactly like the decode fallback path.

The host-side contract is byte identity with the host splitters
(``pack.split_chunk`` for line/nul, ``splitters._scan_syslen_region``
for syslen): same records, same order, across arbitrary chunk
boundaries.  Anything the kernels cannot express exactly (a syslen
length prefix over 9 digits, span-count overflow) declines the whole
region to the host path — never a divergent answer.

Decline ladder: the first compile per (bytes, rows) shape runs under
the production watchdog (slot ``framing/<framing>``); a timeout or any
device error falls back to the host splitter for that flush (the raw
bytes are still on the host, so no record is ever lost), feeding the
breaker like a decode failure.  ``FramingEconomics`` mirrors
RouteEconomics for the framing-vs-host-pack arm: the device tier
probes first, a slow-measuring one buys host-pack comparison batches,
and the loser re-probes periodically.
"""

from __future__ import annotations

import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import events as _events
from ..utils.metrics import registry as _metrics

SCALAR_ORACLE = "flowgger_tpu.tpu.pack:split_chunk"
DIFF_TEST = (
    "tests/test_framing.py::test_frame_sep_spans_match_host_split",
    "tests/test_framing.py::test_frame_syslen_spans_match_host_scan",
    "tests/test_framing.py::test_raw_ingest_byte_identity_all_framings",
)

_I32 = jnp.int32
# numpy scalar, NOT jnp.int32(...): materializing a device scalar at
# import time costs a jit(convert_element_type) compile in every fresh
# process — the one fresh compile that broke the zero-JIT artifact
# boot's compile_cache_misses == 0 gate (inside traced code a numpy
# int32 scalar folds in identically)
_BIG = np.int32(1 << 30)

# region byte floor (mirrors pack._MIN_BYTES) and the syslen digit-run
# cap the exact-int32 value parse supports; longer prefixes decline the
# region to the host scan, which owns the > 2^31-1 error semantics
MIN_REGION_BYTES = 1 << 14
MAX_PREFIX_DIGITS = 9

# decline hysteresis (same shape as the fused tier's): this many
# watchdog declines in a row put the framing tier on a cooldown of
# host-framed flushes before the next probe
DECLINE_LIMIT = 3
COOLDOWN = 32

_POW10 = tuple(10 ** i for i in range(MAX_PREFIX_DIGITS))


class FramingDeclined(Exception):
    """The device framing tier declined this region (compile watchdog,
    span overflow, or an inexpressible syslen prefix); the caller must
    re-frame on the host path — same bytes, no records lost."""


def region_bucket(nbytes: int) -> int:
    """Padded device size for a raw region: next power of two with a
    floor, so steady-state traffic hits a handful of compiled shapes
    (the same amortization argument as pack's row bucketing)."""
    b = MIN_REGION_BYTES
    while b < nbytes:
        b <<= 1
    return b


def syslen_hops(nbytes: int) -> int:
    """Pointer-doubling iterations that cover every chain in a region
    of ``nbytes``: frame starts strictly increase, so ceil(log2(B+1))
    hops reach any frame head."""
    return max(1, int(nbytes + 1).bit_length())


# ---------------------------------------------------------------------------
# stage A: span kernels
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sep", "strip_cr", "ncap"))
def frame_sep_spans_jit(region, rlen, sep: int = 10,
                        strip_cr: bool = True, ncap: int = 256):
    """Separator framing spans over ``region[:rlen]`` (u8 [B]).

    Returns starts/lens (orig, CR-stripped) [ncap], n, consumed (one
    past the last separator) and an overflow flag (n > ncap — the
    caller sized ncap from its exact host-side separator count, so
    overflow only means the caller must decline to the host path).
    """
    B = region.shape[0]
    idx = jnp.arange(B, dtype=_I32)
    valid = idx < rlen
    is_sep = (region == jnp.uint8(sep)) & valid
    ordc = jnp.cumsum(is_sep.astype(_I32))
    n = ordc[-1]
    # packed-ordinal extraction: the k-th separator's position scatters
    # into slot k (each ordinal hit exactly once; extras dump past ncap)
    slot = jnp.where(is_sep, jnp.minimum(ordc - 1, ncap), ncap)
    ends = jnp.zeros(ncap + 1, _I32).at[slot].add(
        jnp.where(is_sep, idx, 0))[:ncap]
    k = jnp.arange(ncap, dtype=_I32)
    live = k < n
    prev_end = jnp.concatenate([jnp.full((1,), -1, _I32), ends[:-1]])
    starts = jnp.where(live, prev_end + 1, 0)
    lens = ends - starts
    if strip_cr:
        before = region[jnp.clip(ends - 1, 0, B - 1)]
        has_cr = live & (lens > 0) & (before == jnp.uint8(13))
        lens = lens - has_cr.astype(_I32)
    lens = jnp.where(live, lens, 0)
    consumed = jnp.where(
        n > 0, ends[jnp.clip(n - 1, 0, ncap - 1)] + 1, 0)
    return {"starts": starts, "lens": lens, "n": n,
            "consumed": consumed, "overflow": n > ncap}


@functools.partial(jax.jit, static_argnames=("ncap", "max_hops"))
def frame_syslen_spans_jit(region, rlen, ncap: int = 256,
                           max_hops: int = 15):
    """RFC5425 octet-count framing spans over ``region[:rlen]``.

    Mirrors ``splitters._scan_syslen_region``: frames are
    ``<decimal> <body>`` back to back from offset 0; the scan stops at
    the first incomplete frame (consumed = its start) and ``err`` is
    set when the stop position holds a malformed prefix (a space is
    reachable but the bytes before it are not all digits, or the
    prefix is empty).  ``decline`` flags a reachable prefix longer
    than MAX_PREFIX_DIGITS digits (or span overflow): the value could
    exceed what the int32 parse expresses, so the caller re-frames the
    region on the host, which owns those exact error semantics.
    """
    B = region.shape[0]
    idx = jnp.arange(B, dtype=_I32)
    valid = idx < rlen
    bi = region.astype(_I32)
    is_digit = (bi >= 48) & (bi <= 57) & valid
    is_space = (bi == 32) & valid
    # next space / next non-digit at-or-after each position (reverse
    # cummin lookaheads; positions at/past rlen act as non-digits)
    sp = jax.lax.cummin(jnp.where(is_space, idx, _BIG), axis=0,
                        reverse=True)
    nd = jax.lax.cummin(
        jnp.where(is_digit, _BIG, jnp.minimum(idx, rlen)), axis=0,
        reverse=True)
    has_space = sp < rlen
    prefix_ok = has_space & (nd == sp) & (sp > idx)
    run = jnp.where(prefix_ok, sp - idx, 0)
    too_long = prefix_ok & (run > MAX_PREFIX_DIGITS)
    # digit-prefix value at every position: weight each digit by
    # 10^(distance to its run's space), then difference a right-to-left
    # cumsum.  The full-buffer cumsum may wrap int32, but each frame's
    # window sum is < 1e9, so the wrapped difference is exact.
    exp = jnp.clip(sp - 1 - idx, 0, MAX_PREFIX_DIGITS - 1)
    pow10 = jnp.asarray(_POW10, dtype=_I32)
    w = jnp.where(is_digit & has_space, (bi - 48) * pow10[exp], 0)
    suf = jnp.cumsum(w[::-1])[::-1]
    suf_ext = jnp.concatenate([suf, jnp.zeros(1, _I32)])
    val = suf - suf_ext[jnp.clip(sp, 0, B)]
    body = sp + 1
    nxt = body + val
    frame_ok = prefix_ok & ~too_long & (nxt <= rlen)
    # the frame chain from offset 0, resolved by pointer doubling:
    # jump[p] = next frame start (sentinel B when p heads no complete
    # frame); each hop both propagates the reached set one jump and
    # doubles the jump table, so max_hops = ceil(log2(B+1)) suffices
    jump = jnp.concatenate(
        [jnp.where(frame_ok, jnp.clip(nxt, 0, B), B),
         jnp.full((1,), B, _I32)])
    reach = jnp.zeros(B + 1, bool).at[0].set(True)
    j = jump
    for _ in range(max_hops):
        reach = reach.at[jnp.where(reach, j, B)].max(reach)
        j = j[j]
    heads = reach[:B] & frame_ok
    ordc = jnp.cumsum(heads.astype(_I32))
    n = ordc[-1]
    slot = jnp.where(heads, jnp.minimum(ordc - 1, ncap), ncap)
    starts = jnp.zeros(ncap + 1, _I32).at[slot].add(
        jnp.where(heads, body, 0))[:ncap]
    lens = jnp.zeros(ncap + 1, _I32).at[slot].add(
        jnp.where(heads, val, 0))[:ncap]
    consumed = jnp.max(jnp.where(heads, jnp.clip(nxt, 0, B), 0))
    # error analysis at the chain stop, mirroring the host scan: a
    # reachable space with a non-digit (or empty) prefix before it
    stop = jnp.clip(consumed, 0, B - 1)
    sp_stop = sp[stop]
    nd_stop = nd[stop]
    bad_prefix = (sp_stop < rlen) & ((nd_stop != sp_stop)
                                     | (sp_stop == consumed))
    err = (consumed < rlen) & bad_prefix
    decline = jnp.any(reach[:B] & too_long) | (n > ncap)
    return {"starts": starts, "lens": lens, "n": n,
            "consumed": consumed, "err": err, "decline": decline}


# ---------------------------------------------------------------------------
# stage B: device pack (gather)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("max_len",))
def frame_gather_jit(region, starts, lens, max_len: int = 512):
    """Gather the framed records into a dense [rows, max_len] batch on
    device (the arena copy the host pack used to do), with lens clipped
    to max_len — oversized rows splice later from the host region bytes
    exactly like the decode fallback path."""
    col = jnp.arange(max_len, dtype=_I32)[None, :]
    lens_c = jnp.minimum(lens.astype(_I32), max_len)
    idx = starts.astype(_I32)[:, None] + col
    gathered = region[jnp.clip(idx, 0, region.shape[0] - 1)]
    batch = jnp.where(col < lens_c[:, None], gathered,
                      jnp.uint8(0)).astype(jnp.uint8)
    return batch, lens_c


# ---------------------------------------------------------------------------
# host wrapper: region bytes -> packed tuple
# ---------------------------------------------------------------------------

def _device_put2(arr, device):
    return jax.device_put(arr, device) if device is not None \
        else jnp.asarray(arr)


def _watchdogged(slot: str, fn):
    from .device_common import guarded_compile_call

    return guarded_compile_call(slot, fn)


def _aot_spans(framing: str, statics: dict, args):
    from . import aot

    return aot.framing_call(framing, args, statics)


def _aot_gather(statics: dict, args):
    from . import aot

    return aot.framing_call("gather", args, statics)


def device_frame_region(region: bytes, framing: str, max_len: int,
                        n_records: Optional[int] = None, device=None):
    """Frame one raw region on device and return
    ``(packed, consumed, err)`` with the exact ``pack_*_2d`` packed
    contract — (batch, clipped_lens, chunk, starts, orig_lens, n_real)
    — where batch/clipped_lens are *device-resident* arrays ready to
    chain straight into ``block_submit`` (and the fused programs) with
    no host arena copy.

    ``framing`` is ``line`` / ``nul`` / ``syslen``.  For line/nul the
    caller passes a region ending at its final separator plus the exact
    separator count ``n_records`` (one memchr-speed ``bytes.count``);
    for syslen the kernel itself finds ``consumed`` and ``err``.
    Raises FramingDeclined (compile watchdog, span overflow, or an
    inexpressible syslen prefix) — the caller re-frames on the host.
    Any other exception is a device failure for the breaker.
    """
    from . import pack as _pack
    from .device_common import CompileTimeout

    nbytes = len(region)
    B = region_bucket(nbytes)
    buf = np.zeros(B, dtype=np.uint8)
    if nbytes:
        buf[:nbytes] = np.frombuffer(region, dtype=np.uint8)
    region_dev = _device_put2(buf, device)
    rlen = _device_put2(np.int32(nbytes), device)
    dev_label = ",".join(sorted(str(d) for d in region_dev.devices()))

    from . import aot as _aot

    # for syslen the space count bounds the span-array width (frames <=
    # spaces: each frame's own delimiter is one); line/nul pass the
    # exact separator count.  Statics come from the ONE recipe the AOT
    # builder also uses (aot.framing_statics), so a loaded artifact and
    # this jit can never drift apart.
    ncap = _pack.bucket_rows(max(n_records or 1, 1))
    statics = _aot.framing_statics(framing, ncap, B)
    if framing == "syslen":
        kfn = lambda: frame_syslen_spans_jit(  # noqa: E731
            region_dev, rlen, **statics)
    else:
        kfn = lambda: frame_sep_spans_jit(  # noqa: E731
            region_dev, rlen, **statics)

    def stage_a():
        out = _aot_spans(framing, statics, (region_dev, rlen))
        if out is not None:
            return out
        return kfn()

    slot = f"framing/{framing}:{B}x{ncap}:{dev_label}"
    try:
        out = _watchdogged(slot, stage_a)
    except CompileTimeout:
        _metrics.inc("framing_declines")
        _events.emit("framing", "framing_decline", route=framing,
                     detail="compile watchdog")
        raise FramingDeclined("compile watchdog") from None
    spans = jax.device_get(out)
    n = int(spans["n"])
    consumed = int(spans["consumed"])
    err = bool(spans.get("err", False))
    if bool(spans.get("overflow", False)) or bool(spans.get("decline",
                                                            False)):
        _metrics.inc("framing_declines")
        _events.emit("framing", "framing_decline", route=framing,
                     detail="span overflow or oversized prefix",
                     cost=nbytes, cost_unit="region_bytes")
        raise FramingDeclined("span overflow or oversized prefix")
    # span metadata is the only D2H on this path: 2 x i32 per slot
    _metrics.inc("framing_span_fetch_bytes", 8 * ncap + 16)

    rows = _pack.bucket_rows(max(n, 1))
    starts_np = np.zeros(rows, dtype=np.int32)
    orig_lens = np.asarray(spans["lens"][:n], dtype=np.int32)
    starts_np[:n] = spans["starts"][:n]
    _pack._note_shape(rows, max_len)

    if rows == ncap and framing != "syslen":
        starts_dev, lens_dev = out["starts"], out["lens"]
    else:
        lens_p = np.zeros(rows, dtype=np.int32)
        lens_p[:n] = orig_lens
        starts_dev = _device_put2(starts_np, device)
        lens_dev = _device_put2(lens_p, device)

    g_statics = _aot.framing_statics("gather", max_len, B)

    def stage_b():
        res = _aot_gather(g_statics, (region_dev, starts_dev, lens_dev))
        if res is not None:
            return res
        return frame_gather_jit(region_dev, starts_dev, lens_dev,
                                max_len=max_len)

    gslot = f"framing/gather:{B}x{rows}x{max_len}:{dev_label}"
    try:
        batch_dev, lens_c_dev = _watchdogged(gslot, stage_b)
    except CompileTimeout:
        _metrics.inc("framing_declines")
        _events.emit("framing", "framing_decline", route=framing,
                     detail="compile watchdog (gather)")
        raise FramingDeclined("compile watchdog (gather)") from None
    _metrics.inc("framing_rows", n)
    _pack.note_overlen(orig_lens, max_len)
    packed = (batch_dev, lens_c_dev, region, starts_np, orig_lens, n)
    return packed, consumed, err


# ---------------------------------------------------------------------------
# framing-vs-host-pack economics
# ---------------------------------------------------------------------------

class FramingEconomics:
    """Measured seconds/row of the device framing stage vs the host
    split+pack it replaces; ``allow_framing()`` routes each flush to
    the cheaper one with periodic loser re-probes — the RouteEconomics
    pattern applied to the framing arm (on a real accelerator the
    device tier wins and nothing changes; on a CPU backend the native
    memcpy pack usually wins and the tier self-disables, visibly)."""

    MARGIN = 1.5
    ALPHA = 0.4
    OK_SPR = 1e-6  # ~1M rows/s framing needs no host comparison

    def __init__(self, enabled: bool = True, probe_every: int = 256):
        self.enabled = enabled
        self.probe_every = max(2, int(probe_every))
        self._lock = threading.Lock()
        self._spr = {"framing": None, "hostpack": None}
        self._batches = 0
        # journal bookkeeping: device framing is the probe-first
        # default, so the first measured re-route to the host pack (and
        # every flip back) is one economics_switch event
        self._winner = "framing"

    def allow_framing(self) -> bool:
        if not self.enabled:
            return True
        with self._lock:
            dev, host = self._spr["framing"], self._spr["hostpack"]
            self._batches += 1
            if dev is None:
                return True          # no framing sample yet: probe it
            if host is None:
                # healthy device framing never pays the host pack; a
                # slow-measuring one buys one comparison flush
                return dev <= self.OK_SPR
            probe = self._batches % self.probe_every == 0
            if dev > host * self.MARGIN:
                return probe         # framing losing: re-probe on schedule
            if host > dev * self.MARGIN:
                return not probe     # host losing: re-sample on schedule
            return True              # within noise: prefer the device tier

    def observe(self, path: str, rows: int, seconds: float) -> None:
        if not self.enabled or rows <= 0 or path not in self._spr:
            return
        spr = seconds / rows
        flip = None
        with self._lock:
            prev = self._spr[path]
            self._spr[path] = spr if prev is None \
                else prev + self.ALPHA * (spr - prev)
            ewma = self._spr[path]
            dev, host = self._spr["framing"], self._spr["hostpack"]
            if dev is not None and host is not None:
                new = self._winner
                if dev > host * self.MARGIN:
                    new = "hostpack"
                elif host > dev * self.MARGIN:
                    new = "framing"
                if new != self._winner:
                    flip = (self._winner, new,
                            dev if new == "framing" else host,
                            host if new == "framing" else dev)
                    self._winner = new
        # exported unconditionally: when the tier self-disables on a
        # slow backend, these two gauges in /healthz are the operator's
        # signal for WHY device framing stopped engaging
        _metrics.set_gauge(f"framing_{path}_spr", ewma)
        if flip is not None:
            old, new, new_spr, old_spr = flip
            _events.emit(
                "economics", "economics_switch", route="framing",
                detail=f"{old} -> {new} "
                       f"({old}={old_spr:.3g} s/row, {new}={new_spr:.3g})",
                cost=new_spr, cost_unit="s_per_row",
                msg=f"framing economics: {old} -> {new} (measured "
                    f"{new_spr:.3g} s/row vs {old_spr:.3g})")

    def snapshot(self) -> dict:
        with self._lock:
            return {"framing_s_per_row": self._spr["framing"],
                    "hostpack_s_per_row": self._spr["hostpack"],
                    "batches": self._batches}

    @classmethod
    def from_config(cls, config) -> "FramingEconomics":
        enabled = config.lookup_bool(
            "input.tpu_encode_economics",
            "input.tpu_encode_economics must be a boolean", True)
        probe_every = config.lookup_int(
            "input.tpu_encode_probe_every",
            "input.tpu_encode_probe_every must be an integer (batches)",
            256)
        return cls(enabled=enabled, probe_every=probe_every)


def cooldown_state(route_state: dict, framing: str) -> dict:
    """Per-handler decline-hysteresis dict for one framing's device
    tier — its own namespace, so a framing decline never eats the
    decode/encode tiers' decline budgets (fused_routes precedent)."""
    return route_state.setdefault(f"framing:{framing}", {})


def note_decline(state: dict) -> None:
    """Count one watchdog decline; DECLINE_LIMIT in a row starts a
    COOLDOWN of host-framed flushes before the next probe."""
    state["declines"] = state.get("declines", 0) + 1
    if state["declines"] >= DECLINE_LIMIT:
        state["cooldown"] = COOLDOWN
        state["declines"] = 0


def in_cooldown(state: dict) -> bool:
    cd = state.get("cooldown", 0)
    if cd > 0:
        state["cooldown"] = cd - 1
        return True
    return False


def note_success(state: dict) -> None:
    state["declines"] = 0
