"""Device-side RFC5424→GELF encode: the kernel emits the *final framed
output bytes* as one dense ``[N, OW]`` byte matrix plus a length vector,
then compacts the tier rows on-device (device_common._compact_kernel)
so the host fetch is ~``sum(out_len)`` bytes — truly output-sized —
instead of ~24 span channels or the padded matrix (the reference fuses
decode→encode per line in its hot loop, line_splitter.rs:44-54 →
gelf_encoder.rs:59-115 — this is the batched-TPU shape of that fusion).
The row-constant head, timestamp-label, and tail segments never cross
PCIe at all: the kernel runs with ``elide=True`` and the driver splices
those exact host-tier bytes back after the fetch
(device_common.splice_elided_rows), which is what brings fetched
bytes/row *under* emitted bytes/row.

Everything is gather-free (the environment's recorded XLA-on-TPU fact:
dynamic gathers lower near-serially — never gather):

- **JSON escaping** is a monotone expansion: each byte's destination is
  ``j + #escapes-before(j) (+1 for the escaped byte itself)``, placed
  collision-free by the MSB-first barrel shifter
  (device_common._monotone_expand).
- **Segment assembly** is an OR-accumulation over a *static* list of
  ~48 segments (1 brace + 5 per SD pair + 17 tail parts, mirroring
  encode_gelf_block.py's layout byte-for-byte) via
  device_common.assemble_rows.
- **SD pair sorting** (serde_json's BTreeMap key order) extracts each
  name's first 8 bytes into two packed int32 words via masked one-hot
  sums, runs a 12-comparator sorting network over the ≤6-pair tier with
  the d-mapped spans riding as payload, and falls the row back to the
  host tiers when keys are ambiguous (equal 8-byte prefixes that zero-
  padding cannot order) or duplicate (dict last-wins semantics).

Rows outside the tier (kernel-flagged, non-ASCII, >6 pairs, RFC5424
value escapes, 6-byte ``\\u00XX`` control escapes, oversized output)
keep their existing host paths, so observable bytes stay identical to
the scalar route in every case.

The timestamp digits (shortest round-trip f64, serde_json/Ryu form) are
formatted host-side (native threaded formatter) and uploaded as a
``[N, TS_W]`` text block — the only host↔device round-trip; everything
else rides the decode call's device-resident channels.
"""


from __future__ import annotations

# byte-identity contract (flowcheck FC03): the scalar counterpart
# this route must stay byte-identical to, and the differential
# test that enforces it
SCALAR_ORACLE = "flowgger_tpu.encoders.gelf:GelfEncoder"
DIFF_TEST = "tests/test_device_gelf.py::test_device_matches_scalar_and_engages"

import os
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .device_common import (  # noqa: F401  (re-exported for tests/siblings)
    COMPACT_G,
    COMPACT_MIN_SAVING,
    E_CAP,
    TS_W,
    _AMBIG_LEN,
    _BIG,
    _NET6,
    _compact_kernel,
    _monotone_expand,
    _rot_rows,
    _out_width,
    assemble_rows,
    escape_stage,
    fetch_encode_driver,
    sort_pairs_by_key8,
    ts_text_block as _ts_text_block,
)
from .rfc5424 import best_scan_impl

_I32 = jnp.int32
_U8 = jnp.uint8

# constant bank: the same byte constants the host tier uses (single
# source of truth — the two tiers must never diverge, since fallback
# rows splice host-tier output into device-tier blocks)
from .encode_gelf_block import (  # noqa: E402
    _C_APP, _C_DASH, _C_FULL, _C_HOST, _C_LEVEL, _C_OPEN, _C_P0, _C_P1,
    _C_P2, _C_PROC, _C_SDID, _C_SEVD, _C_SHORT, _C_TAIL, _C_TS,
    _C_UNKNOWN,
)

_PARTS = {
    "open": _C_OPEN,
    "p0": _C_P0,
    "p1": _C_P1,
    "p2": _C_P2,
    "app": _C_APP,
    "full": _C_FULL,
    "host": _C_HOST,
    "level": _C_LEVEL,
    "proc": _C_PROC,
    "sdid": _C_SDID,
    "short": _C_SHORT,
    "ts": _C_TS,
    "tail": _C_TAIL,
    "unknown": _C_UNKNOWN,
    "dash": _C_DASH,
    "sevd": _C_SEVD,
}

def _bank(suffix: bytes, extras: Tuple[Tuple[str, str], ...] = ()
          ) -> Tuple[bytes, Dict[str, int], Dict[str, bytes]]:
    """Constant bank with any ``gelf_extra`` pairs folded into the
    neighbouring segment constants (static insertion slots — the same
    gelf_extra_consts the host tier uses, so the two tiers can never
    disagree on extras placement)."""
    from .encode_gelf_block import gelf_extra_consts

    parts = dict(_PARTS)
    if extras:
        econsts = gelf_extra_consts(list(extras))
        assert econsts is not None  # route_ok pre-checked
        (parts["open"], parts["app"], parts["full"], parts["host"],
         parts["level"], parts["proc"], parts["p6x"], parts["short"],
         parts["ts"], parts["tail"]) = econsts
    from .device_common import build_bank

    bank, offs = build_bank(parts, suffix)
    return bank, offs, parts


def elide_spec(suffix: bytes, extras=()):
    """(head, ts-label, tail) constants the elided kernel skips and the
    host splice restores — single source shared with the fused route."""
    _, _, parts = _bank(suffix, extras)
    return (parts["open"], parts["ts"], parts["tail"] + suffix)


@partial(jax.jit, static_argnames=("suffix", "max_sd", "impl",
                                   "assemble", "extras", "elide"))
def _encode_kernel(batch, lens, dec, ts_text, ts_len, *, suffix: bytes,
                   max_sd: int, impl: str, assemble: bool = True,
                   extras: Tuple[Tuple[str, str], ...] = (),
                   elide: bool = False):
    N, L = batch.shape
    bank, off, parts = _bank(suffix, extras)
    OW = _out_width(L, L + E_CAP + len(bank) + TS_W)
    iota = jax.lax.broadcasted_iota(_I32, (N, L), 1)
    bb = batch.astype(_I32)

    es = escape_stage(batch, lens, iota, assemble)
    dmap = es["dmap"]

    # ---- fixed-field spans in escaped coordinates ------------------------
    app_s, app_e = dmap(dec["app_start"]), dmap(dec["app_end"])
    proc_s, proc_e = dmap(dec["proc_start"]), dmap(dec["proc_end"])
    host_s, host_e = dmap(dec["host_start"]), dmap(dec["host_end"])
    full_s = dmap(dec["full_start"])
    trim_e = dmap(dec["trim_end"])
    msg_s = dmap(dec["msg_trim_start"])

    sd_count = dec["sd_count"].astype(_I32)
    nsd = sd_count > 0
    # last SD block id span (select over the small static block axis)
    sid_s_raw = jnp.zeros_like(sd_count)
    sid_e_raw = jnp.zeros_like(sd_count)
    for k in range(dec["sid_start"].shape[1]):
        pick = sd_count - 1 == k
        sid_s_raw = jnp.where(pick, dec["sid_start"][:, k].astype(_I32),
                              sid_s_raw)
        sid_e_raw = jnp.where(pick, dec["sid_end"][:, k].astype(_I32),
                              sid_e_raw)
    sid_s, sid_e = dmap(sid_s_raw), dmap(sid_e_raw)

    # ---- SD pairs: 8-byte name keys, d-mapped spans, shared sorter ------
    pair_count = dec["pair_count"].astype(_I32)
    P = dec["name_start"].shape[1]
    val_esc_any = jnp.zeros((N,), dtype=bool)
    cols = {"_pair_count": pair_count, "ns_raw": [], "ne_raw": [],
            "ns": [], "ne": [], "vs": [], "ve": []}
    for p in range(P):
        ns_r = dec["name_start"][:, p].astype(_I32)
        ne_r = dec["name_end"][:, p].astype(_I32)
        val_esc_any |= (dec["val_has_esc"][:, p].astype(bool)
                        & (p < pair_count))
        cols["ns_raw"].append(ns_r)
        cols["ne_raw"].append(ne_r)
        cols["ns"].append(dmap(ns_r))
        cols["ne"].append(dmap(ne_r))
        cols["vs"].append(dmap(dec["val_start"][:, p]))
        cols["ve"].append(dmap(dec["val_end"][:, p]))
    ambig = sort_pairs_by_key8(bb, iota, cols, P)

    # ---- segment table ---------------------------------------------------
    EW = L + E_CAP
    cbase = EW
    tbase = EW + len(bank)
    zero = jnp.zeros((N,), dtype=_I32)
    segs = []  # (src0 [N], seglen [N]) in destination order

    def add_const(name, gate=None):
        ln = zero + len(parts[name]) + (len(suffix) if name == "tail"
                                        else 0)
        if gate is not None:
            ln = jnp.where(gate, ln, 0)
        segs.append((zero + (cbase + off[name]), ln))

    def add_span(s, e, gate=None):
        ln = jnp.maximum(e - s, 0)
        if gate is not None:
            ln = jnp.where(gate, ln, 0)
        segs.append((s, ln))

    if not elide:
        # constant-elision mode skips the row-constant head, timestamp
        # label, and tail segments: the host splice restores them after
        # an output-sized (variable-bytes-only) D2H fetch
        # (device_common.splice_elided_rows)
        add_const("open")
    for p in range(P):
        pv = p < pair_count
        add_const("p0", pv)
        add_span(cols["ns"][p], cols["ne"][p], pv)
        add_const("p1", pv)
        add_span(cols["vs"][p], cols["ve"][p], pv)
        add_const("p2", pv)

    add_const("app")
    add_span(app_s, app_e)
    add_const("full")
    add_span(full_s, trim_e)
    add_const("host")
    host_empty = host_e <= host_s
    segs.append((jnp.where(host_empty, cbase + off["unknown"], host_s),
                 jnp.where(host_empty, len(parts["unknown"]),
                           host_e - host_s)))
    add_const("level")
    segs.append((cbase + off["sevd"] + dec["severity"].astype(_I32),
                 zero + 1))
    add_const("proc")
    add_span(proc_s, proc_e)
    if parts.get("p6x"):
        # extras sorting between "process_id" and "sd_id": always-on
        # constant ahead of the (gated) sd_id segment
        add_const("p6x")
    add_const("sdid", nsd)
    add_span(sid_s, sid_e, nsd)
    add_const("short")
    msg_empty = trim_e <= msg_s
    segs.append((jnp.where(msg_empty, cbase + off["dash"], msg_s),
                 jnp.where(msg_empty, 1, trim_e - msg_s)))
    if not elide:
        add_const("ts")
    segs.append((zero + tbase, ts_len.astype(_I32)))
    if not elide:
        add_const("tail")

    out_len = segs[0][1]
    for _, ln in segs[1:]:
        out_len = out_len + ln

    # ---- tier ------------------------------------------------------------
    tier = (dec["ok"].astype(bool)
            & ~dec["has_high"].astype(bool)
            & ~jnp.any(es["bad_ctl"], axis=1)
            & (es["ne_total"] <= E_CAP)
            & (pair_count <= P)
            & (sd_count <= max_sd)
            & ~val_esc_any
            & ~ambig
            & (out_len <= OW))
    if not assemble:
        return tier
    acc, out_len2 = assemble_rows(segs, es["esc_row"], bank, ts_text,
                                  N, OW)
    return acc, out_len2, tier


def route_ok(encoder, merger) -> bool:
    """Device encode applies to GELF output over line/nul/syslen framing
    (the syslen prefix is spliced host-side over the output-sized device
    body); gelf_extra rides as constant segments when its keys have
    static placement (encode_gelf_block.gelf_extra_slots)."""
    from .device_common import gelf_route_ok
    from .encode_gelf_block import gelf_extra_slots

    return gelf_route_ok(
        encoder, merger, lambda e: gelf_extra_slots(e) is not None)


# fraction of non-tier rows above which the span-fetch host path wins
# (scalar oracle ≈70K rows/s vs native assembler ≈1.16M rows/s per core).
# Rows the decode kernel itself flagged — including 7-16-pair rows the
# span path would rescue through the wider tier-2 kernel — count against
# this budget, so a stream that is persistently rescue-heavy declines to
# the span path rather than scalar-oracling those rows forever.
FALLBACK_FRAC = 0.05

# hysteresis: after this many consecutive declined batches, skip the
# device attempt entirely for COOLDOWN batches before probing again
DECLINE_LIMIT = 3
COOLDOWN = 16


def fetch_encode(handle, packed, encoder, merger, route_state=None):
    """Run the device encode for a submitted rfc5424 decode; returns
    (BlockResult | None, fetch_seconds). None = caller should use the
    span-fetch host path (high fallback fraction).  See
    device_common.fetch_encode_driver for the shared flow."""
    from .block_common import merger_suffix

    out, _, _, max_sd, impl_unused, batch_dev, lens_dev = handle
    suffix, syslen = merger_suffix(merger)
    impl = best_scan_impl()
    extras = tuple((k, v) for k, v in getattr(encoder, "extra", ()))
    # constant elision: the head, timestamp-label, and tail constants
    # never cross PCIe — the kernel skips them and the driver splices
    # these exact host-tier bytes back (same _bank the kernel uses, so
    # the two sides cannot disagree)
    espec = elide_spec(suffix, extras)

    def kernel(ts_text, ts_len, assemble):
        return _encode_kernel(batch_dev, lens_dev, dict(out), ts_text,
                              ts_len, suffix=suffix, max_sd=max_sd,
                              impl=impl, assemble=assemble,
                              extras=extras, elide=True)

    # zero-JIT boot: a loaded AOT artifact replaces the trace+compile
    # (same program, byte-identical); misses/rejects fall through to
    # the jit closure under the same watchdog
    from .aot import encode_wrap

    kernel = encode_wrap("device_gelf", kernel, batch_dev, lens_dev,
                         dict(out), suffix, impl, extras, max_sd=max_sd)

    def wide():
        """Pair-budget escalation: re-decode the batch on-device at the
        decode rescue width (16 SD pairs) and encode from those
        channels — the [N, 16] pair axis sizes the sorter and segment
        table automatically.  Lazy: a 7+-pair stream pays the second
        decode + wide compile only when the base width declines."""
        from .rfc5424 import RESCUE_MAX_PAIRS, decode_rfc5424_jit

        out_w = decode_rfc5424_jit(batch_dev, lens_dev, max_sd=max_sd,
                                   max_pairs=RESCUE_MAX_PAIRS)

        def kernel_w(ts_text, ts_len, assemble):
            return _encode_kernel(batch_dev, lens_dev, dict(out_w),
                                  ts_text, ts_len, suffix=suffix,
                                  max_sd=max_sd, impl=impl,
                                  assemble=assemble, extras=extras,
                                  elide=True)
        return out_w, kernel_w

    from .materialize import _scalar_line

    return fetch_encode_driver(
        kernel, out, batch_dev, lens_dev, packed, encoder, merger,
        route_state, suffix, syslen, scalar_fn=_scalar_line,
        fallback_frac=FALLBACK_FRAC, decline_limit=DECLINE_LIMIT,
        cooldown=COOLDOWN, wide=wide, elide=espec)
