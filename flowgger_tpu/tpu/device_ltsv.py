"""Device-side LTSV→GELF encode: final framed bytes assembled on device
for untyped LTSV rows, compacted and fetched output-sized
(device_common machinery — same contract as device_gelf/device_rfc3164).

Layout mirrors the host tier (encode_ltsv_gelf_block.py) byte-for-byte::

    {"_<key>":"V"..., "full_message":L, "host":H|unknown, ["level":N,]
     "short_message":"M"|"-", "timestamp":T, "version":"1.1"}

Pair selection rides the decode kernel's part/special channels over the
small static part axis: a part is a pair iff its index is none of the
(last-occurrence) special positions, and rows with REPEATED special
names fall back — detected elementwise with the same ``name:``-pattern
planes the decoder uses — so last-occurrence equals name-match on every
row the tier accepts, exactly like the host tier's repeated-special
fallback (encode_ltsv_gelf_block.py special_name handling).

Device tier restrictions (everything else splices through the host
span tier / scalar oracle): rfc3339 or unsigned unix-literal
timestamps (the kernel's split-integer parse covers <= 16 digits
within 2**53 exactly; signed or longer stamps need per-value host
parses), ≤6 pairs, 8-byte sort prefixes with the ambiguity/duplicate
fallback of the rfc5424 device sorter, no typed ``ltsv_schema`` (gated
at the route), ASCII rows within the JSON-escape budget.
"""


from __future__ import annotations

# byte-identity contract (flowcheck FC03): the scalar counterpart
# this route must stay byte-identical to, and the differential
# test that enforces it
SCALAR_ORACLE = "flowgger_tpu.encoders.gelf:GelfEncoder"
DIFF_TEST = "tests/test_device_ltsv.py::test_device_ltsv_matches_scalar_and_engages"

from functools import partial

import jax
import jax.numpy as jnp

from .device_common import (
    E_CAP,
    TS_W,
    _out_width,
    assemble_rows,
    escape_stage,
    fetch_encode_driver,
    sort_pairs_by_key8,
)
from .encode_ltsv_gelf_block import (
    _C_DASH,
    _C_FULL,
    _C_HOST,
    _C_LEVEL,
    _C_P0,
    _C_P1,
    _C_P2,
    _C_SEVD,
    _C_SHORT,
    _C_SHORT_LVL,
    _C_TAIL,
    _C_TS,
    _C_UNKNOWN,
)
from .ltsv import _match_at
from .rfc5424 import best_scan_impl

_I32 = jnp.int32

FALLBACK_FRAC = 0.05
DECLINE_LIMIT = 3
COOLDOWN = 16
MAX_DEV_PAIRS = 6
# escalation width when the 6-pair tier declines a batch (encode-side
# analog of the decode rescue): Batcher-16 sort network, 16-pair
# segment table; parts beyond the decode's P=24 axis still fall back
WIDE_DEV_PAIRS = 16

_PARTS = {
    "open": b"{",
    "p0": _C_P0,
    "p1": _C_P1,
    "p2": _C_P2,
    "full": _C_FULL,
    "host": _C_HOST,
    "level": _C_LEVEL,
    "short_l": _C_SHORT_LVL,
    "short": _C_SHORT,
    "ts": _C_TS,
    "tail": _C_TAIL,
    "unknown": _C_UNKNOWN,
    "dash": _C_DASH,
    "sevd": _C_SEVD,
}


def _bank(suffix: bytes, extras=()):
    """Constant bank; extras fold in via the host tier's
    gelf_extra_consts_ltsv so the two tiers can never diverge."""
    parts = dict(_PARTS)
    parts["hl"] = b""
    parts["l2a"] = b""
    parts["l2b"] = b""
    if extras:
        from .encode_ltsv_gelf_block import gelf_extra_consts_ltsv

        econsts = gelf_extra_consts_ltsv(list(extras))
        assert econsts is not None  # route_ok pre-checked
        (parts["open"], parts["full"], parts["host"], parts["hl"],
         parts["l2a"], parts["l2b"], parts["ts"],
         parts["tail"]) = econsts
    from .device_common import build_bank

    bank, offs = build_bank(parts, suffix)
    return bank, offs, parts


def elide_spec(suffix: bytes, extras=()):
    """(head, ts-label, tail) constants the elided kernel skips and the
    host splice restores — single source shared with the fused route."""
    _, _, parts = _bank(suffix, extras)
    return (parts["open"], parts["ts"], parts["tail"] + suffix)


@partial(jax.jit, static_argnames=("suffix", "impl", "assemble",
                                   "extras", "max_pairs", "elide"))
def _encode_kernel(batch, lens, dec, ts_text, ts_len, *, suffix: bytes,
                   impl: str, assemble: bool = True, extras=(),
                   max_pairs: int = MAX_DEV_PAIRS, elide: bool = False):
    N, L = batch.shape
    bank, off, parts = _bank(suffix, extras)
    OW = _out_width(L, L + E_CAP + len(bank) + TS_W)
    iota = jax.lax.broadcasted_iota(_I32, (N, L), 1)
    bb = batch.astype(_I32)

    es = escape_stage(batch, lens, iota, assemble)
    dmap = es["dmap"]
    lens32 = lens.astype(_I32)
    valid = iota < lens32[:, None]
    row_e = lens32 + es["ne_total"]

    # ---- repeated special names (elementwise planes) --------------------
    prev_tab = jnp.pad((batch == 9) & valid, ((0, 0), (1, 0)))[:, :L]
    pstart = valid & ((iota == 0) | prev_tab)
    rep_special = jnp.zeros((N,), dtype=bool)
    for word in (b"time:", b"host:", b"message:", b"level:"):
        m = _match_at(batch, word, valid) & pstart
        rep_special |= jnp.sum(m.astype(_I32), axis=1) > 1

    # ---- pair selection over the static part axis -----------------------
    n_parts = dec["n_parts"].astype(_I32)
    P = dec["part_start"].shape[1]
    # *_pos channels are BYTE positions of the (last) special key start
    # (-1 when absent); a part is special iff its start equals one
    specials = [dec[k].astype(_I32) for k in ("time_pos", "host_pos",
                                              "msg_pos", "level_pos")]
    pair_ord_cols = []
    run = jnp.zeros((N,), dtype=_I32)
    is_pair_cols = []
    colonless = jnp.zeros((N,), dtype=bool)
    for j in range(P):
        in_row = j < n_parts
        ps_j = dec["part_start"][:, j].astype(_I32)
        is_spec = jnp.zeros((N,), dtype=bool)
        for sp in specials:
            is_spec |= (sp >= 0) & (ps_j == sp)
        isp = in_row & ~is_spec
        colonless |= in_row & (dec["colon_pos"][:, j].astype(_I32) < 0)
        run = run + isp.astype(_I32)
        is_pair_cols.append(isp)
        pair_ord_cols.append(run)
    pair_count = run

    # per-pair channel select (static P x MAX_DEV_PAIRS where-chains)
    def sel(chan_key, plus=0):
        outs = []
        ch = dec[chan_key].astype(_I32)
        for p in range(max_pairs):
            acc = jnp.zeros((N,), dtype=_I32)
            for j in range(P):
                acc = jnp.where(is_pair_cols[j]
                                & (pair_ord_cols[j] == p + 1),
                                ch[:, j] + plus, acc)
            outs.append(acc)
        return outs

    ns_r = sel("part_start")
    ne_r = sel("colon_pos")            # name end = ':' position
    vs_r = sel("colon_pos", plus=1)
    ve_r = sel("part_end")

    # ---- 8-byte sort keys + shared network ------------------------------
    cols = {"_pair_count": pair_count,
            "ns_raw": list(ns_r), "ne_raw": list(ne_r),
            "ns": [dmap(x) for x in ns_r],
            "ne": [dmap(x) for x in ne_r],
            "vs": [dmap(x) for x in vs_r],
            "ve": [dmap(x) for x in ve_r]}
    ambig = sort_pairs_by_key8(bb, iota, cols, max_pairs)

    # ---- fixed-field spans ----------------------------------------------
    host_s = dmap(dec["host_start"])
    host_e = dmap(dec["host_end"])
    msg_s = dmap(dec["msg_start"])
    msg_e = dmap(dec["msg_end"])
    has_msg = dec["msg_pos"].astype(_I32) >= 0
    level = dec["level_val"].astype(_I32)
    has_level = level >= 0

    # ---- segment table (mirrors the host tier's 1 + 5p + 13 layout) -----
    EW = L + E_CAP
    cbase = EW
    tbase = EW + len(bank)
    zero = jnp.zeros((N,), dtype=_I32)
    # elide=True: the row-constant head/ts-label/tail segments stay off
    # the device row; the host splice restores them post-fetch
    # (device_common.splice_elided_rows)
    segs = [] if elide else [(zero + (cbase + off["open"]),
                              zero + len(parts["open"]))]
    for p in range(max_pairs):
        pv = p < pair_count
        segs.append((zero + (cbase + off["p0"]),
                     jnp.where(pv, 2, 0)))
        segs.append((cols["ns"][p],
                     jnp.where(pv, cols["ne"][p] - cols["ns"][p], 0)))
        segs.append((zero + (cbase + off["p1"]),
                     jnp.where(pv, 3, 0)))
        segs.append((cols["vs"][p],
                     jnp.where(pv, cols["ve"][p] - cols["vs"][p], 0)))
        segs.append((zero + (cbase + off["p2"]),
                     jnp.where(pv, 2, 0)))
    host_empty = host_e <= host_s
    qsrc = cbase + off["p1"] + 2   # a '"' byte inside the '":"' const
    segs += [
        (zero + (cbase + off["full"]), zero + len(parts["full"])),
        (zero, row_e),
        (zero + (cbase + off["host"]), zero + len(parts["host"])),
        (jnp.where(host_empty, cbase + off["unknown"], host_s),
         jnp.where(host_empty, len(_C_UNKNOWN), host_e - host_s)),
        (zero + (cbase + off["hl"]), zero + len(parts["hl"])),
        (zero + (cbase + off["level"]),
         jnp.where(has_level, len(_C_LEVEL), 0)),
        (cbase + off["sevd"] + jnp.maximum(level, 0),
         jnp.where(has_level, 1, 0)),
        # extras between level and short: after-number when a level is
        # present, string-close otherwise (same pairing as short below)
        (jnp.where(has_level, cbase + off["l2a"], cbase + off["l2b"]),
         jnp.where(has_level, len(parts["l2a"]), len(parts["l2b"]))),
        (jnp.where(has_level, cbase + off["short_l"],
                   cbase + off["short"]),
         jnp.where(has_level, len(_C_SHORT_LVL), len(_C_SHORT))),
        (jnp.where(has_msg, qsrc, cbase + off["dash"]),
         jnp.where(has_msg, 1, len(_C_DASH))),
        (msg_s, jnp.where(has_msg, msg_e - msg_s, 0)),
        (zero + qsrc, jnp.where(has_msg, 1, 0)),
    ]
    if not elide:
        segs.append((zero + (cbase + off["ts"]),
                     zero + len(parts["ts"])))
    segs.append((zero + tbase, ts_len.astype(_I32)))
    if not elide:
        segs.append((zero + (cbase + off["tail"]),
                     zero + len(parts["tail"]) + len(suffix)))

    out_len = segs[0][1]
    for _, ln in segs[1:]:
        out_len = out_len + ln

    # timestamps: rfc3339 rides the computed-channel path; unix-literal
    # floats ride the split-integer parse when unsigned and within f64's
    # exact-integer range (<= 16 digits, value < 2**53 — the host
    # combine is then the correctly rounded strtod value); anything
    # else (signed, 17+ digits) falls back to the host tier
    kind = dec["ts_kind"].astype(_I32)
    meta = dec["ts_meta"].astype(_I32)
    ts_hi = dec["ts_hi"].astype(_I32)
    ts_lo = dec["ts_lo"].astype(_I32)
    ndig = (meta >> 8) & 255
    signed = ((meta >> 16) & 1) == 1
    f16_ok = (ts_hi < 9007199) | ((ts_hi == 9007199)
                                  & (ts_lo <= 254740992))
    float_dev = ((kind == 1) & ~signed
                 & ((ndig <= 15) | ((ndig == 16) & f16_ok)))
    tier = (dec["ok"].astype(bool)
            & ~dec["has_high"].astype(bool)
            & ~jnp.any(es["bad_ctl"], axis=1)
            & (es["ne_total"] <= E_CAP)
            & ((kind == 0) | float_dev)
            & (dec["host_pos"].astype(_I32) >= 0)
            & ~colonless
            & ~rep_special
            & (pair_count <= max_pairs)
            & ~ambig
            & (out_len <= OW))
    if not assemble:
        return tier
    acc, out_len2 = assemble_rows(segs, es["esc_row"], bank, ts_text,
                                  N, OW)
    return acc, out_len2, tier


def route_ok(encoder, merger, decoder=None) -> bool:
    """GELF output over line/nul/syslen framing, untyped decode only
    (``ltsv_schema`` rows carry per-value canonicality screens that are
    host work); gelf_extra rides as constant segments when this
    layout's keys place statically (gelf_extra_consts_ltsv)."""
    from .device_common import gelf_route_ok
    from .encode_ltsv_gelf_block import gelf_extra_consts_ltsv

    if decoder is not None and getattr(decoder, "schema", None):
        return False
    return gelf_route_ok(
        encoder, merger,
        lambda e: gelf_extra_consts_ltsv(e) is not None)


TS_KEYS = ("days", "sod", "off", "nanos", "ts_kind",
           "ts_hi", "ts_lo", "ts_meta")


def ts_vals_ltsv(small, okh):
    """rfc3339 rows combine days/sod/off/nanos; float-span rows
    combine the kernel's exact split-integer parse (vectorized —
    no per-row Python).  Shared by the split and fused ltsv tiers."""
    import numpy as np

    from .materialize import compute_ts

    kind = small["ts_kind"]
    rfc = okh & (kind == 0)
    masked = {k: np.where(rfc, small[k], 0)
              for k in ("days", "sod", "off", "nanos")}
    vals = compute_ts(masked)
    fv = ((small["ts_hi"].astype(np.float64) * 1e9
           + small["ts_lo"].astype(np.float64))
          / np.power(10.0, (small["ts_meta"] & 255).astype(np.int64)))
    return np.where(okh & (kind == 1), fv, vals)


def fetch_encode(handle, packed, encoder, merger, route_state=None,
                 decoder=None):
    """Device ltsv→GELF encode for a submitted ltsv decode handle;
    returns (BlockResult | None, fetch_seconds)."""
    from .block_common import merger_suffix
    from .materialize_ltsv import _scalar_ltsv

    out, batch_dev, lens_dev = handle
    suffix, syslen = merger_suffix(merger)
    impl = best_scan_impl()
    extras = tuple((k, v) for k, v in getattr(encoder, "extra", ()))
    # constant elision, extended from the rfc5424→GELF leg: head /
    # ts-label / tail never cross PCIe, the splice restores them
    espec = elide_spec(suffix, extras)

    def kernel(ts_text, ts_len, assemble):
        return _encode_kernel(batch_dev, lens_dev, dict(out), ts_text,
                              ts_len, suffix=suffix, impl=impl,
                              assemble=assemble, extras=extras,
                              elide=True)

    # zero-JIT boot: consult the AOT artifact store before compiling
    from .aot import encode_wrap

    kernel = encode_wrap("device_ltsv", kernel, batch_dev, lens_dev,
                         dict(out), suffix, impl, extras)

    def wide():
        """16-pair escalation kernel (lazy: compiled only when a batch
        declines at the 6-pair width)."""
        def kernel_w(ts_text, ts_len, assemble):
            return _encode_kernel(batch_dev, lens_dev, dict(out), ts_text,
                                  ts_len, suffix=suffix, impl=impl,
                                  assemble=assemble, extras=extras,
                                  max_pairs=WIDE_DEV_PAIRS, elide=True)
        return out, kernel_w

    def scalar_fn(line):
        return _scalar_ltsv(decoder, line)

    return fetch_encode_driver(
        kernel, out, batch_dev, lens_dev, packed, encoder, merger,
        route_state, suffix, syslen, scalar_fn=scalar_fn,
        fallback_frac=FALLBACK_FRAC, decline_limit=DECLINE_LIMIT,
        cooldown=COOLDOWN, ts_keys=TS_KEYS,
        ts_vals_fn=ts_vals_ltsv, wide=wide, elide=espec)
