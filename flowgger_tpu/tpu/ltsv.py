r"""Columnar LTSV decoder (BASELINE.json config #2).

Scalar spec: flowgger_tpu/decoders/ltsv.py (reference
ltsv_decoder.rs:23-267).  Line shape: tab-separated ``key:value`` parts;
special keys time/host/message/level; everything else becomes an SD pair
(typed by the host-side schema).

Columnar plan (same no-gather discipline as tpu/rfc5424.py):

- tab cumsum segments the line into parts; the k-th part's span and its
  first ``:`` come from payload-packed masked min-reductions;
- the special keys are found *elementwise*: position p starts ``time:``
  iff the five shifted byte-planes match ``t i m e :`` and p is a part
  start (line start or preceded by a tab) — one vectorized pattern per
  special key, last occurrence wins via a max-reduction (the scalar
  decoder's assignments also overwrite);
- ``time`` values parse on-device for the two fast-path forms: plain
  unix float (optional sign/fraction) and (optionally ``[...]``-wrapped)
  RFC3339; apache-english timestamps and other oddities flag the row to
  the scalar oracle;
- ``level`` parses as an int; out-of-range falls back (exact error text
  comes from the oracle);
- remaining parts are emitted as (key, value) span pairs; schema typing
  (u64/i64/f64/bool + suffixes) happens at host materialization where
  Python values are being built anyway.

ts result is returned as integer pieces: unix float values as
(mantissa, scale) can't cover the f64 domain, so the kernel only
fast-paths RFC3339 (days/sod/off/nanos like rfc5424) and flags plain
floats for a *vectorized host* parse (numpy float64 on the value spans
is exact and cheap) — ``ts_kind`` 0=rfc3339, 1=float-span, else fallback.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from .rfc5424 import (
    _days_from_civil,
    _days_in_month,
    _min_where,
    _scan_ordinals,
    _shift_left,
    _shift_right,
    best_extract_impl,
    best_scan_impl,
    extract_by_ord,
)

DEFAULT_MAX_PARTS = 24
_I32 = jnp.int32


def _match_at(bb, text: bytes, valid):
    """Elementwise: does ``text`` start at each position?  Uses shifted
    byte planes only (no gathers)."""
    m = (bb == text[0]) & valid
    for i, ch in enumerate(text[1:], start=1):
        m &= _shift_left(bb, i, 0) == ch
    return m


def decode_ltsv(batch: jnp.ndarray, lens: jnp.ndarray,
                max_parts: int = DEFAULT_MAX_PARTS,
                scan_impl: str = None,
                extract_impl: str = None) -> Dict[str, jnp.ndarray]:
    if scan_impl is None:
        scan_impl = best_scan_impl()
    if extract_impl is None:
        extract_impl = best_extract_impl()
    N, L = batch.shape
    lens = lens.astype(_I32)
    iota = jax.lax.broadcasted_iota(_I32, (N, L), 1)
    valid = iota < lens[:, None]
    # uint8 byte plane (see rfc5424.py): widen inside consumer fusions
    bb = jnp.where(valid, batch, jnp.uint8(0))
    is_digit = (bb >= 48) & (bb <= 57)
    dig = bb.astype(_I32) - 48

    is_tab = (bb == 9) & valid
    (tab_ord,) = _scan_ordinals([is_tab], scan_impl)
    n_tabs = jnp.max(jnp.where(is_tab, tab_ord, 0), axis=1).astype(_I32)
    n_parts = n_tabs + 1
    ok = n_parts <= max_parts

    # part starts: 0 and tab+1; part ends: tab positions and len —
    # tab positions via packed-sum extraction words (one word per 3
    # ordinals) instead of one masked min-reduction per ordinal
    tab_pos = extract_by_ord(is_tab, tab_ord, iota, max_parts - 1, L,
                             extract_impl)
    part_end = jnp.concatenate(
        [jnp.minimum(tab_pos, lens[:, None]), lens[:, None]], axis=1)
    part_start = jnp.concatenate(
        [jnp.zeros_like(lens)[:, None],
         jnp.minimum(tab_pos + 1, lens[:, None])], axis=1)

    # first ':' in each part (or L): a colon is first-in-its-part iff the
    # last tab-or-colon strictly before it is a tab (or line start).  One
    # cummax of a tagged channel (2*iota+1 at tabs, 2*iota at colons) plus
    # ONE packed-sum extraction keyed on part ordinals replaces the old
    # P=24 per-part _min_where stack (round-5 fusion fold; the same shape
    # that took rfc5424's sid_end stack out).
    is_colon = (bb == ord(":")) & valid
    tag = jnp.where(is_tab, 2 * iota + 1,
                    jnp.where(is_colon, 2 * iota, -1))
    last_tc = _shift_right(jax.lax.cummax(tag, axis=1), 1, -1)
    # -1 & 1 == 1, so line start (no prior tab/colon) also counts as tab
    first_colon = is_colon & ((last_tc & 1) == 1)
    # part ordinal of a (non-tab) position = tabs at or before it
    part_of = tab_ord.astype(_I32)
    colon_pos = extract_by_ord(first_colon, part_of + 1, iota, max_parts, L,
                               extract_impl)
    has_colon = colon_pos < part_end

    # ---- special keys, elementwise pattern matches ----------------------
    at_part_start = (iota == 0) | (_shift_right(is_tab, 1, False))
    # pack (position, part ordinal) in one word so the max-reduction that
    # finds the key also yields which part holds it (fold: the 4 per-key
    # value_span min-reductions become [N, P]-sized part_end selects)
    tbits = int(L + 1).bit_length()
    pos_part = (iota << tbits) | part_of

    def special(key: bytes):
        pat = _match_at(bb, key + b":", valid) & at_part_start
        # last occurrence wins (scalar decoder overwrites); max over the
        # packed word orders by position (the high field)
        w = jnp.max(jnp.where(pat, pos_part, -1), axis=1)
        pos = jnp.where(w >= 0, w >> tbits, -1)
        pidx = jnp.where(w >= 0, w & ((1 << tbits) - 1), 0)
        return pos, pidx

    time_pos, time_pi = special(b"time")
    host_pos, host_pi = special(b"host")
    msg_pos, msg_pi = special(b"message")
    level_pos, level_pi = special(b"level")

    krange = jnp.arange(max_parts, dtype=_I32)

    def value_span(pos, pidx, key_len):
        """[value_start, part end) for a special key at pos — tabs are
        separators, so the value always runs to its part's end; select
        part_end[n, pidx] with a tiny [N, P] masked sum (no gather)."""
        vstart = pos + key_len + 1
        vend = jnp.sum(
            jnp.where(krange[None, :] == pidx[:, None], part_end, 0), axis=1)
        return vstart, jnp.where(pos >= 0, vend, -1)

    host_start, host_end = value_span(host_pos, host_pi, 4)
    msg_start, msg_end = value_span(msg_pos, msg_pi, 7)
    level_start, level_end = value_span(level_pos, level_pi, 5)
    time_start, time_end = value_span(time_pos, time_pi, 4)

    has_time = time_pos >= 0
    has_host = host_pos >= 0
    ok &= has_time & has_host  # missing -> oracle for exact error text
    tv_len = time_end - time_start

    # ---- level parse ----------------------------------------------------
    has_level = level_pos >= 0
    lv_r = iota - level_start[:, None]
    lv_len = level_end - level_start
    in_lv = (lv_r >= 0) & (lv_r < lv_len[:, None]) & has_level[:, None]
    lv_digits_ok = ~jnp.any(in_lv & ~is_digit, axis=1)
    lv_w = jnp.where(lv_r >= 0, 10 ** jnp.clip(lv_len[:, None] - 1 - lv_r, 0, 8), 0)
    level_val = jnp.sum(jnp.where(in_lv, dig * lv_w, 0), axis=1)
    lv_ok = (~has_level) | (lv_digits_ok & (lv_len >= 1) & (lv_len <= 3)
                            & (level_val <= 7))
    ok &= lv_ok  # >7 or junk -> oracle reproduces the exact error

    # ---- time parse -----------------------------------------------------
    # optional [ ... ] wrapper.  The bytes at time_start, time_start+1 and
    # time_end-1 ride ONE packed 8-bit-field sum (fold: was 3 reductions —
    # t_first, t_last, and the post-bracket c0); coinciding positions for
    # 1/2-char values land in separate fields, so no carries.
    bi = bb.astype(_I32)
    w3 = jnp.sum(
        jnp.where(iota == time_start[:, None], bi, 0)
        + (jnp.where(iota == (time_start + 1)[:, None], bi, 0) << 8)
        + (jnp.where(iota == (time_end - 1)[:, None], bi, 0) << 16), axis=1)
    w3 = jnp.where(has_time, w3, 0)
    t_first = w3 & 255
    t_second = (w3 >> 8) & 255
    t_last = (w3 >> 16) & 255
    bracketed = (t_first == ord("[")) & (t_last == ord("]")) & (tv_len >= 2)
    ts_s = jnp.where(bracketed, time_start + 1, time_start)
    ts_e = jnp.where(bracketed, time_end - 1, time_end)
    tlen = ts_e - ts_s

    r = iota - ts_s[:, None]
    in_t = (r >= 0) & (r < tlen[:, None])

    # float form: [+-]? digits [. digits]  (exponents/inf/nan -> fallback)
    c0 = jnp.where(bracketed, t_second, t_first)
    has_sign = (c0 == ord("+")) | (c0 == ord("-"))
    body_from = jnp.where(has_sign, 1, 0)
    dot_pos = _min_where(in_t & (bb == ord(".")), r, 1 << 20)
    n_dots = jnp.sum((in_t & (bb == ord("."))).astype(_I32), axis=1)
    # both disqualifiers share ONE any-reduction (fold: was 2)
    float_viol = (
        (in_t & (r >= body_from[:, None]) & (r != dot_pos[:, None]) & ~is_digit)
        | (in_t & (r == body_from[:, None]) & (bb == ord(".")))
    )
    float_ok = (
        ~jnp.any(float_viol, axis=1) & (n_dots <= 1) & (tlen >= 1)
        & (tlen - body_from >= 1)
    )

    # exact split-integer parse of the float span for the device-encode
    # tier: value == (ts_hi * 1e9 + ts_lo) / 10**frac.  The tier bounds
    # total digits (<= 16 within 2**53) so the f64 combine on the host
    # is the correctly rounded strtod value — byte-identical to the
    # scalar path's float(span) + json_f64.  ts_meta packs
    # frac_digits | n_digits<<8 | has_sign<<16, all elementwise.
    has_dot = n_dots == 1
    nd_digits = tlen - body_from - has_dot.astype(_I32)
    frac_digits = jnp.where(has_dot, tlen - 1 - dot_pos, 0)
    di = r - body_from[:, None] - (r > dot_pos[:, None]).astype(_I32)
    place = nd_digits[:, None] - 1 - di
    dig_m = (in_t & is_digit & (r >= body_from[:, None])
             & (r != dot_pos[:, None]))
    lo_w = jnp.where(dig_m & (place >= 0) & (place <= 8),
                     10 ** jnp.clip(place, 0, 8), 0)
    hi_w = jnp.where(dig_m & (place >= 9) & (place <= 17),
                     10 ** jnp.clip(place - 9, 0, 8), 0)
    ts_lo = jnp.sum(dig * lo_w, axis=1)
    ts_hi = jnp.sum(dig * hi_w, axis=1)
    ts_meta = (jnp.clip(frac_digits, 0, 255)
               | (jnp.clip(nd_digits, 0, 255) << 8)
               | (has_sign.astype(_I32) << 16))

    # rfc3339 form: reuse the rfc5424 timestamp machinery inline.
    # Digit sums ride packed 8/14-bit fields: month|day|hour|minute in one
    # word, year|sec in a second (fold: was 6 reductions); per-field sums
    # are <= 99/9999, so fields never carry.
    dz = jnp.where(in_t, dig, 0)
    w_mdhm = ((r == 5) * 10 + (r == 6)
              + (((r == 8) * 10 + (r == 9)) << 8)
              + (((r == 11) * 10 + (r == 12)) << 16)
              + (((r == 14) * 10 + (r == 15)) << 24))
    wm = jnp.sum(dz * w_mdhm, axis=1)
    month = wm & 255
    day = (wm >> 8) & 255
    hour = (wm >> 16) & 255
    minute = (wm >> 24) & 255
    w_ys = ((r == 0) * 1000 + (r == 1) * 100 + (r == 2) * 10 + (r == 3)
            + (((r == 17) * 10 + (r == 18)) << 14))
    wy = jnp.sum(dz * w_ys, axis=1)
    year = wy & 16383
    sec = (wy >> 14) & 255
    digit_off = ((r >= 0) & (r <= 18) &
                 (r != 4) & (r != 7) & (r != 10) & (r != 13) & (r != 16))
    # every structural disqualifier (digit slots, separators, and — below —
    # the numeric-offset shape) ORs into one mask for a single any (fold:
    # was 6 reductions across rviol/oviol)
    viol_mask = in_t & digit_off & ~is_digit
    viol_mask |= in_t & ((r == 4) | (r == 7)) & (bb != ord("-"))
    viol_mask |= in_t & (r == 10) & (bb != ord("T")) & (bb != ord("t"))
    viol_mask |= in_t & ((r == 13) | (r == 16)) & (bb != ord(":"))
    has_frac = jnp.sum(jnp.where(in_t & (r == 19), bb.astype(_I32), 0),
                       axis=1) == ord(".")
    rd = r - 20
    frac_run = _min_where(in_t & (rd >= 0) & (rd < 10) & ~is_digit, rd, 10)
    frac_run = jnp.minimum(frac_run, jnp.maximum(tlen - 20, 0))
    frac_len = jnp.where(has_frac, frac_run, 0)
    w_frac = ((rd == 0) * 100000000 + (rd == 1) * 10000000 + (rd == 2) * 1000000
              + (rd == 3) * 100000 + (rd == 4) * 10000 + (rd == 5) * 1000
              + (rd == 6) * 100 + (rd == 7) * 10 + (rd == 8))
    nanos = jnp.sum(jnp.where(in_t & (rd >= 0) & (rd < frac_len[:, None]),
                              dig * w_frac, 0), axis=1)
    opos = jnp.where(has_frac, 20 + frac_len, 19)
    r2 = r - opos[:, None]
    oc = jnp.sum(jnp.where(in_t & (r2 == 0), bb.astype(_I32), 0), axis=1)
    is_zulu = (oc == ord("Z")) | (oc == ord("z"))
    is_num_off = (oc == ord("+")) | (oc == ord("-"))
    off_ok = jnp.where(is_zulu, tlen == opos + 1, True)
    viol_mask |= (in_t & ((r2 == 1) | (r2 == 2) | (r2 == 4) | (r2 == 5))
                  & ~is_digit & is_num_off[:, None])
    viol_mask |= (in_t & (r2 == 3) & (bb != ord(":")) & is_num_off[:, None])
    struct_viol = jnp.any(viol_mask, axis=1)
    # oh|om packed in one 8-bit-field sum (fold: was 2 reductions)
    w_ohm = jnp.sum(dz * ((r2 == 1) * 10 + (r2 == 2)
                          + (((r2 == 4) * 10 + (r2 == 5)) << 8)), axis=1)
    oh = w_ohm & 255
    om = (w_ohm >> 8) & 255
    off_ok &= jnp.where(is_num_off,
                        (tlen == opos + 6) & (oh <= 23) & (om <= 59),
                        True)
    rfc_ok = (
        (tlen >= 20) & ~struct_viol & (is_zulu | is_num_off) & off_ok
        & (month >= 1) & (month <= 12) & (day >= 1)
        & (day <= _days_in_month(year, month))
        & (hour <= 23) & (minute <= 59) & (sec <= 59)
        & jnp.where(has_frac, (frac_len >= 1) & (frac_len <= 9), True)
    )
    off_secs = jnp.where(is_num_off,
                         jnp.where(oc == ord("-"), -1, 1) * (oh * 3600 + om * 60),
                         0)
    days = _days_from_civil(year, month, day)
    sod = hour * 3600 + minute * 60 + sec

    # ts_kind: 0 = rfc3339 (days/sod/off/nanos valid), 1 = float span
    # (host parses the span), 2 = neither -> row fallback
    ts_kind = jnp.where(rfc_ok, 0, jnp.where(float_ok, 1, 2))
    ok &= ts_kind < 2

    return {
        "ok": ok,
        "has_high": jnp.any((bb >= 128) & valid, axis=1),
        "n_parts": n_parts,
        "part_start": part_start,
        "part_end": part_end,
        "colon_pos": jnp.where(has_colon, colon_pos, -1),
        "time_pos": time_pos, "host_pos": host_pos,
        "msg_pos": msg_pos, "level_pos": level_pos,
        "host_start": host_start, "host_end": host_end,
        "msg_start": msg_start, "msg_end": msg_end,
        "level_val": jnp.where(has_level, level_val, -1),
        "ts_kind": ts_kind,
        "ts_start": ts_s, "ts_end": ts_e,
        "days": days, "sod": sod, "off": off_secs, "nanos": nanos,
        "ts_hi": ts_hi, "ts_lo": ts_lo, "ts_meta": ts_meta,
    }


@functools.partial(jax.jit, static_argnames=("max_parts", "demand"))
def decode_ltsv_jit(batch, lens, max_parts=DEFAULT_MAX_PARTS, demand=None):
    """``demand`` (static frozenset): keep only the channels the
    consumer reads so XLA dead-code-eliminates the rest — the fused
    ltsv→GELF route drops e.g. the raw ts span channels."""
    out = decode_ltsv(batch, lens, max_parts=max_parts)
    if demand is not None:
        out = {k: v for k, v in out.items() if k in demand}
    return out


def decode_ltsv_submit(batch, lens, sharded=None):
    """Asynchronous dispatch (pair with decode_ltsv_fetch) — the ltsv
    leg of the block pipeline's double buffering.  ``sharded`` swaps in
    the multi-chip mesh kernel (parallel.mesh.ShardedDecode).  The
    handle carries the uploaded device arrays so the device-side encode
    (tpu/device_ltsv.py) reuses them without a re-upload."""
    import jax.numpy as jnp

    if sharded is not None:
        b, ln = sharded.put(batch, lens)
        return sharded.fn(b, ln), b, ln
    from .aot import decode_call

    b, ln = jnp.asarray(batch), jnp.asarray(lens)
    # zero-JIT boot: a loaded AOT artifact replaces the trace+compile
    out = decode_call("ltsv", (b, ln))
    if out is None:
        out = decode_ltsv_jit(b, ln)
    return out, b, ln


def decode_ltsv_fetch(handle):
    import numpy as np

    return {k: np.asarray(v) for k, v in handle[0].items()}
