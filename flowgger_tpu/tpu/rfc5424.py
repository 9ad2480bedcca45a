r"""Columnar RFC5424 decoder: the TPU-native replacement for the
reference's per-line parser (rfc5424_decoder.rs:17-242).

Grammar recap (scalar spec: flowgger_tpu/decoders/rfc5424.py):
``[BOM]<PRI>1 TS HOST APP PROCID MSGID ( - | [id k="v" ...]+ ) [msg]``

Design rule learned from TPU profiling: **no gathers**.  XLA lowers
dynamic gathers (``take_along_axis``/``jnp.take``) to near-serial code on
TPU (measured ~650ms for one [N,L] pack gather at N=256k vs ~5ms for a
full cumulative scan), so every "value at computed position" here is
expressed with primitives the VPU executes wide:

- masked min-reductions ``min(where(mask & ord==k, iota<<SHIFT|payload))``
  extract the k-th delimiter position *and* its local context in one
  reduction — context bits (preceding byte class, run starts, escape
  counts) are packed into the low bits of the minimized value;
- value-dependent lookback ("the byte before this name run") rides along
  a ``cummax`` of ``pos<<8 | byte`` over non-name positions;
- fixed-layout fields (PRI digits, the RFC3339 timestamp) are parsed by
  weighting each byte with a function of its *field-relative offset*
  ``r = iota - field_start`` and summing — never by slicing a window.

Second rule, from live-chip profiling: **scans are the cost model** —
one [1M,256] i32 cumsum/cummax costs ~22ms on v5e while a group of
sibling masked reductions fuses to ~10ms, so the decode runs on two
scan channels, both lowered as MXU matmuls against a triangular ones
matrix (see _scan_ordinals): spaces+quotes packed into one, brackets in
the other.  Backslash-run parity is a bounded bit-packed shifted-AND
ladder (no scan), and the name lookback is per-pair fused masked
max-reductions instead of a cummax.

Everything else is elementwise/reduction arithmetic: prefix parity of
real quotes for in/out-of-value classification, Hinnant civil-date math
in int32 (the identical formula to utils/timeparse.py so the final f64
is bit-equal).

Any deviation from the fast-path grammar (bogus quotes, empty PRI, nil
timestamps, >max_sd blocks, >max_pairs pairs...) sets ``ok=False`` for
that row only — the host re-runs the scalar oracle on it, keeping
observable output byte-identical (differential-tested in
tests/test_tpu_rfc5424.py).

Returned spans are byte offsets relative to each row.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

DEFAULT_MAX_LEN = 512
DEFAULT_MAX_SD = 4
# two-tier pair budget: the common-case kernel extracts 6 pairs (every
# extract channel costs ceil(max_pairs/3) reduction passes, so a small
# budget is most of the win of the round-2 pass-count rework); rows with
# more pairs re-dispatch to a wider second-tier kernel compiled lazily,
# and only rows beyond the rescue budget fall back to the scalar oracle
DEFAULT_MAX_PAIRS = 6
RESCUE_MAX_PAIRS = 16
# backslash runs are resolved by a bounded shifted-AND ladder instead of
# a scan; a run of >= ESC_RUN_CAP backslashes feeding a quote sends the
# row to the scalar oracle (exact semantics preserved via fallback)
ESC_RUN_CAP = 16

_I32 = jnp.int32


def _min_where(mask, packed, notfound):
    """Per-row min of ``packed`` where mask, else ``notfound``."""
    return jnp.min(jnp.where(mask, packed, notfound), axis=1)


def _at(iota, pos, values, default=0):
    """values[n, pos[n]] as a masked reduction (no gather): pos is [N].
    (The rfc5424 kernel folds its own uses into packed sum words; the
    ltsv/rfc3164/gelf kernels still use this directly.)"""
    hit = iota == pos[:, None]
    return jnp.max(jnp.where(hit, values, default), axis=1)


def _days_from_civil(y, m, d):
    y = y - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _days_in_month(y, m):
    # arithmetic form (no table lookup — gathers are banned): 31 for odd
    # months through July and even months from August, 30 otherwise,
    # February special-cased
    is31 = jnp.where(m >= 8, (m % 2) == 0, (m % 2) == 1)
    base = jnp.where(is31, 31, 30)
    leap = (y % 4 == 0) & ((y % 100 != 0) | (y % 400 == 0))
    return jnp.where(m == 2, jnp.where(leap, 29, 28), base)


def _shift_right(arr, k, fill):
    """arr shifted right by k along axis 1 (prepending fill)."""
    return jnp.pad(arr[:, :-k], ((0, 0), (k, 0)), constant_values=fill)


def _shift_left(arr, k, fill):
    return jnp.pad(arr[:, k:], ((0, 0), (0, k)), constant_values=fill)


def _scan_ordinals(channels, impl: str):
    """Inclusive prefix sums (ordinals) of bool channels along axis 1.

    ``impl='mm'`` (the TPU path) computes each scan as a matmul against
    a triangular ones matrix — the MXU runs [1M,256]@[256,256] in ~1ms
    of FLOPs where a VPU log-shift cumsum pays ~8 materialized [N,L]
    passes (measured 8.8ms vs 21.8ms on v5e; two channels share one f32
    matmul via slot packing, 9.5ms).

    Exactness of the packed f32 path: channels MUST be pairwise
    disjoint (at most one set per position) — element values are then
    {0, 1, 2**bits}, all exactly representable even after the TPU's
    default-precision bf16 input truncation, and the MXU's f32
    accumulator keeps sums <= 2**(2*bits) <= 2**24 exact.  Packing
    applies for bits <= 12, i.e. L <= 4094; wider geometries use one
    int8 matmul per channel (i32 accumulate, exact for any mask).
    ``impl='lax'`` (the CPU path) is bit-packed i32 cumsums."""
    L = channels[0].shape[1]
    bits = max(10, int(L + 1).bit_length())
    # ordinal channels are re-read by every downstream extraction word,
    # so they come back as int16 where L allows (ordinals are bounded by
    # L, and the guard keeps L < 32000 < 2**15-1) — halving the HBM
    # bytes of the hottest reads in the kernel.
    out_t = jnp.int16 if L < 32000 else _I32
    if impl != "mm":
        mask = (1 << bits) - 1
        per = max(1, 31 // bits)
        outs = []
        for base in range(0, len(channels), per):
            grp = channels[base:base + per]
            word = grp[0].astype(_I32)
            for s, ch in enumerate(grp[1:], 1):
                word = word + (ch.astype(_I32) << (bits * s))
            scanned = jnp.cumsum(word, axis=1)
            for s in range(len(grp)):
                outs.append(((scanned >> (bits * s)) & mask).astype(out_t))
        return outs
    iota_l = jnp.arange(L, dtype=_I32)
    tri_f = (iota_l[:, None] <= iota_l[None, :]).astype(jnp.float32)
    tri_i = tri_f.astype(jnp.int8)
    pack2 = 2 * bits <= 24
    outs = []
    base = 0
    while base < len(channels):
        if pack2 and base + 1 < len(channels):
            packed = (channels[base].astype(jnp.float32)
                      + channels[base + 1].astype(jnp.float32) * float(1 << bits))
            s = jax.lax.dot_general(
                packed, tri_f, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(_I32)
            outs.append((s & ((1 << bits) - 1)).astype(out_t))
            outs.append((s >> bits).astype(out_t))
            base += 2
        else:
            s = jax.lax.dot_general(
                channels[base].astype(jnp.int8), tri_i,
                (((1,), (0,)), ((), ())), preferred_element_type=_I32)
            outs.append(s.astype(out_t))
            base += 1
    return outs


def _bitpack32(plane):
    """[N, L] bool -> [N, ceil(L/32)] uint32, bit j of word w = plane[:,
    32w+j].  The reshape/broadcast form beats 32 strided slices on TPU:
    a stride-32 minor-axis slice still reads every 128-lane tile, so the
    slice formulation pays ~32 reads of the plane (measured +13ms on the
    full kernel)."""
    N, L = plane.shape
    W = (L + 31) // 32
    if W * 32 != L:
        plane = jnp.pad(plane, ((0, 0), (0, W * 32 - L)))
    lane = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(
        plane.reshape(N, W, 32).astype(jnp.uint32) << lane[None, None, :],
        axis=2)


def _bitunpack32(words, L):
    """Inverse of _bitpack32: [N, W] uint32 -> [N, L] bool."""
    N, W = words.shape
    lane = jnp.arange(32, dtype=jnp.uint32)
    b = ((words[:, :, None] >> lane[None, None, :]) & 1) != 0
    return b.reshape(N, W * 32)[:, :L]


def _esc_parity(is_bs):
    """Backslash-run parity without a scan: ``escaped[i]`` <=> the run of
    backslashes ending at ``i-1`` has odd length (exact for runs <
    ESC_RUN_CAP).

    Returns ``(escaped, cap_words)``: ``cap_words`` is the
    [N, ceil(L/32)] packed uint32 stream (same bit layout as
    ``_bitpack32``) of positions whose run reached the cap, for the
    caller to AND against a packed quote plane.

    The ladder XORs nested run-indicators ``a_k = bs at i-1..i-k``.  The
    [N, L] bool planes are bit-packed into [N, L/32] uint32 lanes first
    — the 15 shifted ANDs then touch 1/32nd of the bytes."""
    N, L = is_bs.shape
    packed = _bitpack32(is_bs)

    def sr(w, k):
        # shift right in *position* space by k (1 <= k <= 31): bit j of
        # word w comes from bit j-k, borrowing the top of word w-1
        prev = jnp.pad(w[:, :-1], ((0, 0), (1, 0)))
        return (w << jnp.uint32(k)) | (prev >> jnp.uint32(32 - k))

    a_k = sr(packed, 1)
    esc = a_k
    for k in range(2, ESC_RUN_CAP):
        a_k = a_k & sr(packed, k)
        esc = esc ^ a_k
    assert ESC_RUN_CAP < 32  # sr() handles shifts of 1..31 only
    cap = a_k & sr(packed, ESC_RUN_CAP)

    return _bitunpack32(esc, L), cap


def _slot_geometry(L: int):
    """Slot geometry for the bit-packed sum extraction: each i32 word
    carries as many (value+1) slots as fit in 30 bits, with slot width
    sized to the packed byte axis — 10 bits / 3 slots for the common
    L <= 1022, widening automatically for long-record configs
    (tpu_max_line_len)."""
    slot_bits = max(10, int(L + 1).bit_length())
    slots = max(1, 30 // slot_bits)
    return slot_bits, slots, (1 << slot_bits) - 1


def extract_by_ord(mask, ord_, value, K, fill, extract_impl="sum",
                   slot_bits=None):
    """out[n, k] = ``value`` at the position with ordinal k+1 (masked),
    else ``fill``.  The ordinal channel must hit each ordinal at most
    once per row.  Shared by every format kernel.

    - ``"sum"``: bit-packed masked sums — few wide passes, no scatter;
      the TPU path (XLA:TPU lowers scatter/gather near-serially);
    - ``"scatter"``: one scatter-min per channel — the CPU path.

    ``slot_bits`` overrides the position-sized slot geometry when the
    caller packs several small fields into one value (fewer slots per
    word, but fewer reduction words for the channel group overall)."""
    N, L = mask.shape
    if slot_bits is None:
        slot_bits, slots, slot_mask = _slot_geometry(L)
    else:
        slots = max(1, 30 // slot_bits)
        slot_mask = (1 << slot_bits) - 1
    if extract_impl == "scatter":
        # ord_ may be parity-derived and go negative before its zone;
        # gate on >= 1 so .at[] never wraps a negative column index
        big = jnp.iinfo(jnp.int32).max
        hit = mask & (ord_ >= 1)
        rows = jax.lax.broadcasted_iota(_I32, mask.shape, 0)
        cols = jnp.where(hit, jnp.minimum(ord_ - 1, K), K)
        init = jnp.full((N, K + 1), big, _I32)
        out = init.at[rows, cols].min(
            jnp.where(hit, value.astype(_I32), big))[:, :K]
        return jnp.where(out == big, fill, out)
    cols = []
    v1 = jnp.clip(value, 0, slot_mask - 1) + 1
    for base in range(0, K, slots):
        acc = jnp.where(mask & (ord_ == base + 1), v1, 0)
        for s in range(1, slots):
            if base + s < K:
                acc = acc + (jnp.where(mask & (ord_ == base + 1 + s),
                                       v1, 0) << (slot_bits * s))
        word = jnp.sum(acc, axis=1)
        for slot in range(min(slots, K - base)):
            v = (word >> (slot_bits * slot)) & slot_mask
            cols.append(jnp.where(v == 0, fill, v - 1))
    return jnp.stack(cols, axis=1)


def extract_counts_by_ord(mask, ord_, K, extract_impl="sum"):
    """out[n, k] = number of masked positions with ordinal k+1 — an
    *accumulating* variant of extract_by_ord (the mask may hit many
    positions per ordinal; each per-word slot's total is bounded by
    L < 2**slot_bits, so slots cannot carry)."""
    N, L = mask.shape
    slot_bits, slots, slot_mask = _slot_geometry(L)
    if extract_impl == "scatter":
        hit = mask & (ord_ >= 1)
        rows = jax.lax.broadcasted_iota(_I32, mask.shape, 0)
        cols = jnp.where(hit, jnp.minimum(ord_ - 1, K), K)
        init = jnp.zeros((N, K + 1), _I32)
        return init.at[rows, cols].add(hit.astype(_I32))[:, :K]
    cols = []
    for base in range(0, K, slots):
        acc = jnp.where(mask & (ord_ == base + 1), 1, 0)
        for s in range(1, slots):
            if base + s < K:
                acc = acc + (jnp.where(mask & (ord_ == base + 1 + s),
                                       1, 0) << (slot_bits * s))
        word = jnp.sum(acc, axis=1)
        for slot in range(min(slots, K - base)):
            cols.append((word >> (slot_bits * slot)) & slot_mask)
    return jnp.stack(cols, axis=1)


def decode_rfc5424(batch: jnp.ndarray, lens: jnp.ndarray,
                   max_sd: int = DEFAULT_MAX_SD,
                   max_pairs: int = DEFAULT_MAX_PAIRS,
                   scan_impl: str = None,
                   extract_impl: str = "sum") -> Dict[str, jnp.ndarray]:
    """Decode a packed ``[N, L]`` uint8 batch (jit/pjit/shard_map safe).

    ``scan_impl`` picks the prefix-scan lowering: ``"mm"`` (MXU matmul
    against a triangular ones matrix — the TPU default, ~2.4x a VPU
    cumsum) or ``"lax"`` (jnp.cumsum — the CPU default).  None resolves
    by backend.

    ``extract_impl`` picks how k-th-delimiter values come out:
    - ``"sum"``: bit-packed masked sums — few wide passes, no scatter;
      the TPU path (XLA:TPU lowers scatter/gather near-serially);
    - ``"scatter"``: one scatter-min per channel — the CPU path, where
      scatters are cheap and the [N,L] reduction passes are what hurts
      (~70x faster than "sum" on the CPU backend).
    Identical outputs; differential-tested against each other."""
    if scan_impl is None:
        scan_impl = best_scan_impl()
    N, L = batch.shape

    def _extract(mask, ord_, value, K, fill):
        return extract_by_ord(mask, ord_, value, K, fill, extract_impl)
    lens = lens.astype(_I32)
    iota = jax.lax.broadcasted_iota(_I32, (N, L), 1)
    bu = batch  # uint8 view for comparisons (half the HBM traffic of i32)
    valid = iota < lens[:, None]
    bb = jnp.where(valid, bu, jnp.asarray(0, bu.dtype))
    # uint8 byte plane: every mask read touches 1 byte/position; sites
    # that need arithmetic widen inside their own fusion (free VPU work
    # vs doubled HBM traffic for a materialized int16 plane)
    is_digit = (bb >= 48) & (bb <= 57)
    dig = bb.astype(_I32) - 48

    # ---- BOM (rs:57-72) --------------------------------------------------
    bom = (
        (lens >= 3)
        & (bb[:, 0] == 0xEF) & (bb[:, 1] == 0xBB) & (bb[:, 2] == 0xBF)
    )
    start0 = jnp.where(bom, 3, 0).astype(_I32)
    first_ch = jnp.where(bom, bb[:, 3] if L > 3 else 0, bb[:, 0])
    ok = first_ch == ord("<")

    # ---- scan budget ------------------------------------------------------
    # Scans are the kernel's dominant cost on TPU (measured ~22ms per
    # [1M,256] i32 cumsum/cummax vs ~10ms for a group of fused masked
    # reductions — tools/profile_kernel.py / profile_r3.py), so the
    # whole decode runs on TWO scan channels, both MXU matmuls:
    #   1: ordinals of (is_sp, real_q) — one packed scan (space + quote)
    #   2: ordinals of rbrack — its mask needs stage 1's quote parity
    # The backslash-parity cummax is replaced by a bounded shifted-AND
    # ladder (exact for runs < ESC_RUN_CAP; longer runs before a quote
    # fall back to the scalar oracle); open/close-quote ordinals are
    # parity-DERIVED from scan 1 (zone quotes strictly alternate), with
    # their zone from a min-reduction SD terminator instead of the
    # chain-walk sd_end so no scan has to wait on the bracket chain; the
    # name lookback that used to be scan 3 (a cummax) is now max_pairs
    # fused masked max-reductions keyed on the extracted open-quote
    # positions (see the pair-extraction section).

    # ---- escape parity (bounded bit-packed ladder, no scan) --------------
    # escaped[i] <=> the backslash run ending at i-1 has odd length
    # (exact while run < ESC_RUN_CAP; cap hits feeding a quote send the
    # row to the scalar oracle — semantics preserved via fallback).
    is_bs = (bb == 92) & valid
    escaped, cap_words = _esc_parity(is_bs)

    # ---- stage B scan: space ordinals + quote parity ----------------------
    is_sp = (bb == 32) & valid
    quote = (bb == ord('"')) & valid
    real_q_all = quote & ~escaped
    # the cap-hit stream never leaves bit-packed form — AND against the
    # packed quote plane and fold the row-wise "a quote consumed an
    # unknown run parity" violation straight into ok (no [N, L] unpack
    # for a channel consumed row-wise)
    viol2d = jnp.zeros_like(quote)
    ok &= ~jnp.any((cap_words & _bitpack32(quote)) != 0, axis=1)
    sp_ord, q_incl_all = _scan_ordinals([is_sp, real_q_all], scan_impl)
    sp = _extract(is_sp, sp_ord, iota, 6, L)  # [N, 6]
    ok &= sp[:, 5] < L
    f_start = jnp.concatenate([start0[:, None], sp + 1], axis=1)  # [N,7]
    f_end = jnp.concatenate([sp, lens[:, None]], axis=1)          # [N,7]

    # ---- PRI + version (rs:74-92) ---------------------------------------
    gt = _min_where((bb == ord(">")) & (iota > start0[:, None]) & valid,
                    iota, L)
    ndig = gt - start0 - 1
    ok &= (gt < f_end[:, 0]) & (ndig >= 1) & (ndig <= 3)
    # digits weighted by 10^(gt-1-iota); non-digit in range -> violation
    e = gt[:, None] - 1 - iota
    pri_zone = (iota > start0[:, None]) & (iota < gt[:, None])
    w_pri = jnp.where(e == 0, 1, jnp.where(e == 1, 10, jnp.where(e == 2, 100, 0)))
    viol2d |= pri_zone & ~is_digit   # accumulated; reduced once at the end

    # ---- packed field sums ------------------------------------------------
    # every fixed-layout numeric field and single-position structural flag
    # comes out of three bit-packed sum reductions instead of one pass
    # each: component sums are bounded by construction (2-digit fields
    # <= 99, year <= 9999, PRI <= 999, flags are unique-position bits),
    # so the packed spans cannot carry into each other.
    ts_s = f_start[:, 1]
    tlen = f_end[:, 1] - ts_s
    r = iota - ts_s[:, None]
    in_ts = (r >= 0) & (r < tlen[:, None])
    dz = jnp.where(in_ts, dig, 0)
    rest_s = f_start[:, 6]

    # word1: year[0:14] month[14:21] day[21:28] has_frac[28] version[29]
    w1 = (
        dz * ((r == 0) * 1000 + (r == 1) * 100 + (r == 2) * 10 + (r == 3))
        + (dz * ((r == 5) * 10 + (r == 6)) << 14)
        + (dz * ((r == 8) * 10 + (r == 9)) << 21)
        + (jnp.where(in_ts & (r == 19) & (bb == ord(".")), 1, 0) << 28)
        + (jnp.where((iota == gt[:, None] + 1) & (bb == ord("1")), 1, 0) << 29)
    )
    word1 = jnp.sum(w1, axis=1)
    year = word1 & 0x3FFF
    month = (word1 >> 14) & 0x7F
    day = (word1 >> 21) & 0x7F
    has_frac = ((word1 >> 28) & 1) == 1
    ver_ok = ((word1 >> 29) & 1) == 1

    # word2: hour[0:7] minute[7:14] sec[14:21] pri[21:31]
    w2 = (
        dz * ((r == 11) * 10 + (r == 12))
        + (dz * ((r == 14) * 10 + (r == 15)) << 7)
        + (dz * ((r == 17) * 10 + (r == 18)) << 14)
        + (jnp.where(pri_zone, dig * w_pri, 0) << 21)
    )
    word2 = jnp.sum(w2, axis=1)
    hour = word2 & 0x7F
    minute = (word2 >> 7) & 0x7F
    sec = (word2 >> 14) & 0x7F
    pri = word2 >> 21

    ok &= pri <= 255
    ok &= ver_ok & (f_end[:, 0] == gt + 2)
    facility = pri >> 3
    severity = pri & 7

    digit_off = ((r >= 0) & (r <= 18) &
                 (r != 4) & (r != 7) & (r != 10) & (r != 13) & (r != 16))
    viol2d |= in_ts & digit_off & ~is_digit
    viol2d |= in_ts & ((r == 4) | (r == 7)) & (bb != ord("-"))
    viol2d |= in_ts & (r == 10) & (bb != ord("T")) & (bb != ord("t"))
    viol2d |= in_ts & ((r == 13) | (r == 16)) & (bb != ord(":"))
    ok &= tlen >= 20
    ok &= (month >= 1) & (month <= 12) & (day >= 1) & (day <= _days_in_month(year, month))
    ok &= (hour <= 23) & (minute <= 59) & (sec <= 59)

    # fractional seconds: run of digits from r==20
    rd = r - 20
    # first non-digit offset in [0, 10) == run length (capped)
    frac_run = _min_where(in_ts & (rd >= 0) & (rd < 10) & ~is_digit,
                          rd, 10)
    frac_run = jnp.minimum(frac_run, jnp.maximum(tlen - 20, 0))
    frac_len = jnp.where(has_frac, frac_run, 0)
    ok &= jnp.where(has_frac, (frac_len >= 1) & (frac_len <= 9), True)
    w_frac = (
        (rd == 0) * 100000000 + (rd == 1) * 10000000 + (rd == 2) * 1000000
        + (rd == 3) * 100000 + (rd == 4) * 10000 + (rd == 5) * 1000
        + (rd == 6) * 100 + (rd == 7) * 10 + (rd == 8) * 1
    )
    in_frac = in_ts & (rd >= 0) & (rd < frac_len[:, None])
    nanos = jnp.sum(jnp.where(in_frac, dig * w_frac, 0), axis=1)

    # offset zone at r2 = r - opos; word3 packs its digits, the
    # remaining single-position flags, and (for the common L <= 1023
    # geometry) the high-byte count that used to be its own reduction:
    # oh[0:7] om[7:14] zulu[14] plus[15] minus[16] dash[17] sd_open[18]
    # high_count[19:29]
    opos = jnp.where(has_frac, 20 + frac_len, 19)
    r2 = r - opos[:, None]
    at_off = in_ts & (r2 == 0)
    at_rest = iota == rest_s[:, None]
    pack_high = L <= 1023  # count <= L must fit bits [19:29)
    w3 = (
        dz * ((r2 == 1) * 10 + (r2 == 2))
        + (dz * ((r2 == 4) * 10 + (r2 == 5)) << 7)
        + (jnp.where(at_off & ((bb == ord("Z")) | (bb == ord("z"))), 1, 0) << 14)
        + (jnp.where(at_off & (bb == ord("+")), 1, 0) << 15)
        + (jnp.where(at_off & (bb == ord("-")), 1, 0) << 16)
        + (jnp.where(at_rest & (bb == ord("-")), 1, 0) << 17)
        + (jnp.where(at_rest & (bb == ord("[")), 1, 0) << 18)
    )
    if pack_high:
        w3 = w3 + (jnp.where((bb >= 128) & valid, 1, 0) << 19)
    word3 = jnp.sum(w3, axis=1)
    oh = word3 & 0x7F
    om = (word3 >> 7) & 0x7F
    is_zulu = ((word3 >> 14) & 1) == 1
    neg_off = ((word3 >> 16) & 1) == 1
    is_num_off = (((word3 >> 15) & 3) != 0)
    is_dash = ((word3 >> 17) & 1) == 1
    is_sd = ((word3 >> 18) & 1) == 1

    ok &= is_zulu | is_num_off
    ok &= jnp.where(is_zulu, tlen == opos + 1, True)
    off_dig = (r2 == 1) | (r2 == 2) | (r2 == 4) | (r2 == 5)
    viol2d |= in_ts & off_dig & ~is_digit & is_num_off[:, None]
    viol2d |= in_ts & (r2 == 3) & (bb != ord(":")) & is_num_off[:, None]
    ok &= jnp.where(is_num_off,
                    (tlen == opos + 6) & (oh <= 23) & (om <= 59), True)
    off_secs = jnp.where(is_num_off,
                         jnp.where(neg_off, -1, 1) * (oh * 3600 + om * 60),
                         0)
    days = _days_from_civil(year, month, day)
    sod = hour * 3600 + minute * 60 + sec

    # ---- structured data (field 6 / "rest") ------------------------------
    ok &= rest_s < lens
    ok &= is_dash | is_sd

    in_rest = (iota >= rest_s[:, None]) & valid

    # quote parity relative to the rest zone: stage B counted *all* real
    # quotes (header fields may legally contain '"'); subtracting the
    # running count at rest_s restores the in-rest-only ordinals the
    # grammar needs — one fused reduction instead of a second scan.
    q_before_rest = jnp.max(
        jnp.where(valid & (iota < rest_s[:, None]), q_incl_all, 0), axis=1)
    q_excl = (q_incl_all - real_q_all.astype(q_incl_all.dtype)
              - q_before_rest[:, None])
    real_q = real_q_all & in_rest
    outside = (q_excl & 1) == 0
    open_q = real_q & outside
    close_q = real_q & ~outside

    prev_bb = _shift_right(bb, 1, 0)
    next_bb = _shift_left(bb, 1, 0)
    # name characters: printable 33..126 except ' " = ]'  (rs:175-179)
    is_name = (
        (bb >= 33) & (bb <= 126)
        & (bb != 34) & (bb != 61) & (bb != 93)
    )

    # structural ']' chain with payload bits:
    #   bit0: legal terminator (prev is ' ' or closing quote)
    #   bit1: next is '['   bit2: next is ' '
    prev_closeq = _shift_right(close_q, 1, False)
    rbrack = (bb == ord("]")) & outside & in_rest
    next_valid = _shift_left(valid, 1, False)
    rb_payload = (
        ((prev_bb == 32) | prev_closeq).astype(_I32)
        + ((next_bb == ord("[")) & next_valid).astype(_I32) * 2
        + ((next_bb == 32) & next_valid).astype(_I32) * 4
    )

    # ---- stage C scan: bracket + pair ordinals ---------------------------
    # brackets need a real scan (their mask depends on quote parity), but
    # open/close-quote ordinals come free from the stage-B parity: zone
    # quotes strictly alternate, so the j-th rest-quote (j = q_excl + 1)
    # is open iff q_excl is even, with oq_ord = q_excl//2 + 1 at opens,
    # cq_ord = (q_excl+1)//2 at closes — and at value-interior positions
    # (q_excl odd) the enclosing pair is (q_excl+1)//2, which is what the
    # escape-count attribution below needs.
    (rb_ord,) = _scan_ordinals([rbrack], scan_impl)
    oq_ord = (q_excl >> 1) + 1
    cq_ord = (q_excl + 1) >> 1
    # pos and payload flags ride one packed value (pos<<3 | flags, 12-bit
    # slots): 3 reduction words for the ']' chain instead of 2+2
    rb_sb = (((L << 3) | 7) + 1).bit_length()
    rb_word = extract_by_ord(rbrack, rb_ord, (iota << 3) | rb_payload,
                             max_sd + 1, L << 3, extract_impl,
                             slot_bits=rb_sb)
    rb_pos = rb_word >> 3
    rb_flags = rb_word & 7
    rb_found = rb_pos < L

    # SD terminator for the pair-ordinal zone, derived from the
    # extracted ']' columns instead of a dedicated [N, L] min-reduction:
    # the first structural ']' followed by a space or EOL.  On rows that
    # pass the chain checks below this equals the chain-walk sd_end
    # (every earlier chain ']' is followed by '[').  Rows whose first
    # terminator lies beyond the max_sd+1 extracted brackets always fail
    # the sd_count / end-flags checks below and fall back, so the
    # truncated view never changes an accepted row's zone.
    term_col = rb_found & (((rb_flags & 4) != 0)
                           | (rb_pos == (lens - 1)[:, None]))
    sd_end_zone = jnp.min(jnp.where(term_col, rb_pos, L), axis=1)
    zone_c = in_rest & (iota <= sd_end_zone[:, None]) & is_sd[:, None]
    oq_mask = open_q & zone_c
    cq_mask = close_q & zone_c

    # running AND over the (small, static) block axis
    chain_alive = ((rb_flags[:, :max_sd] & 2) != 0) & rb_found[:, :max_sd]
    sd_count_raw = jnp.ones_like(lens)
    alive = chain_alive[:, 0]
    for k in range(max_sd):
        sd_count_raw = sd_count_raw + alive.astype(_I32)
        if k + 1 < max_sd:
            alive = alive & chain_alive[:, k + 1]
    sd_count = jnp.where(is_sd, sd_count_raw, 0)
    # sd_end / flags of the terminating ']' via a small where-chain
    last_idx = jnp.clip(sd_count - 1, 0, max_sd)
    sd_end = rb_pos[:, 0]
    end_flags = rb_flags[:, 0]
    for k in range(1, max_sd + 1):
        sel = last_idx == k
        sd_end = jnp.where(sel, rb_pos[:, k], sd_end)
        end_flags = jnp.where(sel, rb_flags[:, k], end_flags)
    ok &= jnp.where(is_sd, (sd_count_raw <= max_sd) & (sd_end < L), True)

    blk_start = jnp.concatenate(
        [rest_s[:, None], rb_pos[:, :max_sd - 1] + 1], axis=1) if max_sd > 1 \
        else rest_s[:, None]
    blk_idx_valid = (jnp.arange(max_sd, dtype=_I32)[None, :]
                     < sd_count[:, None])
    blk_rb = rb_pos[:, :max_sd]

    # every block's ']' must be a legal terminator
    rb_legal = (rb_flags[:, :max_sd] & 1) != 0
    ok &= jnp.where(is_sd,
                    jnp.all(jnp.where(blk_idx_valid, rb_legal, True),
                            axis=1), True)

    # sd_id span per block: blk_start+1 .. first space (must precede ']').
    # The first space of block k is the only structural space there not
    # preceded by a close quote or another space, and its inclusive
    # bracket ordinal is k-1 — so all max_sd sid_end channels come out
    # of one packed-sum extraction instead of per-block [N, L]
    # min-reductions.  Multi-hit ordinals only occur on rows already
    # flagged by the name-run violations above (they fall back), where
    # the old per-block first-space answer was equally meaningless.
    sid_start = blk_start + 1
    prev_sp = _shift_right(is_sp, 1, False)
    sid_sp_mask = is_sp & outside & zone_c & ~prev_closeq & ~prev_sp
    sid_end = _extract(sid_sp_mask, rb_ord + 1, iota, max_sd, L)
    ok &= jnp.where(is_sd,
                    jnp.all(jnp.where(blk_idx_valid, sid_end < blk_rb, True),
                            axis=1), True)

    # pair regions: strictly between sd_id space and block ']'
    in_pair = jnp.zeros((N, L), dtype=bool)
    for k in range(max_sd):
        in_pair |= (
            (iota > sid_end[:, k:k + 1]) & (iota < blk_rb[:, k:k + 1])
            & blk_idx_valid[:, k:k + 1]
        )
    in_pair &= is_sd[:, None]
    sd_zone = in_rest & (iota <= sd_end[:, None]) & is_sd[:, None]

    # structural rules the parity model needs checked explicitly:
    viol2d |= open_q & sd_zone & (prev_bb != ord("="))
    name_struct = is_name & (bb != 32) & outside & in_pair
    prev_name = _shift_right(name_struct, 1, False)
    next_name = _shift_left(name_struct, 1, False)
    ns_mask = name_struct & ~prev_name        # name-run starts
    name_run_end = name_struct & ~next_name
    viol2d |= name_run_end & (next_bb != ord("="))
    # a pair name must be preceded by a space (the sd_id terminator or
    # the separator after the previous pair's close quote) — the byte
    # the old per-pair lookback checked
    viol2d |= ns_mask & (prev_bb != 32)
    eq_struct = (bb == ord("=")) & outside & in_pair
    next_open = _shift_left(open_q & in_pair, 1, False)
    viol2d |= eq_struct & ~next_open
    viol2d |= real_q & sd_zone & ~in_pair

    # ---- pair extraction -------------------------------------------------
    # oq_ord is parity-derived (not a cumsum), so the pair total is the
    # max ordinal over the zone's open quotes rather than a last-column
    # read of a running count
    pair_total = jnp.max(jnp.where(oq_mask, oq_ord, 0), axis=1)
    pair_count = jnp.where(is_sd, pair_total, 0)
    ok &= jnp.where(is_sd, pair_count <= max_pairs, True)

    # per-pair quantities via the dual-impl extractor
    oq_pos = _extract(oq_mask, oq_ord, iota, max_pairs, L)
    cq_pos = _extract(cq_mask, cq_ord, iota, max_pairs, L)
    # backslashes per value interior: quote-parity marks the inside of a
    # value, open-quote ordinal attributes each backslash to its pair —
    # one accumulating extract replaces the two bs-cumsum channels
    inside_val = (q_excl % 2) == 1
    val_esc_count = extract_counts_by_ord(is_bs & inside_val, oq_ord,
                                          max_pairs, extract_impl)

    pair_valid = (jnp.arange(max_pairs, dtype=_I32)[None, :]
                  < pair_count[:, None])

    # name starts: a name-run start's pair index IS the parity-derived
    # open-quote ordinal (2(k-1) zone quotes precede pair k's name, and
    # no quote sits between the name and its open quote), so the k-th
    # name start comes out of the same packed-sum extractor as the quote
    # positions — replacing the round-3 stack of max_pairs masked
    # max-reductions (one [N, L] traversal per pair) with one 2-word
    # extraction.  Rows with several runs per ordinal (malformed pairs)
    # produce garbage sums, but every such row is already flagged by the
    # name_run_end / eq_struct / prev-space violations above and falls
    # back to the scalar oracle.
    ns_pos = _extract(ns_mask, oq_ord, iota, max_pairs, L)
    oq_name_start = jnp.where(pair_valid, ns_pos, 0)

    # name sanity per extracted pair: a run was found and it is nonempty
    # ('=' sits at oq_pos-1, so the run spans [ns_pos, oq_pos-1)).
    ok &= jnp.all(jnp.where(pair_valid, ns_pos <= oq_pos - 2, True), axis=1)

    ok &= jnp.all(jnp.where(pair_valid, cq_pos > oq_pos, True), axis=1)
    name_end = oq_pos - 1  # position of '='


    # block assignment: number of block starts at or before the quote
    # (python loop over the small static block axis; no 3-D tensors)
    pair_sd = -jnp.ones_like(oq_pos)
    for k in range(max_sd):
        pair_sd = pair_sd + (blk_start[:, k:k + 1] <= oq_pos).astype(_I32)
    pair_sd = jnp.where(pair_valid, jnp.clip(pair_sd, 0, max_sd - 1), 0)

    # value escapes: backslashes strictly inside the value
    val_has_esc = val_esc_count > 0
    val_has_esc &= pair_valid & (cq_pos > oq_pos + 1)

    # ---- message span ----------------------------------------------------
    after_sd_pos = sd_end + 1
    sd_msg_ok = (after_sd_pos < lens) & ((end_flags & 4) != 0)
    ok &= jnp.where(is_sd, sd_msg_ok, True)
    msg_start = jnp.where(is_dash, rest_s + 1, after_sd_pos)

    # ---- host-assembly aux channels --------------------------------------
    # Python str whitespace over ASCII is {\t..\r, \x1c..\x1f, ' '}; these
    # three reductions let the host build output bytes without re-scanning
    # the batch (tpu/assemble.py): rstrip end of the full message, lstrip
    # start of msg, and the ASCII-purity flag that gates the fast tier.
    is_ws = ((bb >= 9) & (bb <= 13)) | ((bb >= 28) & (bb <= 32))
    non_ws = valid & ~is_ws
    trim_end = jnp.maximum(
        jnp.max(jnp.where(non_ws, iota + 1, 0), axis=1), start0)
    msg_a = _min_where(non_ws & (iota >= msg_start[:, None]), iota, L)
    msg_trim_start = jnp.minimum(msg_a, trim_end)
    if pack_high:
        has_high = ((word3 >> 19) & 0x3FF) > 0
    else:
        has_high = jnp.any((bb >= 128) & valid, axis=1)

    # single reduction over every accumulated 2-D violation
    ok &= ~jnp.any(viol2d, axis=1)

    return {
        "ok": ok,
        "bom": bom,
        "facility": facility,
        "severity": severity,
        "days": days,
        "sod": sod,
        "off": off_secs,
        "nanos": nanos,
        "host_start": f_start[:, 2], "host_end": f_end[:, 2],
        "app_start": f_start[:, 3], "app_end": f_end[:, 3],
        "proc_start": f_start[:, 4], "proc_end": f_end[:, 4],
        "msgid_start": f_start[:, 5], "msgid_end": f_end[:, 5],
        "msg_start": msg_start,
        "sd_count": sd_count,
        "sid_start": sid_start, "sid_end": sid_end,
        "pair_count": pair_count,
        "name_start": oq_name_start, "name_end": name_end,
        "val_start": oq_pos + 1, "val_end": cq_pos,
        "pair_sd": pair_sd,
        "val_has_esc": val_has_esc,
        "full_start": start0,
        "trim_end": trim_end,
        "msg_trim_start": msg_trim_start,
        "has_high": has_high,
    }


@functools.partial(jax.jit,
                   static_argnames=("max_sd", "max_pairs", "extract_impl",
                                    "demand"))
def decode_rfc5424_jit(batch, lens, max_sd=DEFAULT_MAX_SD,
                       max_pairs=DEFAULT_MAX_PAIRS, extract_impl="sum",
                       demand=None):
    """``demand`` (static frozenset of channel names, On-Demand parsing
    per arxiv 2312.17149) keeps only the channels the consumer actually
    reads: dropping a channel from the traced output makes every
    computation feeding only it dead code, so XLA never materializes the
    fields the output format elides (e.g. msgid/facility on the GELF
    route).  None = the full channel dict (host materializers)."""
    out = decode_rfc5424(batch, lens, max_sd=max_sd, max_pairs=max_pairs,
                         extract_impl=extract_impl)
    if demand is not None:
        out = {k: v for k, v in out.items() if k in demand}
    return out


_PAIR_KEYS = ("name_start", "name_end", "val_start", "val_end",
              "pair_sd", "val_has_esc")


def decode_rfc5424_submit(batch, lens, max_sd: int = DEFAULT_MAX_SD,
                          extract_impl: str = None, sharded=None):
    """Dispatch the kernel asynchronously (JAX returns futures); pair
    with ``decode_rfc5424_fetch``.  Splitting submit from fetch lets the
    batch pipeline overlap device decode of batch N with host encoding
    of batch N-1 (double buffering).  ``sharded`` (a
    parallel.mesh.ShardedDecode) swaps in the multi-chip mesh kernel."""
    from .device_common import d2h_begin, h2d

    impl = extract_impl or best_extract_impl()
    if sharded is not None:
        # the sharded fn was jitted with its own kernel params; the
        # handle must reflect those (rescue and device-encode stages
        # size their work from the handle's max_sd/impl)
        max_sd = sharded.kw.get("max_sd", DEFAULT_MAX_SD)
        impl = sharded.kw.get("extract_impl", "sum")
        batch_dev, lens_dev = sharded.put(batch, lens)
        out = sharded.fn(batch_dev, lens_dev)
    else:
        from .aot import decode_call

        batch_dev, lens_dev = h2d(batch, lens)
        # zero-JIT boot: a loaded AOT artifact replaces the trace+compile
        # (same channels, byte-identical by construction); None → jit
        out = decode_call("rfc5424", (batch_dev, lens_dev),
                          {"max_sd": max_sd, "extract_impl": impl})
        if out is None:
            out = decode_rfc5424_jit(batch_dev, lens_dev,
                                     max_sd=max_sd, extract_impl=impl)
    # every channel is wanted on the host a batch from now: their copies
    # queue behind the program here, and the fetcher finds host buffers
    d2h_begin(out)
    # the handle keeps the original *host* arrays (rescue_refetch slices
    # them without a device round-trip) plus the uploaded *device*
    # arrays so downstream device-side stages (tpu/device_gelf.py) can
    # reuse them without a re-upload
    return (out, batch, lens, max_sd, impl, batch_dev, lens_dev)


def rescue_refetch(host, batch, lens, rows_idx, field_keys, dispatch,
                   width):
    """Tier-2 rescue: re-dispatch ``rows_idx`` through a wider kernel
    (``dispatch(sub_batch, sub_lens) -> host dict``) and merge results
    back; per-field channels in ``field_keys`` widen to ``width``.
    Shared by every two-tier format kernel."""
    import numpy as np

    if not rows_idx.size:
        return host
    rows = 256
    while rows < rows_idx.size:
        rows <<= 1
    batch_np = np.asarray(batch)
    lens_np = np.asarray(lens)
    sub_b = np.zeros((rows, batch_np.shape[1]), dtype=np.uint8)
    sub_l = np.zeros(rows, dtype=lens_np.dtype)
    sub_b[:rows_idx.size] = batch_np[rows_idx]
    sub_l[:rows_idx.size] = lens_np[rows_idx]
    host2 = dispatch(sub_b, sub_l)
    merged = {}
    for k, v in host.items():
        if k in field_keys:
            wide = np.zeros((v.shape[0], width), dtype=v.dtype)
            wide[:, :v.shape[1]] = v
            wide[rows_idx] = host2[k][:rows_idx.size]
            merged[k] = wide
        else:
            v = v.copy()
            v[rows_idx] = host2[k][:rows_idx.size]
            merged[k] = v
    return merged


def _fetch_channels(out):
    """The host channels of a decode program whose copies were begun at
    its dispatch: one wait, for the program and the copies behind it."""
    from ..obs.trace import tracer as _tracer
    from .device_common import d2h_all

    if _tracer.active:
        # tracing only: tell the wait for the program from the wait for
        # its copies, which would otherwise hold both
        with _tracer.sub(_tracer.bound(), "device_wait", "fetch"):
            jax.block_until_ready(out)
    return d2h_all(out)


def decode_rfc5424_fetch(handle):
    """Block on a submitted decode and return host numpy channels,
    re-dispatching pair-overflow rows (DEFAULT_MAX_PAIRS < pairs <=
    RESCUE_MAX_PAIRS) through the wider tier-2 kernel so they stay
    on-device instead of hitting the scalar fallback.  Pair channels
    come back widened to RESCUE_MAX_PAIRS when any row needed tier 2."""
    import numpy as np

    from .device_common import d2h_begin, h2d

    out, batch, lens, max_sd, impl = handle[:5]
    host = _fetch_channels(out)
    pc = host["pair_count"]
    over = np.flatnonzero((pc > DEFAULT_MAX_PAIRS) & (pc <= RESCUE_MAX_PAIRS))

    def dispatch(sub_b, sub_l):
        # a second round over the link, inside the fetch stage: it can
        # only start once ``pair_count`` is on the host
        out2 = decode_rfc5424_jit(*h2d(sub_b, sub_l, parent="fetch"),
                                  max_sd=max_sd,
                                  max_pairs=RESCUE_MAX_PAIRS,
                                  extract_impl=impl)
        return _fetch_channels(d2h_begin(out2))

    return rescue_refetch(host, batch, lens, over, _PAIR_KEYS, dispatch,
                          RESCUE_MAX_PAIRS)


def decode_rfc5424_host(batch, lens, max_sd: int = DEFAULT_MAX_SD,
                        extract_impl: str = None):
    """Synchronous submit + fetch."""
    return decode_rfc5424_fetch(
        decode_rfc5424_submit(batch, lens, max_sd, extract_impl))


def best_scan_impl() -> str:
    """MXU matmul scans on accelerators (tri-matrix dot: 8.8ms vs 21.8ms
    per [1M,256] scan channel on v5e — the matmul trades O(L) extra
    FLOPs for ~6 fewer memory passes, a good trade only where a systolic
    array makes the FLOPs free); plain cumsum on the CPU backend.

    The platform->impl mapping is single-sourced in aot._scan_impl_for:
    the AOT builder stamps it into every fused/encode artifact key, and
    a drift between the two would make every artifact silently miss."""
    from .aot import _scan_impl_for

    return _scan_impl_for(jax.default_backend())


def best_extract_impl() -> str:
    """Bit-packed sums everywhere since the round-2 pass-count rework:
    with the 6-pair default tier the sum path's reduction count dropped
    ~2x and now beats scatter-min on the CPU backend too (measured
    1.86s vs 2.18s per 65k batch); on TPU scatters were never viable
    (XLA lowers them near-serially)."""
    return "sum"


def pack_on_device(buf: jnp.ndarray, starts: jnp.ndarray, lens: jnp.ndarray,
                   max_len: int) -> jnp.ndarray:
    """Gather a raw chunk ``uint8[B]`` into a padded ``[N, max_len]``
    batch on device.

    NOTE: XLA lowers this gather poorly on TPU (near-serial); the hot
    path packs on the host instead (tpu/pack.py pack_lines_2d).  Kept
    for the CPU backend.
    """
    idx = starts[:, None].astype(_I32) + jnp.arange(max_len, dtype=_I32)[None, :]
    mask = jnp.arange(max_len, dtype=_I32)[None, :] < lens[:, None]
    gathered = jnp.take(buf, jnp.clip(idx, 0, buf.shape[0] - 1))
    return jnp.where(mask, gathered, 0).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("max_len", "max_sd", "max_pairs"))
def decode_chunk_jit(buf, starts, lens, max_len=DEFAULT_MAX_LEN,
                     max_sd=DEFAULT_MAX_SD, max_pairs=DEFAULT_MAX_PAIRS):
    """Fused pack+decode from a raw chunk buffer (CPU-backend path)."""
    batch = pack_on_device(buf, starts, lens, max_len)
    return decode_rfc5424(batch, jnp.minimum(lens, max_len),
                          max_sd=max_sd, max_pairs=max_pairs)
