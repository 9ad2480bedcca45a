r"""Shared JSON structural index (simdjson stage 1, arxiv 1902.08318).

ONE implementation of the batched flat-JSON tokenizer both JSON paths
ride — ``tpu/gelf.py`` (GELF's flat-JSON screen) and ``tpu/jsonl.py``
(generic JSON-lines) — so the quote-parity string masking, the
bit-packed backslash ladder, and the packed-ordinal span extractors are
single-sourced and the two decoders cannot drift.

Stage-1 plan (all branchless, no gathers — see tpu/gelf.py's module
docstring for the scan-free design history):

- byte classification: whitespace / quote / backslash / structural
  planes straight off the [N, L] batch;
- quote parity classifies in/out-of-string (escaped quotes via the
  shared bit-packed backslash ladder, ``rfc5424._esc_parity``);
- bounded-window lookarounds (one packed reduce-window each way)
  answer "previous/next significant byte" for token-role assignment;
- key/value spans extract via packed-ordinal matmul sums keyed on the
  key-open ordinal plane (``rfc5424.extract_by_ord``).

``nested`` extends the index with a **structural-character depth
channel** (cumsum of opens minus closes outside strings): top-level
container values (``"k": {...}`` / ``"k": [...]``) become spans of
class VT_OBJECT / VT_ARRAY whose extents pair the depth-1→2 open with
the matching 2→1 close by key ordinal — contents nest arbitrarily up
to ``nested`` levels; deeper rows flag to the scalar oracle.  With
``nested=0`` (the GELF screen) any bracket outside a string
disqualifies the row, preserving the flat-only contract byte for byte.

Anything structurally surprising (stray tokens, >1 value per key,
window overflow, unbalanced anything) flags the row ``ok=False`` so the
caller's scalar oracle keeps observable output byte-identical.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .rfc5424 import (
    _bitpack32,
    _esc_parity,
    _scan_ordinals,
    _slot_geometry,
    _shift_left,
    _shift_right,
    extract_by_ord,
    extract_counts_by_ord,
)

WS_WINDOW = 8
_I32 = jnp.int32

# value token classes.  VT_OBJECT/VT_ARRAY only appear with nested > 0.
VT_STRING, VT_NUMBER, VT_TRUE, VT_FALSE, VT_NULL = 0, 1, 2, 3, 4
VT_OBJECT, VT_ARRAY = 5, 6


def structural_index(batch: jnp.ndarray, lens: jnp.ndarray,
                     max_fields: int, scan_impl: str, extract_impl: str,
                     nested: int = 0) -> Dict[str, jnp.ndarray]:
    """Tokenize a packed [N, L] batch of one-JSON-object lines into
    per-key span channels (see module docstring).  Returns the channel
    dict shared by the GELF and JSON-lines decoders."""
    N, L = batch.shape
    lens = lens.astype(_I32)
    iota = jax.lax.broadcasted_iota(_I32, (N, L), 1)
    valid = iota < lens[:, None]
    # uint8 byte plane (see rfc5424.py): widen inside consumer fusions
    bb = jnp.where(valid, batch, jnp.asarray(0, batch.dtype))

    is_ws = ((bb == 32) | (bb == 9) | (bb == 10) | (bb == 13)) & valid
    nonws = valid & ~is_ws

    # ---- escaped quotes & string parity ---------------------------------
    is_bs = (bb == 92) & valid
    quote = (bb == ord('"')) & valid
    escaped, cap_words = _esc_parity(is_bs)
    real_q = quote & ~escaped
    cap_viol = jnp.any((cap_words & _bitpack32(quote)) != 0, axis=1)
    (q_incl,) = _scan_ordinals([real_q], scan_impl)
    q_excl = q_incl - real_q.astype(q_incl.dtype)
    outside = (q_excl & 1) == 0
    open_q = real_q & outside
    close_q = real_q & ~outside
    inside_str = (~outside) & valid
    ok = ~cap_viol

    # ---- bounded-window lookarounds -------------------------------------
    # ptb/ntb: byte of the nearest non-ws position within WS_WINDOW
    # before/after each position (0 when none in window).  Rows with a
    # longer outside-string whitespace run fall back, so "not found in
    # window" can never silently mean "found nothing relevant".  One
    # packed (position << 8 | byte) reduce-window pass each way.
    bi32 = bb.astype(_I32)
    pv = jnp.where(nonws, (iota << 8) | bi32, -1)
    rw_p = jax.lax.reduce_window(pv, jnp.int32(-1), jax.lax.max,
                                 (1, WS_WINDOW), (1, 1),
                                 ((0, 0), (WS_WINDOW - 1, 0)))
    ptb_w = _shift_right(rw_p, 1, -1)
    ptb = jnp.where(ptb_w >= 0, ptb_w & 255, 0)
    _BIG = jnp.int32(1 << 30)
    nv = jnp.where(nonws, (iota << 8) | bi32, _BIG)
    rw_n = jax.lax.reduce_window(nv, _BIG, jax.lax.min,
                                 (1, WS_WINDOW), (1, 1),
                                 ((0, 0), (0, WS_WINDOW - 1)))
    ntb_w = _shift_left(rw_n, 1, _BIG)
    ntb = jnp.where(ntb_w < _BIG, ntb_w & 255, 0)

    # ws run > WS_WINDOW outside strings: a windowed count hitting W+1
    # (edge padding contributes 0, so short runs at the line start can
    # never flag)
    run = is_ws & outside
    rw_run = jax.lax.reduce_window(run.astype(_I32), jnp.int32(0),
                                   jax.lax.add, (1, WS_WINDOW + 1), (1, 1),
                                   ((0, 0), (WS_WINDOW, 0)))
    # every row-disqualifying plane ORs into one mask reduced by a
    # single any at the end
    viol = rw_run == WS_WINDOW + 1

    # ---- structure: braces, brackets, depth -----------------------------
    lb = (bb == ord("{")) & outside
    rb = (bb == ord("}")) & outside
    lsb = (bb == ord("[")) & outside
    rsb = (bb == ord("]")) & outside
    if nested:
        open_br = lb | lsb
        close_br = rb | rsb
        cum_open, cum_close = _scan_ordinals([open_br, close_br],
                                             scan_impl)
        # inclusive depth: an open counts at its own position, a close
        # uncounts at its own — so the top-level '{' sits at depth 1,
        # a nested open at >= 2, a top-level-value close back at 1,
        # and the final '}' at 0
        depth = cum_open.astype(_I32) - cum_close.astype(_I32)
        viol |= (depth < 0) & valid
        max_depth = jnp.max(jnp.where(valid, depth, 0), axis=1)
        ok &= max_depth <= 1 + nested
        top = depth == 1
        # exactly one depth-1 '{' (the object) and one depth-0 '}'
        # (its close); '['/']' may only appear inside a value
        lb_top = lb & top
        rb_end = rb & (depth == 0)
        viol |= lsb & top
        # ends of top-level container values; like a string value
        # close, the next significant byte must be ',' or '}'
        nested_close = close_br & top & ~rb_end
        viol |= nested_close & (ntb != ord(",")) & (ntb != ord("}"))
        # a depth-1→2 open is only legal in value position
        cont_start = open_br & (depth == 2)
        is_cont_val = cont_start & (ptb == ord(":"))
        viol |= cont_start & ~is_cont_val
    else:
        depth = None
        top = outside
        lb_top, rb_end = lb, rb
        viol |= (lsb | rsb)
        nested_close = jnp.zeros_like(lb)
        is_cont_val = jnp.zeros_like(lb)
    # first/last non-ws position with an is-it-the-brace tag packed into
    # the reduction word: first significant byte must be the object
    # open, last must be its close
    wf = jnp.min(jnp.where(nonws, 2 * iota + (~lb).astype(_I32),
                           2 * L + 2), axis=1)
    first_is_lb = (wf & 1) == 0
    first_nonws = wf >> 1
    wl = jnp.max(jnp.where(nonws, 2 * iota + rb.astype(_I32), -1), axis=1)
    last_is_rb = (wl & 1) == 1
    last_nonws = wl >> 1
    ok &= first_is_lb & last_is_rb & (first_nonws < last_nonws)

    # ---- token roles (elementwise, top level only) ----------------------
    # an open quote sits at an outside-string (even-parity) position;
    # a CLOSE quote is inside its own string by parity, so its
    # top-levelness comes from the depth channel alone (depth never
    # changes inside a string — brackets there are parity-masked out)
    if nested:
        top_open_q = open_q & top
        top_close_q = close_q & (depth == 1)
    else:
        top_open_q = open_q
        top_close_q = close_q
    if nested:
        # quotes inside nested containers (depth >= 2) carry no
        # top-level role; an outside-string quote at depth <= 0 sits
        # before the object open / after its close — structurally junk
        viol |= open_q & ~top & (depth < 2)
    is_key_open = top_open_q & ((ptb == ord("{")) | (ptb == ord(",")))
    is_val_open = top_open_q & (ptb == ord(":"))
    viol |= top_open_q & ~is_key_open & ~is_val_open
    is_key_close = top_close_q & (ntb == ord(":"))
    is_val_close = top_close_q & ~is_key_close
    # a value close must be followed by ',' or '}'
    viol |= is_val_close & (ntb != ord(",")) & (ntb != ord("}"))

    colon_out = (bb == ord(":")) & top & valid
    comma_out = (bb == ord(",")) & top & valid
    # every comma introduces another key (next non-ws is a quote)
    viol |= comma_out & (ntb != ord('"'))

    key_ord, kc_ord = _scan_ordinals(
        [is_key_open, is_key_close], scan_impl)
    # row counts ride packed sums, as many per-count fields per i32
    # word as L allows; the ordinal-plane maxes equal plain mask counts
    # because the ordinals are inclusive cumsums
    cbits, per, cmask = _slot_geometry(L)

    def packed_counts(masks):
        outs = []
        for base in range(0, len(masks), per):
            grp = masks[base:base + per]
            acc = grp[0].astype(_I32)
            for s, m in enumerate(grp[1:], 1):
                acc = acc + (m.astype(_I32) << (cbits * s))
            word = jnp.sum(acc, axis=1)
            for s in range(len(grp)):
                outs.append((word >> (cbits * s)) & cmask)
        return outs

    count_masks = [real_q, lb_top, rb_end, is_key_open, is_key_close,
                   colon_out, comma_out]
    if nested:
        count_masks += [lb | lsb, rb | rsb]
        (n_quotes, lbc, rbc, n_keys, n_kc, n_colons, n_commas,
         n_open, n_close) = packed_counts(count_masks)
        ok &= n_open == n_close  # balanced brackets
    else:
        n_quotes, lbc, rbc, n_keys, n_kc, n_colons, n_commas = \
            packed_counts(count_masks)
    ok &= (n_quotes & 1) == 0  # every string closed
    ok &= (lbc == 1) & (rbc == 1)
    ok &= n_kc == n_keys
    ok &= n_keys <= max_fields
    ok &= n_colons == n_keys
    ok &= n_commas == jnp.maximum(n_keys - 1, 0)

    # ---- literal/number runs --------------------------------------------
    structural = (colon_out | comma_out | lb | rb | real_q)
    if nested:
        structural = structural | lsb | rsb
        is_lit = nonws & outside & top & ~structural
    else:
        is_lit = nonws & outside & ~structural
    lit_start = is_lit & ~_shift_right(is_lit, 1, False)
    lit_end_m = is_lit & ~_shift_left(is_lit, 1, False)
    # nothing significant may precede the first key
    viol |= is_lit & (key_ord == 0)
    # backslashes are only legal inside strings; a bs "outside" (per
    # possibly-garbled parity) sends the row to the oracle, which also
    # shields the parity math itself from junk input
    viol |= is_bs & outside
    ok &= ~jnp.any(viol, axis=1)

    # number/literal value start: a literal-run start whose previous
    # non-ws byte is ':'
    is_lit_val = lit_start & (ptb == ord(":"))
    is_val_start = is_val_open | is_lit_val | is_cont_val
    # literal tokens match against a packed next-4-bytes word; high
    # input bytes overflow into the sign bit deterministically and can
    # never collide with the ASCII token constants
    w2 = (bi32 << 8) | _shift_left(bi32, 1, 0)
    w4 = (w2 << 16) | _shift_left(w2, 2, 0)
    true_at = w4 == int.from_bytes(b"true", "big")
    null_at = w4 == int.from_bytes(b"null", "big")
    false_at = (w4 == int.from_bytes(b"fals", "big")) & \
        (_shift_left(bi32, 4, 0) == ord("e"))
    is_num0 = ((bb >= 48) & (bb <= 57)) | (bb == ord("-"))
    vclass = jnp.where(
        is_val_open, 1 + VT_STRING,
        jnp.where(true_at, 1 + VT_TRUE,
                  jnp.where(false_at, 1 + VT_FALSE,
                            jnp.where(null_at, 1 + VT_NULL,
                                      jnp.where(is_num0, 1 + VT_NUMBER,
                                                0)))))
    if nested:
        vclass = jnp.where(
            is_cont_val,
            jnp.where(bb == ord("{"), 1 + VT_OBJECT, 1 + VT_ARRAY),
            vclass)

    # ---- per-key extraction (packed-sum words) --------------------------
    F = max_fields
    key_open_pos = extract_by_ord(is_key_open, key_ord, iota, F, L,
                                  extract_impl)
    key_close_pos = extract_by_ord(is_key_close, kc_ord, iota, F, L,
                                   extract_impl)
    # value position and class share one extraction word per slot: the
    # class rides bits above the position field (fill L keeps the class
    # field 0; classes span 1..7, exactly the 3-bit field)
    pbits = max(10, int(L + 1).bit_length())
    vs_packed = extract_by_ord(is_val_start, key_ord,
                               iota | (vclass << pbits), F, L,
                               extract_impl, slot_bits=pbits + 3)
    val_start_pos = vs_packed & ((1 << pbits) - 1)
    val_class1 = vs_packed >> pbits
    val_close_pos = extract_by_ord(is_val_close, key_ord, iota, F, L,
                                   extract_impl)
    lit_end_pos = extract_by_ord(lit_end_m, key_ord, iota, F, L,
                                 extract_impl)
    # exactly one value token per key: a string close, a literal run,
    # or (nested mode) a container open.  Key ordinals are constant
    # across a container's interior — quotes/commas/colons there sit at
    # depth >= 2 and never open a new top-level key — so the close
    # extraction below keys on the same ordinal as its open.
    val_token_m = is_val_close | lit_start
    if nested:
        val_token_m = val_token_m | is_cont_val
    val_tokens = extract_counts_by_ord(val_token_m, key_ord, F,
                                       extract_impl)
    esc_count = extract_counts_by_ord(is_bs & inside_str, key_ord, F,
                                      extract_impl)

    field_valid = (jnp.arange(F, dtype=_I32)[None, :] < n_keys[:, None])
    ok &= jnp.all(jnp.where(field_valid, val_tokens == 1,
                            val_tokens == 0), axis=1)
    ok &= jnp.all(jnp.where(field_valid, val_class1 >= 1, True), axis=1)
    val_type = jnp.where(field_valid, val_class1 - 1, -1)

    # per-key ordering sanity: open < close < value start
    ok &= jnp.all(jnp.where(field_valid,
                            (key_open_pos < key_close_pos)
                            & (key_close_pos < val_start_pos), True),
                  axis=1)
    # extraction-collision guard: multiple val-starts per key would
    # corrupt the packed sums — val_tokens==1 bounds val_close/lit
    # runs/container opens, and >1 val_start implies >1 of those (the
    # former is bounded; a second val_open implies a second ':' which
    # the colon count bounds)

    # string values: close quote; containers: matching close bracket;
    # literals: last run byte + 1
    is_string = val_type == VT_STRING
    if nested:
        cont_close_pos = extract_by_ord(nested_close, key_ord, iota, F,
                                        L, extract_impl)
        is_cont = (val_type == VT_OBJECT) | (val_type == VT_ARRAY)
        val_end = jnp.where(
            is_string, val_close_pos,
            jnp.where(is_cont, cont_close_pos + 1, lit_end_pos + 1))
        ok &= jnp.all(jnp.where(field_valid & is_cont,
                                cont_close_pos > val_start_pos, True),
                      axis=1)
    else:
        val_end = jnp.where(is_string, val_close_pos, lit_end_pos + 1)
    val_end = jnp.minimum(val_end, lens[:, None])
    # literal token length must match exactly (rejects "truex")
    lit_len = jnp.where(val_type == VT_TRUE, 4,
                        jnp.where(val_type == VT_FALSE, 5,
                                  jnp.where(val_type == VT_NULL, 4, -1)))
    ok &= jnp.all(jnp.where(field_valid & (lit_len > 0),
                            val_end - val_start_pos == lit_len, True),
                  axis=1)
    # string values must close after they open
    ok &= jnp.all(jnp.where(field_valid & is_string,
                            val_close_pos > val_start_pos, True), axis=1)

    esc_flag = (esc_count > 0) & field_valid

    return {
        "ok": ok,
        # n_fields stays un-zeroed on not-ok rows so the fetch-side
        # rescue can screen precisely; every consumer gates on ok
        # before reading it
        "n_fields": n_keys,
        "key_start": key_open_pos + 1, "key_end": key_close_pos,
        "val_start": jnp.where(is_string, val_start_pos + 1,
                               val_start_pos),
        "val_end": val_end,
        "val_type": val_type,
        "key_esc": esc_flag, "val_esc": esc_flag & is_string,
    }
