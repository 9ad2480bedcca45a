"""Device-side RFC3164→GELF encode: final framed bytes assembled on
device for the legacy-syslog fast path, compacted and fetched
output-sized (device_common machinery — same contract as device_gelf).

The rfc3164 fast-path record carries no SD, no appname/procid/msgid, an
unstripped message, and the whole line as full_message
(rfc3164_decoder.rs:31-122 lenient grammar; materialize_rfc3164.py), so
the sorted-key GELF object is eleven segments per row::

    {"full_message":F,"host":H,["level":N,]"short_message":M,
     "timestamp":T,"version":"1.1"}

with the level pair gated per row on has_pri — exactly the layout of
the host tier (encode_rfc3164_gelf_block.py), whose byte constants this
kernel shares so fallback splices can never diverge.
"""


from __future__ import annotations

# byte-identity contract (flowcheck FC03): the scalar counterpart
# this route must stay byte-identical to, and the differential
# test that enforces it
SCALAR_ORACLE = "flowgger_tpu.encoders.gelf:GelfEncoder"
DIFF_TEST = "tests/test_device_rfc3164.py::test_device_3164_matches_scalar_and_engages"

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
)
from .encode_rfc3164_gelf_block import (
    _C_HOST,
    _C_LEVEL,
    _C_OPEN,
    _C_SEVD,
    _C_SHORT_NOPRI,
    _C_SHORT_PRI,
    _C_TAIL,
    _C_TS,
)
from .rfc5424 import best_scan_impl

_I32 = jnp.int32

FALLBACK_FRAC = 0.05
DECLINE_LIMIT = 3
COOLDOWN = 16

_PARTS = {
    "open": _C_OPEN,
    "host": _C_HOST,
    "level": _C_LEVEL,
    "short_p": _C_SHORT_PRI,
    "short_n": _C_SHORT_NOPRI,
    "ts": _C_TS,
    "tail": _C_TAIL,
    "sevd": _C_SEVD,
}


def _bank(suffix: bytes, extras=()):
    """Constant bank; extras fold in via the host tier's
    gelf_extra_consts_3164 so the two tiers can never diverge."""
    parts = dict(_PARTS)
    parts["hl"] = b""
    parts["l2a"] = b""
    parts["l2b"] = b""
    if extras:
        from .encode_rfc3164_gelf_block import gelf_extra_consts_3164

        econsts = gelf_extra_consts_3164(list(extras))
        assert econsts is not None  # route_ok pre-checked
        (parts["open"], parts["host"], parts["hl"], parts["l2a"],
         parts["l2b"], parts["short_p"], parts["short_n"], parts["ts"],
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
                                   "extras", "elide"))
def _encode_kernel(batch, lens, dec, ts_text, ts_len, *, suffix: bytes,
                   impl: str, assemble: bool = True, extras=(),
                   elide: bool = False):
    N, L = batch.shape
    bank, off, parts = _bank(suffix, extras)
    OW = _out_width(L, L + E_CAP + len(bank) + TS_W)
    iota = jax.lax.broadcasted_iota(_I32, (N, L), 1)

    es = escape_stage(batch, lens, iota, assemble)
    dmap = es["dmap"]

    lens32 = lens.astype(_I32)
    host_s, host_e = dmap(dec["host_start"]), dmap(dec["host_end"])
    msg_s = dmap(dec["msg_start"])
    row_e = lens32 + es["ne_total"]     # dmap(lens) without the reduction
    has_pri = dec["has_pri"].astype(bool)

    EW = L + E_CAP
    cbase = EW
    tbase = EW + len(bank)
    zero = jnp.zeros((N,), dtype=_I32)
    # constant-elision mode (elide=True) skips the row-constant head,
    # timestamp-label, and tail segments: the host splice restores them
    # after an output-sized variable-bytes-only D2H fetch
    # (device_common.splice_elided_rows — same contract as device_gelf)
    segs = [] if elide else [
        (zero + (cbase + off["open"]), zero + len(parts["open"])),
    ]
    segs += [
        (zero, row_e),                                   # full_message
        (zero + (cbase + off["host"]), zero + len(parts["host"])),
        (host_s, jnp.maximum(host_e - host_s, 0)),
        (zero + (cbase + off["hl"]), zero + len(parts["hl"])),
        (zero + (cbase + off["level"]),
         jnp.where(has_pri, len(parts["level"]), 0)),
        (cbase + off["sevd"] + dec["severity"].astype(_I32),
         jnp.where(has_pri, 1, 0)),
        # extras between level and short: after-number variant when PRI
        # present, string-close variant otherwise (same selection as the
        # short constant below)
        (jnp.where(has_pri, cbase + off["l2a"], cbase + off["l2b"]),
         jnp.where(has_pri, len(parts["l2a"]), len(parts["l2b"]))),
        (jnp.where(has_pri, cbase + off["short_p"],
                   cbase + off["short_n"]),
         jnp.where(has_pri, len(parts["short_p"]),
                   len(parts["short_n"]))),
        (msg_s, jnp.maximum(row_e - msg_s, 0)),          # short_message
    ]
    if not elide:
        segs.append((zero + (cbase + off["ts"]), zero + len(parts["ts"])))
    segs.append((zero + tbase, ts_len.astype(_I32)))
    if not elide:
        segs.append((zero + (cbase + off["tail"]),
                     zero + len(parts["tail"]) + len(suffix)))

    out_len = segs[0][1]
    for _, ln in segs[1:]:
        out_len = out_len + ln

    tier = (dec["ok"].astype(bool)
            & ~dec["has_high"].astype(bool)
            & ~jnp.any(es["bad_ctl"], axis=1)
            & (es["ne_total"] <= E_CAP)
            & (out_len <= OW))
    if not assemble:
        return tier
    acc, out_len2 = assemble_rows(segs, es["esc_row"], bank, ts_text,
                                  N, OW)
    return acc, out_len2, tier


def route_ok(encoder, merger) -> bool:
    """GELF output over line/nul/syslen framing; gelf_extra rides as
    constant segments when this layout can place the keys statically
    (gelf_extra_consts_3164 — note the rfc3164 fixed-key set differs
    from the rfc5424 one, so placeability differs too)."""
    from .device_common import gelf_route_ok
    from .encode_rfc3164_gelf_block import gelf_extra_consts_3164

    return gelf_route_ok(
        encoder, merger,
        lambda e: gelf_extra_consts_3164(e) is not None)


def fetch_encode(handle, packed, encoder, merger, route_state=None):
    """Device rfc3164→GELF encode for a submitted rfc3164 decode handle
    (out dict, batch_dev, lens_dev); returns (BlockResult | None,
    fetch_seconds) with None = use the host span path."""
    from .block_common import merger_suffix
    from .materialize_rfc3164 import _scalar_3164

    out, batch_dev, lens_dev = handle
    suffix, syslen = merger_suffix(merger)
    impl = best_scan_impl()
    extras = tuple((k, v) for k, v in getattr(encoder, "extra", ()))
    # constant elision (PR 4's rfc5424→GELF win, extended here): the
    # head, timestamp-label, and tail constants never cross PCIe — the
    # splice restores the exact host-tier bytes (same _bank both sides)
    espec = elide_spec(suffix, extras)

    def kernel(ts_text, ts_len, assemble):
        return _encode_kernel(batch_dev, lens_dev, dict(out), ts_text,
                              ts_len, suffix=suffix, impl=impl,
                              assemble=assemble, extras=extras,
                              elide=True)

    # zero-JIT boot: consult the AOT artifact store before compiling
    from .aot import encode_wrap

    kernel = encode_wrap("device_rfc3164", kernel, batch_dev, lens_dev,
                         dict(out), suffix, impl, extras)

    return fetch_encode_driver(
        kernel, out, batch_dev, lens_dev, packed, encoder, merger,
        route_state, suffix, syslen, scalar_fn=_scalar_3164,
        fallback_frac=FALLBACK_FRAC, decline_limit=DECLINE_LIMIT,
        cooldown=COOLDOWN, elide=espec)
