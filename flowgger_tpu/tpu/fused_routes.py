"""Fused device-resident decode→encode routes: ONE compiled program per
(in-format, out-format) pair, so field span channels never leave the
device between the decode and the encode.

The split tier (tpu/device_*.py) runs decode and encode as two separate
XLA programs with the full decode channel dict materialized to HBM as
program outputs in between — and, on the host block path, fetched over
PCIe, spliced, and re-uploaded.  A fused route traces the block decode
(rfc5424/rfc3164/ltsv/gelf) and its device encode kernel into a single
jitted program: the decoder's span channels are internal values of one
XLA computation, fusible with the encode stages and never transferred.
This is the batched-TPU shape of the reference's per-line hot loop
(line_splitter.rs:44-54 → encoder/mod.rs:54-56), and it collapses the
AOT artifact matrix from decode×encode pairs to one program per route
(ROADMAP item 1).

Two further wins ride the fusion:

- **Field-demand masks** (On-Demand parsing, arxiv 2312.17149): each
  route declares the decode channels its encoder actually consumes
  (``DEMAND``), threaded into the decoder as a static ``demand``
  argument.  Channels the output format drops (rfc5424's msgid and
  facility on the GELF route, ltsv's raw timestamp span, ...) vanish
  from the traced output, so XLA dead-code-eliminates their entire
  materialization chain — the decode work for unused fields is never
  executed, not just never fetched.
- **Constant elision on every route** (PR 4 shipped it for
  rfc5424→GELF only): all four fused kernels run ``elide=True`` — the
  row-constant head, timestamp-label, and tail segments never cross
  PCIe, ``splice_elided_rows`` restores the exact host-tier bytes — so
  fetched bytes/row lands under emitted bytes/row on every route.

Degradation ladder (unchanged contract): every fused compile runs under
``guarded_compile_call`` watchdog slots (namespaced ``fused/<route>`` so
two routes at one shape cannot mask each other); a timeout or a
tier-fraction decline falls back to the existing split path — split
decode, device-or-host encode, scalar oracle — and the emitted bytes
stay identical at every rung.  ``FLOWGGER_FUSED_COMPILE_TIMEOUT_MS``
optionally tightens the first-compile wait for the fused tier alone
(the shared ``FLOWGGER_COMPILE_TIMEOUT_MS`` deadline applies otherwise).

Where this container's XLA cannot compile the fused programs at all,
byte identity is still enforced eagerly via ``jax.disable_jit()`` — see
the DIFF_TESTs below.
"""

from __future__ import annotations

# byte-identity contract (flowcheck FC03): the scalar counterparts the
# fused route matrix must stay byte-identical to (one oracle per output
# format), and the differential tests that enforce it across the matrix
SCALAR_ORACLE = (
    "flowgger_tpu.encoders.gelf:GelfEncoder",
    "flowgger_tpu.encoders.rfc5424:RFC5424Encoder",
    "flowgger_tpu.encoders.ltsv:LTSVEncoder",
    "flowgger_tpu.encoders.capnp:CapnpEncoder",
)
DIFF_TEST = (
    "tests/test_fused_routes.py::test_fused_matches_scalar_oracle_all_routes",
    "tests/test_fused_routes.py::test_fused_route_fuzz_vs_scalar",
    "tests/test_device_encode_out.py::test_fused_new_output_routes_match_scalar",
)

import os
from functools import partial

import jax
import jax.numpy as jnp

from ..utils.metrics import registry as _metrics

# decline hysteresis — same ladder constants as the split device tiers
FALLBACK_FRAC = 0.05
DECLINE_LIMIT = 3
COOLDOWN = 16

FUSED_COMPILE_TIMEOUT_ENV = "FLOWGGER_FUSED_COMPILE_TIMEOUT_MS"

_TS4 = ("days", "sod", "off", "nanos")

# ---------------------------------------------------------------------------
# Field-demand masks: exactly the decode channels each route's encode
# kernel + fetch driver read.  Everything else is dead in the fused
# trace and never materialized.  A missing key fails fast (KeyError in
# the encode stage), so the eager differential tests double as
# completeness checks for these sets.
DEMAND = {
    "rfc5424_gelf": frozenset((
        "ok", "has_high", "severity", *_TS4,
        "host_start", "host_end", "app_start", "app_end",
        "proc_start", "proc_end", "full_start", "trim_end",
        "msg_trim_start", "sd_count", "sid_start", "sid_end",
        "pair_count", "name_start", "name_end", "val_start", "val_end",
        "val_has_esc",
    )),  # drops: bom, facility, msgid_start/end, msg_start, pair_sd
    "rfc3164_gelf": frozenset((
        "ok", "has_pri", "has_high", "severity", *_TS4,
        "host_start", "host_end", "msg_start",
    )),  # drops: facility
    "ltsv_gelf": frozenset((
        "ok", "has_high", "n_parts", "part_start", "part_end",
        "colon_pos", "time_pos", "host_pos", "msg_pos", "level_pos",
        "host_start", "host_end", "msg_start", "msg_end", "level_val",
        "ts_kind", "ts_hi", "ts_lo", "ts_meta", *_TS4,
    )),  # drops: ts_start, ts_end
    "gelf_gelf": frozenset((
        "ok", "n_fields", "key_start", "key_end", "val_start",
        "val_end", "val_type", "key_esc", "val_esc",
    )),  # the canonicalizing re-encode touches every channel
    "rfc5424_rfc5424": frozenset((
        "ok", "has_high", "facility", "severity", *_TS4,
        "host_start", "host_end", "app_start", "app_end",
        "proc_start", "proc_end", "msgid_start", "msgid_end",
        "msg_trim_start", "trim_end", "sd_count", "sid_start", "sid_end",
        "pair_count", "pair_sd", "name_start", "name_end",
        "val_start", "val_end", "val_has_esc",
    )),  # drops: bom, full_start, msg_start
    "rfc3164_rfc5424": frozenset((
        "ok", "has_pri", "has_high", "facility", "severity", *_TS4,
        "host_start", "host_end", "msg_start",
    )),  # the relay upgrade reads every rfc3164 channel
    "rfc5424_ltsv": frozenset((
        "ok", "has_high", "facility", "severity", *_TS4,
        "host_start", "host_end", "app_start", "app_end",
        "proc_start", "proc_end", "msgid_start", "msgid_end",
        "full_start", "msg_trim_start", "trim_end",
        "pair_count", "name_start", "name_end",
        "val_start", "val_end", "val_has_esc",
    )),  # drops: bom, msg_start, sd_count, sid_start/end, pair_sd
    "rfc5424_capnp": frozenset((
        "ok", "has_high", "facility", "severity", *_TS4,
        "host_start", "host_end", "app_start", "app_end",
        "proc_start", "proc_end", "msgid_start", "msgid_end",
        "full_start", "msg_trim_start", "trim_end",
        "sd_count", "sid_start", "sid_end",
        "pair_count", "pair_sd", "name_start", "name_end",
        "val_start", "val_end", "val_has_esc",
    )),  # drops: bom, msg_start
}


def fused_compile_timeout_s():
    """Deadline override for fused-tier guarded compiles; None = the
    shared watchdog deadline (FLOWGGER_COMPILE_TIMEOUT_MS)."""
    raw = os.environ.get(FUSED_COMPILE_TIMEOUT_ENV)
    if raw is None:
        return None
    try:
        return int(raw) / 1000.0
    except ValueError:
        return None


# ---------------------------------------------------------------------------
# The fused programs: decode traced inline into the encode kernel.
# assemble=False returns a dict — the tier plus the channels the fetch
# driver formats timestamps from ("ok" + ts_keys) — so the driver needs
# no separate decode output dict at all.

@partial(jax.jit, static_argnames=("max_sd", "suffix", "impl",
                                   "assemble", "extras", "demand"))
def _fused_rfc5424_gelf(batch, lens, ts_text, ts_len, *, max_sd: int,
                        suffix: bytes, impl: str, assemble: bool,
                        extras, demand):
    from .device_gelf import _encode_kernel
    from .rfc5424 import decode_rfc5424_jit

    dec = decode_rfc5424_jit(batch, lens, max_sd=max_sd,
                             extract_impl="sum", demand=demand)
    res = _encode_kernel(batch, lens, dec, ts_text, ts_len,
                         suffix=suffix, max_sd=max_sd, impl=impl,
                         assemble=assemble, extras=extras, elide=True)
    if not assemble:
        return {"tier": res,
                **{k: dec[k] for k in ("ok",) + _TS4}}
    return res


@partial(jax.jit, static_argnames=("suffix", "impl", "assemble",
                                   "extras", "demand"))
def _fused_rfc3164_gelf(batch, lens, year, ts_text, ts_len, *,
                        suffix: bytes, impl: str, assemble: bool,
                        extras, demand):
    from .device_rfc3164 import _encode_kernel
    from .rfc3164 import decode_rfc3164_jit

    dec = decode_rfc3164_jit(batch, lens, year, demand=demand)
    res = _encode_kernel(batch, lens, dec, ts_text, ts_len,
                         suffix=suffix, impl=impl, assemble=assemble,
                         extras=extras, elide=True)
    if not assemble:
        return {"tier": res,
                **{k: dec[k] for k in ("ok",) + _TS4}}
    return res


@partial(jax.jit, static_argnames=("suffix", "impl", "assemble",
                                   "extras", "demand"))
def _fused_ltsv_gelf(batch, lens, ts_text, ts_len, *, suffix: bytes,
                     impl: str, assemble: bool, extras, demand):
    from .device_ltsv import _encode_kernel
    from .ltsv import decode_ltsv_jit

    dec = decode_ltsv_jit(batch, lens, demand=demand)
    res = _encode_kernel(batch, lens, dec, ts_text, ts_len,
                         suffix=suffix, impl=impl, assemble=assemble,
                         extras=extras, elide=True)
    if not assemble:
        # narrowed timestamp channels: this route's head constant is a
        # single "{" (sorted "_key" pairs lead the object), so its
        # elided-constant savings are small — the fixed per-row small
        # fetch must shrink to stay under them.  Kind rides u8, the
        # fraction count u8, the offset i16 minutes (rfc3339 offsets
        # are whole minutes), and the host fetches the calendar vs
        # split-integer channels only for timestamp kinds the batch
        # actually contains (_ltsv_small_fetch).
        return {"tier": res, "ok": dec["ok"],
                "ts_kind8": dec["ts_kind"].astype(jnp.uint8),
                "ts_frac8": (dec["ts_meta"] & 255).astype(jnp.uint8),
                "off_min16": (dec["off"] // 60).astype(jnp.int16),
                "days": dec["days"], "sod": dec["sod"],
                "nanos": dec["nanos"],
                "ts_hi": dec["ts_hi"], "ts_lo": dec["ts_lo"]}
    return res


def _ltsv_small_fetch(out, fetch):
    """Kind-conditional small-channel fetch for the fused ltsv route:
    reconstructs the exact channel dict ``ts_vals_ltsv`` consumes
    (off = off_min*60 and frac = meta&255 are bit-exact by
    construction) while homogeneous-timestamp streams ship only the
    channels their kind needs."""
    import numpy as np

    ok = fetch(out["ok"]).astype(bool)
    kind = fetch(out["ts_kind8"])
    n_full = ok.shape[0]

    def z32():
        return np.zeros(n_full, dtype=np.int32)

    small = {"ok": ok, "ts_kind": kind.astype(np.int32)}
    if bool((ok & (kind == 0)).any()):
        small["days"] = fetch(out["days"])
        small["sod"] = fetch(out["sod"])
        small["off"] = fetch(out["off_min16"]).astype(np.int32) * 60
        small["nanos"] = fetch(out["nanos"])
    else:
        small.update(days=z32(), sod=z32(), off=z32(), nanos=z32())
    if bool((ok & (kind == 1)).any()):
        small["ts_hi"] = fetch(out["ts_hi"])
        small["ts_lo"] = fetch(out["ts_lo"])
        small["ts_meta"] = fetch(out["ts_frac8"]).astype(np.int32)
    else:
        small.update(ts_hi=z32(), ts_lo=z32(), ts_meta=z32())
    return small


@partial(jax.jit, static_argnames=("suffix", "assemble", "demand"))
def _fused_gelf_gelf(batch, lens, ts_text, ts_len, *, suffix: bytes,
                     assemble: bool, demand):
    from .device_gelf_gelf import _encode_kernel
    from .gelf import decode_gelf_jit

    dec = decode_gelf_jit(batch, lens, demand=demand)
    res = _encode_kernel(batch, lens, dec, ts_text, ts_len,
                         suffix=suffix, assemble=assemble, elide=True)
    if not assemble:
        # the gelf→GELF probe already returns a dict (its timestamp
        # parse exists encode-side only); add the decode's ok gate
        return {**res, "ok": dec["ok"]}
    return res


# The non-GELF output legs (PR 19): their probes all return dicts —
# tier plus the one/two-byte channels their callable elides splice the
# row-dependent heads from (fac8/sev8, gap offsets).

@partial(jax.jit, static_argnames=("max_sd", "suffix", "assemble",
                                   "demand"))
def _fused_rfc5424_rfc5424(batch, lens, ts_text, ts_len, *, max_sd: int,
                           suffix: bytes, assemble: bool, demand):
    from .device_rfc5424_out import _encode_kernel
    from .rfc5424 import decode_rfc5424_jit

    dec = decode_rfc5424_jit(batch, lens, max_sd=max_sd,
                             extract_impl="sum", demand=demand)
    res = _encode_kernel(batch, lens, dec, ts_text, ts_len,
                         suffix=suffix, max_sd=max_sd,
                         assemble=assemble, elide=True)
    if not assemble:
        return {**res, **{k: dec[k] for k in ("ok",) + _TS4}}
    return res


@partial(jax.jit, static_argnames=("suffix", "assemble", "demand"))
def _fused_rfc3164_rfc5424(batch, lens, year, ts_text, ts_len, *,
                           suffix: bytes, assemble: bool, demand):
    from .device_rfc5424_out import _encode_kernel_3164
    from .rfc3164 import decode_rfc3164_jit

    dec = decode_rfc3164_jit(batch, lens, year, demand=demand)
    res = _encode_kernel_3164(batch, lens, dec, ts_text, ts_len,
                              suffix=suffix, assemble=assemble,
                              elide=True)
    if not assemble:
        return {**res, **{k: dec[k] for k in ("ok",) + _TS4}}
    return res


@partial(jax.jit, static_argnames=("max_sd", "suffix", "extras",
                                   "assemble", "demand"))
def _fused_rfc5424_ltsv(batch, lens, ts_text, ts_len, *, max_sd: int,
                        suffix: bytes, extras, assemble: bool, demand):
    from .device_ltsv_out import _encode_kernel
    from .rfc5424 import decode_rfc5424_jit

    dec = decode_rfc5424_jit(batch, lens, max_sd=max_sd,
                             extract_impl="sum", demand=demand)
    res = _encode_kernel(batch, lens, dec, ts_text, ts_len,
                         suffix=suffix, extras=extras,
                         assemble=assemble, elide=True)
    if not assemble:
        return {**res, **{k: dec[k] for k in ("ok",) + _TS4}}
    return res


@partial(jax.jit, static_argnames=("max_sd", "suffix", "extras",
                                   "assemble", "demand"))
def _fused_rfc5424_capnp(batch, lens, ts_text, ts_len, *, max_sd: int,
                         suffix: bytes, extras, assemble: bool, demand):
    from .device_capnp import _encode_kernel
    from .rfc5424 import decode_rfc5424_jit

    dec = decode_rfc5424_jit(batch, lens, max_sd=max_sd,
                             extract_impl="sum", demand=demand)
    res = _encode_kernel(batch, lens, dec, ts_text, ts_len,
                         suffix=suffix, extras=extras,
                         assemble=assemble, elide=True)
    if not assemble:
        return {**res, **{k: dec[k] for k in ("ok",) + _TS4}}
    return res


# ---------------------------------------------------------------------------


class FusedHandle:
    """A submitted fused batch: the committed device inputs plus the
    route that will run them.  All device work happens at fetch time on
    the lane fetcher thread (the in-flight window provides the
    ingest/compute overlap)."""

    __slots__ = ("route", "batch_dev", "lens_dev", "device")

    def __init__(self, route, batch_dev, lens_dev, device):
        self.route = route
        self.batch_dev = batch_dev
        self.lens_dev = lens_dev
        self.device = device


class FusedRoute:
    """One (in-format → out-format) fused program plus its driver
    recipe."""

    __slots__ = ("name", "fmt", "out")

    def __init__(self, name: str, fmt: str, out: str = "gelf"):
        self.name = name
        self.fmt = fmt
        self.out = out

    # -- applicability -----------------------------------------------------
    def route_ok(self, encoder, merger, decoder=None) -> bool:
        """Reuses the split device tier's gate (output encoder type,
        framing allowlist, extras placement, FLOWGGER_DEVICE_ENCODE
        kill switch, ltsv schema) — a route the split tier would refuse
        is never fused either."""
        if self.out == "rfc5424":
            from . import device_rfc5424_out

            return device_rfc5424_out.route_ok(encoder, merger)
        if self.out == "ltsv":
            from . import device_ltsv_out

            return device_ltsv_out.route_ok(encoder, merger)
        if self.out == "capnp":
            from . import device_capnp

            return device_capnp.route_ok(encoder, merger)
        if self.fmt == "rfc3164":
            from . import device_rfc3164

            return device_rfc3164.route_ok(encoder, merger)
        if self.fmt == "ltsv":
            from . import device_ltsv

            return device_ltsv.route_ok(encoder, merger, decoder)
        if self.fmt == "gelf":
            from . import device_gelf_gelf

            return device_gelf_gelf.route_ok(encoder, merger)
        from . import device_gelf

        return device_gelf.route_ok(encoder, merger)

    # -- driver recipe ------------------------------------------------------
    def make_kernel(self, handle, encoder, merger, ltsv_decoder=None):
        """Build the fused kernel closure plus the driver kwargs
        (scalar oracle, ts channel recipe, elide constants)."""
        # zero-JIT boot: fused_wrap makes each closure consult the AOT
        # artifact store per call (a hit runs the exported program —
        # the same trace, byte-identical); misses/rejects fall through
        # to the fused jit under the same compile watchdog
        from .aot import fused_wrap
        from .block_common import merger_suffix
        from .rfc5424 import best_scan_impl

        suffix, syslen = merger_suffix(merger)
        impl = best_scan_impl()
        extras = tuple((k, v) for k, v in getattr(encoder, "extra", ()))
        demand = DEMAND[self.name]
        b, ln = handle.batch_dev, handle.lens_dev
        kw = {"suffix": suffix, "syslen": syslen}

        if self.out != "gelf":
            return self._make_kernel_out(b, ln, suffix, impl, extras,
                                         demand, kw, fused_wrap)
        if self.fmt == "rfc3164":
            from ..utils.timeparse import current_year_utc
            from .device_rfc3164 import elide_spec
            from .materialize_rfc3164 import _scalar_3164

            year = jnp.int32(current_year_utc())

            def kernel(ts_text, ts_len, assemble):
                return _fused_rfc3164_gelf(
                    b, ln, year, ts_text, ts_len, suffix=suffix,
                    impl=impl, assemble=assemble, extras=extras,
                    demand=demand)

            kernel = fused_wrap(self.name, kernel, (b, ln, year),
                               suffix, impl, extras)
            kw.update(scalar_fn=_scalar_3164,
                      elide=elide_spec(suffix, extras))
            return kernel, kw
        if self.fmt == "ltsv":
            from .device_ltsv import elide_spec, ts_vals_ltsv
            from .materialize_ltsv import _scalar_ltsv

            def kernel(ts_text, ts_len, assemble):
                return _fused_ltsv_gelf(
                    b, ln, ts_text, ts_len, suffix=suffix, impl=impl,
                    assemble=assemble, extras=extras, demand=demand)

            kernel = fused_wrap(self.name, kernel, (b, ln), suffix,
                               impl, extras)
            kw.update(scalar_fn=lambda line: _scalar_ltsv(ltsv_decoder,
                                                          line),
                      ts_vals_fn=ts_vals_ltsv,
                      small_fetch_fn=_ltsv_small_fetch,
                      elide=elide_spec(suffix, extras))
            return kernel, kw
        if self.fmt == "gelf":
            from .device_gelf_gelf import TS_KEYS, elide_spec, ts_vals_gelf
            from .materialize_gelf import _scalar_gelf

            def kernel(ts_text, ts_len, assemble):
                return _fused_gelf_gelf(
                    b, ln, ts_text, ts_len, suffix=suffix,
                    assemble=assemble, demand=demand)

            kernel = fused_wrap(self.name, kernel, (b, ln), suffix,
                               impl, extras)
            kw.update(scalar_fn=_scalar_gelf, ts_keys=TS_KEYS,
                      ts_vals_fn=ts_vals_gelf, elide=elide_spec(suffix))
            return kernel, kw

        from .device_gelf import elide_spec
        from .materialize import _scalar_line
        from .rfc5424 import DEFAULT_MAX_SD

        def kernel(ts_text, ts_len, assemble):
            return _fused_rfc5424_gelf(
                b, ln, ts_text, ts_len, max_sd=DEFAULT_MAX_SD,
                suffix=suffix, impl=impl, assemble=assemble,
                extras=extras, demand=demand)

        kernel = fused_wrap(self.name, kernel, (b, ln), suffix, impl,
                           extras)
        kw.update(scalar_fn=_scalar_line,
                  elide=elide_spec(suffix, extras))
        return kernel, kw

    def _make_kernel_out(self, b, ln, suffix, impl, extras, demand, kw,
                         fused_wrap):
        """Driver recipes for the non-GELF output legs (PR 19): each
        reuses its split module's single-sourced callable elide, stamp
        renderer, and narrowed small fetch."""
        from .materialize import _scalar_line
        from .rfc5424 import DEFAULT_MAX_SD

        if self.name == "rfc5424_rfc5424":
            from . import device_rfc5424_out as m

            def kernel(ts_text, ts_len, assemble):
                return _fused_rfc5424_rfc5424(
                    b, ln, ts_text, ts_len, max_sd=DEFAULT_MAX_SD,
                    suffix=suffix, assemble=assemble, demand=demand)

            kernel = fused_wrap(self.name, kernel, (b, ln), suffix,
                               impl, extras)
            kw.update(scalar_fn=_scalar_line,
                      ts_render=m._render_rfc3339,
                      small_fetch_fn=m._small_fetch(("fac8", "sev8")),
                      elide=m.elide_spec(suffix))
            return kernel, kw
        if self.name == "rfc3164_rfc5424":
            from ..utils.timeparse import current_year_utc
            from . import device_rfc5424_out as m
            from .materialize_rfc3164 import _scalar_3164

            year = jnp.int32(current_year_utc())

            def kernel(ts_text, ts_len, assemble):
                return _fused_rfc3164_rfc5424(
                    b, ln, year, ts_text, ts_len, suffix=suffix,
                    assemble=assemble, demand=demand)

            kernel = fused_wrap(self.name, kernel, (b, ln, year),
                               suffix, impl, extras)
            kw.update(scalar_fn=_scalar_3164,
                      ts_render=m._render_rfc3339,
                      small_fetch_fn=m._small_fetch(
                          ("fac8", "sev8", "pri1", "hostl16")),
                      elide=m.elide_spec(suffix, leg="rfc3164"))
            return kernel, kw
        if self.name == "rfc5424_ltsv":
            from . import device_ltsv_out as m

            def kernel(ts_text, ts_len, assemble):
                return _fused_rfc5424_ltsv(
                    b, ln, ts_text, ts_len, max_sd=DEFAULT_MAX_SD,
                    suffix=suffix, extras=extras, assemble=assemble,
                    demand=demand)

            kernel = fused_wrap(self.name, kernel, (b, ln), suffix,
                               impl, extras)
            kw.update(scalar_fn=_scalar_line,
                      ts_render=m._render_display,
                      small_fetch_fn=m._small_fetch,
                      elide=m.elide_spec(suffix, extras))
            return kernel, kw
        # rfc5424_capnp
        from . import device_capnp as m

        def kernel(ts_text, ts_len, assemble):
            return _fused_rfc5424_capnp(
                b, ln, ts_text, ts_len, max_sd=DEFAULT_MAX_SD,
                suffix=suffix, extras=extras, assemble=assemble,
                demand=demand)

        kernel = fused_wrap(self.name, kernel, (b, ln), suffix, impl,
                           extras)
        kw.update(scalar_fn=_scalar_line,
                  ts_render=m._render_le_f64,
                  small_fetch_fn=m._small_fetch,
                  elide=m.elide_spec(suffix, extras))
        return kernel, kw


ROUTES = {
    "rfc5424": FusedRoute("rfc5424_gelf", "rfc5424"),
    "rfc3164": FusedRoute("rfc3164_gelf", "rfc3164"),
    "ltsv": FusedRoute("ltsv_gelf", "ltsv"),
    "gelf": FusedRoute("gelf_gelf", "gelf"),
    # PR 19: the non-GELF output legs close the N×M matrix
    "rfc5424_rfc5424": FusedRoute("rfc5424_rfc5424", "rfc5424",
                                  out="rfc5424"),
    "rfc3164_rfc5424": FusedRoute("rfc3164_rfc5424", "rfc3164",
                                  out="rfc5424"),
    "rfc5424_ltsv": FusedRoute("rfc5424_ltsv", "rfc5424", out="ltsv"),
    "rfc5424_capnp": FusedRoute("rfc5424_capnp", "rfc5424",
                                out="capnp"),
}


def _out_key(encoder) -> str:
    """The output-format leg for this encoder type (fused routes
    dispatch on concrete encoder classes, like the split tiers)."""
    from ..encoders.capnp import CapnpEncoder
    from ..encoders.gelf import GelfEncoder
    from ..encoders.ltsv import LTSVEncoder
    from ..encoders.rfc5424 import RFC5424Encoder

    for cls, key in ((GelfEncoder, "gelf"), (RFC5424Encoder, "rfc5424"),
                     (LTSVEncoder, "ltsv"), (CapnpEncoder, "capnp")):
        if type(encoder) is cls:
            return key
    return ""


def route_for(fmt: str, encoder, merger, decoder=None):
    """The registered fused route for this (fmt, encoder, merger)
    config, or None when no fused program applies (the split path is
    then the route — ``input.tpu_fuse = "auto"`` semantics).  →GELF
    legs keep their original fmt-keyed registrations; the other output
    legs key on ``{fmt}_{out}``."""
    okey = _out_key(encoder)
    route = ROUTES.get(fmt if okey == "gelf" else f"{fmt}_{okey}")
    if route is None or not route.route_ok(encoder, merger, decoder):
        return None
    return route


def cooldown_state(route_state: dict, route: FusedRoute) -> dict:
    """The per-handler fused decline-hysteresis dict for ``route`` —
    the ONE key both the submit-side cooldown check (batch._emit_fast)
    and the driver's decline bookkeeping (fetch_encode) share.  Own
    namespace: a fused decline must not eat the split device tier's
    decline budget (or vice versa)."""
    return route_state.setdefault(f"fused:{route.name}", {})


def submit(route: FusedRoute, packed, device=None) -> FusedHandle:
    """Commit one packed tuple's inputs to the lane device.  No kernel
    runs here: the fused program dispatches on the lane fetcher thread
    (fetch_encode), where a compile-watchdog wait can never stall
    ingest."""
    from .device_common import h2d

    batch_dev, lens_dev = h2d(packed[0], packed[1], device)
    return FusedHandle(route, batch_dev, lens_dev, device)


def fetch_encode(handle: FusedHandle, packed, encoder, merger,
                 ltsv_decoder=None, route_state=None):
    """Run the fused program for a submitted handle through the shared
    fetch driver; returns (BlockResult | None, fetch_seconds).  None =
    the fused tier declined (compile pending, cooldown, or tier
    fraction) — the caller falls back to the split path and counts a
    ``fused_fallbacks``."""
    from .device_common import fetch_encode_driver

    route = handle.route
    state = None
    if route_state is not None:
        state = cooldown_state(route_state, route)
    kernel, kw = route.make_kernel(handle, encoder, merger, ltsv_decoder)
    driver_kw = {k: kw[k] for k in ("ts_keys", "ts_vals_fn",
                                    "small_fetch_fn", "ts_render")
                 if k in kw}
    return fetch_encode_driver(
        kernel, {}, handle.batch_dev, handle.lens_dev, packed, encoder,
        merger, state, kw["suffix"], kw["syslen"],
        scalar_fn=kw["scalar_fn"], fallback_frac=FALLBACK_FRAC,
        decline_limit=DECLINE_LIMIT, cooldown=COOLDOWN,
        elide=kw["elide"], kname_prefix=f"fused/{route.name}",
        compile_timeout_s=fused_compile_timeout_s(),
        route_label=route.name, **driver_kw)
