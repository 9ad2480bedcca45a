"""Single-VMEM structural-pass Pallas kernels: framing spans, the
compiled-NFA stage-1 classifier, and the fused framing→decode entry.

Every device kernel before this PR was composed jnp ops, which XLA
materializes between fusions: the syslen framing chain resolves its
pointer-doubling hops as HBM scatter/gather passes (measured 0.13x
host memcpy on CPU), and the ``jsonidx`` structural screen makes ~60
HBM round-trips over the [N, L] plane.  This module rewrites those
inner loops as true Pallas kernels — the bytes are read into VMEM
once, every intermediate plane lives on-chip, and only the compact
span/index outputs are written back:

- **framing spans** (``frame_sep_spans_pallas`` /
  ``frame_syslen_spans_pallas``): the delimiter/prefix lookahead
  planes build with Mosaic-lowerable log-shift ladders, then the
  data-dependent frame chain resolves as a *sequential scalar walk*
  over VMEM-resident planes (``ref[0, pl.ds(pos, 1)]`` hops) — O(ncap)
  one-element VMEM reads replace the jnp tier's log2(B) full-plane
  scatter/gather passes, because chasing a chain is exactly what a
  scalar loop over on-chip memory is good at;
- **stage-1 classifier** (``structural_index_pallas``): the jsonidx
  structural index as a [block_rows, L] tile kernel whose string
  machine is the compiled-NFA transition-table scan
  (``jsonidx.NFA_TABLE``) and whose scans/lookarounds/extractions all
  use the ``manual``/``sum`` Mosaic-safe forms — one read of the byte
  plane, one write of the packed index;
- **fused framing→decode** (``fused_frame_decode_rfc5424`` /
  ``_jsonl``): spans → gather → decode composed under one jit so the
  dense [rows, max_len] batch is an internal value that never
  materializes as a program output.

``interpret=True`` runs every kernel in the Pallas interpreter, which
is how the CPU differential tests hold them byte-for-byte to the scalar
oracles.  **The chip's compiler refuses all six kernels today**
(jax 0.9.0 / libtpu 0.0.34, compiled for a described v5e in
``tests/test_chip_compile.py``): the span and gather kernels for the
1-element dynamic lane load in ``_read1`` ("cannot statically prove
that index in dimension 1 is a multiple of 128"), the two decode
kernels for an ``arith.trunci`` from i8 to i1 ("Unsupported target
bitwidth for truncation").  Repair or deletion is ROADMAP D2; until
then the tier is opt-in only.  Region-sized kernels run as one VMEM
block, so the tier self-gates at ``PALLAS_MAX_REGION`` bytes and larger
regions stay on the jnp tier.

Engagement is the ``input.tpu_pallas = auto|on|off`` key resolved by
the batch handler into :func:`set_mode`: ``auto`` and ``off`` are
"off" on every backend; ``on`` is "interpret" on the CPU backend and
"compiled" elsewhere, where :func:`require_compiles` first compiles the
handler's kernels and turns a refusal into a start-up error that names
the kernel.  An engaged tier still rides the decline machinery for what
can happen later — framing-side probes run under the compile watchdog
(slot ``pallas/<kind>``) inside ``framing.device_frame_region`` and
fall back to the *jnp* span kernels on a watchdog timeout, the decode
tier (``decode_tier``) likewise — but in compiled mode any other
failure of a kernel is raised to the caller (the breaker counts it),
never declined, cooled down and retried.
"""

from __future__ import annotations

import functools
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .jsonidx import structural_index
from .rfc5424 import _shift_left

SCALAR_ORACLE = (
    "flowgger_tpu.tpu.pack:split_chunk",
    "flowgger_tpu.splitters:_scan_syslen_region",
    "flowgger_tpu.decoders.rfc5424:parse_line",
    "flowgger_tpu.decoders.jsonl:parse_line",
)
DIFF_TEST = (
    "tests/test_pallas_kernels.py::test_sep_spans_match_jnp_and_host",
    "tests/test_pallas_kernels.py::test_syslen_spans_match_jnp_and_host",
    "tests/test_pallas_kernels.py::test_structural_index_pallas_matches_jnp",
    "tests/test_pallas_kernels.py::test_raw_ingest_byte_identity_pallas",
)

_I32 = jnp.int32
# numpy scalar (framing._BIG precedent): folds into traced code without
# costing a fresh-process jit compile at import time
_BIG = np.int32(1 << 30)

# single-block VMEM ceiling for the region kernels: beyond this the
# lookahead planes (~5 x i32 x B) stop fitting comfortably in VMEM and
# the region stays on the jnp tier (which tiles through XLA)
PALLAS_MAX_REGION = 1 << 20

# decode-tier decline hysteresis (framing's DECLINE_LIMIT/COOLDOWN
# pattern, scoped per decode format)
DECLINE_LIMIT = 3
COOLDOWN = 32

from .framing import MAX_PREFIX_DIGITS, _POW10  # noqa: E402 - shared prefix-parse contract


# ---------------------------------------------------------------------------
# engagement mode (set by the batch handler from input.tpu_pallas; the
# pack._SHAPE_BUCKETS module-state precedent — only an explicit config
# resolution touches it)

_mode_lock = threading.Lock()
_MODE = {"mode": "off"}
_DECODE_STATE: Dict[str, Dict] = {}


def set_mode(mode: str) -> None:
    """``off`` | ``compiled`` | ``interpret`` — resolved by the batch
    handler from ``input.tpu_pallas`` and the backend."""
    if mode not in ("off", "compiled", "interpret"):
        raise ValueError(f"unknown pallas mode {mode!r}")
    with _mode_lock:
        _MODE["mode"] = mode
        _DECODE_STATE.clear()


def mode() -> str:
    return _MODE["mode"]


def engaged() -> bool:
    return _MODE["mode"] != "off"


def interpret_mode() -> bool:
    return _MODE["mode"] == "interpret"


def framing_engaged(region_bytes: int) -> bool:
    """The framing tier probes pallas first for regions that fit the
    single-VMEM-block kernels."""
    return engaged() and region_bytes <= PALLAS_MAX_REGION


def fused_leg_mode() -> str:
    """The pallas mode a fused decode→encode program's rfc5424 leg
    traces with: ``compiled`` only where the tier itself is compiled
    (an explicit ``on`` off the CPU backend), else ``off`` — so the
    default fused program on every backend traces the jnp decode leg.
    Interpret mode never rides a fused program: inlined there it
    explodes XLA CPU compile time (the interpreter unrolls the kernel
    body into the already-large encode graph), so CPU tests exercise
    the standalone fused entries (``fused_frame_decode_*``) instead."""
    return "compiled" if _MODE["mode"] == "compiled" else "off"


def must_raise(e: BaseException) -> bool:
    """Whether a failed kernel call is an error and not a decline:
    compiled kernels passed :func:`require_compiles` at start-up, so
    only a watchdog timeout may still decline them."""
    from .device_common import CompileTimeout

    return _MODE["mode"] == "compiled" and not isinstance(e, CompileTimeout)


def require_compiles(fmt: str, rows: int, max_len: int,
                     framing: bool) -> None:
    """Compile, for the default device, every kernel a handler of this
    shape would dispatch in compiled mode; a refusal raises ConfigError
    naming the kernel.  Called once at start-up for an explicit
    ``input.tpu_pallas = "on"`` off the CPU backend."""
    from ..config import ConfigError

    def u8(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint8)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, _I32)

    checks = []
    if fmt == "rfc5424":
        from .rfc5424 import decode_rfc5424_pallas

        checks.append(("decode_rfc5424_pallas",
                       jax.jit(decode_rfc5424_pallas),
                       (u8(rows, max_len), i32(rows)), {}))
    elif fmt == "jsonl":
        checks.append(("decode_jsonl_pallas", decode_jsonl_pallas,
                       (u8(rows, max_len), i32(rows)), {}))
    if framing:
        B = 1 << 16
        checks += [
            ("frame_sep_spans_pallas", frame_sep_spans_pallas,
             (u8(B), i32()), {}),
            ("frame_syslen_spans_pallas", frame_syslen_spans_pallas,
             (u8(B), i32()), {}),
            ("frame_gather_pallas", frame_gather_pallas,
             (u8(B), i32(256), i32(256)), {"max_len": max_len}),
        ]
    for name, fn, args, kw in checks:
        try:
            fn.lower(*args, **kw).compile()
        except Exception as e:  # noqa: BLE001 - any refusal is the start-up error
            first = (str(e).strip().splitlines() or [""])[0]
            raise ConfigError(
                f'input.tpu_pallas = "on": the compiler refuses kernel '
                f"{name} ({type(e).__name__}: {first}); set "
                'input.tpu_pallas = "off" (or leave it out) to run the '
                "jnp kernel tiers") from e


# ---------------------------------------------------------------------------
# in-kernel ladder helpers (axis-1, fill-aware; Mosaic-safe pad/slice)

def _rev_cummin(x, fill):
    L = x.shape[1]
    k = 1
    while k < L:
        x = jnp.minimum(x, _shift_left(x, k, fill))
        k <<= 1
    return x


def _rev_cumsum(x):
    L = x.shape[1]
    k = 1
    while k < L:
        x = x + _shift_left(x, k, 0)
        k <<= 1
    return x


def _pow10_select(exp):
    """10**exp for exp in [0, MAX_PREFIX_DIGITS) as a branchless select
    chain (the jnp tier's ``pow10[exp]`` gather is not Mosaic-lowerable;
    nine immediates are)."""
    out = jnp.full_like(exp, _POW10[0])
    for e in range(1, MAX_PREFIX_DIGITS):
        out = jnp.where(exp == e, np.int32(_POW10[e]), out)
    return out


def _read1(ref, pos):
    """One scalar from an (1, B) VMEM ref at a traced position."""
    from jax.experimental import pallas as pl

    return ref[0, pl.ds(pos, 1)][0]


def _store_meta(meta_ref, scalars):
    """Per-slot (1,)-stores of traced scalars (jnp.stack of scalars
    does not lower through Mosaic)."""
    from jax.experimental import pallas as pl

    for i, v in enumerate(scalars):
        meta_ref[0, pl.ds(i, 1)] = v.reshape(1)


# ---------------------------------------------------------------------------
# stage A: framing span kernels (single VMEM block + scalar chain walk)

def _sep_kernel(r_ref, l_ref, starts_ref, lens_ref, meta_ref, nxt_ref,
                *, sep: int, strip_cr: bool, ncap: int):
    from jax.experimental import pallas as pl

    B = r_ref.shape[1]
    bb = r_ref[...]
    # (1, 1) view for vector ops (Mosaic rejects traced-scalar vs
    # vector compares), scalar view for the walk's scalar arithmetic
    rlv = l_ref[...]
    idx = jax.lax.broadcasted_iota(_I32, (1, B), 1)
    valid = idx < rlv
    is_sep = (bb == sep) & valid
    # integer reductions don't lower on this Mosaic; f32 is exact to
    # 2^24 and B is capped at PALLAS_MAX_REGION = 2^20
    n = jnp.sum(is_sep.astype(jnp.float32)).astype(_I32)
    # next separator at-or-after each position (reverse-cummin ladder),
    # staged into VMEM scratch for the chain walk's scalar hops
    nxt_ref[...] = _rev_cummin(jnp.where(is_sep, idx, _BIG), _BIG)
    starts_ref[...] = jnp.zeros((1, ncap), _I32)
    lens_ref[...] = jnp.zeros((1, ncap), _I32)

    def body(k, carry):
        pos, consumed = carry
        e = _read1(nxt_ref, jnp.minimum(pos, B - 1))
        live = (k < n) & (e < _BIG)
        ec = jnp.minimum(e, B - 1)
        ln = e - pos
        if strip_cr:
            before = _read1(r_ref, jnp.maximum(ec - 1, 0))
            ln = ln - (live & (ln > 0) & (before == 13)).astype(_I32)
        starts_ref[0, pl.ds(k, 1)] = jnp.where(live, pos, 0).reshape(1)
        lens_ref[0, pl.ds(k, 1)] = jnp.where(live, ln, 0).reshape(1)
        nxt_pos = jnp.where(live, e + 1, pos)
        return nxt_pos, jnp.where(live, e + 1, consumed)

    _, consumed = jax.lax.fori_loop(
        0, ncap, body, (jnp.int32(0), jnp.int32(0)))
    _store_meta(meta_ref, (n, consumed, (n > ncap).astype(_I32),
                           jnp.int32(0)))


@functools.partial(jax.jit,
                   static_argnames=("sep", "strip_cr", "ncap", "interpret"))
def frame_sep_spans_pallas(region, rlen, sep: int = 10,
                           strip_cr: bool = True, ncap: int = 256,
                           interpret: bool = False):
    """Pallas tier of ``framing.frame_sep_spans_jit`` — same output
    dict, one VMEM pass (bytes in, span metadata out)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = region.shape[0]
    x = region.astype(_I32).reshape(1, B)
    rl = jnp.asarray(rlen, _I32).reshape(1, 1)
    starts, lens, meta = pl.pallas_call(
        functools.partial(_sep_kernel, sep=sep, strip_cr=strip_cr,
                          ncap=ncap),
        grid=(1,),
        in_specs=[pl.BlockSpec((1, B), lambda i: (0, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((1, ncap), lambda i: (0, 0)),
                   pl.BlockSpec((1, ncap), lambda i: (0, 0)),
                   pl.BlockSpec((1, 4), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, ncap), _I32),
                   jax.ShapeDtypeStruct((1, ncap), _I32),
                   jax.ShapeDtypeStruct((1, 4), _I32)],
        scratch_shapes=[pltpu.VMEM((1, B), _I32)],
        interpret=interpret,
    )(x, rl)
    return {"starts": starts[0], "lens": lens[0], "n": meta[0, 0],
            "consumed": meta[0, 1], "overflow": meta[0, 2] != 0}


def _syslen_kernel(r_ref, l_ref, starts_ref, lens_ref, meta_ref,
                   sp_ref, nd_ref, suf_ref, *, ncap: int):
    from jax.experimental import pallas as pl

    B = r_ref.shape[1]
    bb = r_ref[...]
    rlv = l_ref[...]
    # the walk's rlen must be the same value *species* as the dynamic
    # plane reads (a ds-load extract): Mosaic refuses cmpi between a
    # statically-loaded scalar and a dynamically-extracted one
    rlen = l_ref[0, pl.ds(0, 1)][0]
    zero = rlen * 0
    idx = jax.lax.broadcasted_iota(_I32, (1, B), 1)
    valid = idx < rlv
    is_digit = (bb >= 48) & (bb <= 57) & valid
    is_space = (bb == 32) & valid
    # lookahead planes (framing.frame_syslen_spans_jit's sp/nd/suf,
    # with manual ladders), staged into VMEM scratch — the chain walk
    # below replaces the jnp tier's pointer-doubling scatter/gather
    sp = _rev_cummin(jnp.where(is_space, idx, _BIG), _BIG)
    # clamp via where (minimum against the (1,1) view trips a Mosaic
    # scalar/vector cmpi type check)
    idx_c = jnp.where(valid, idx, jnp.broadcast_to(rlv, idx.shape))
    nd = _rev_cummin(jnp.where(is_digit, _BIG, idx_c), _BIG)
    has_space = sp < rlv
    exp = jnp.clip(sp - 1 - idx, 0, MAX_PREFIX_DIGITS - 1)
    w = jnp.where(is_digit & has_space,
                  (bb - 48) * _pow10_select(exp), 0)
    sp_ref[...] = sp
    nd_ref[...] = nd
    suf_ref[...] = _rev_cumsum(w)
    starts_ref[...] = jnp.zeros((1, ncap), _I32)
    lens_ref[...] = jnp.zeros((1, ncap), _I32)

    def body(k, carry):
        pos, count, consumed, done, decline = carry
        posc = jnp.minimum(pos, B - 1)
        sp_p = _read1(sp_ref, posc)
        nd_p = _read1(nd_ref, posc)
        prefix_ok = (sp_p < rlen) & (nd_p == sp_p) & (sp_p > pos)
        too_long = prefix_ok & (sp_p - pos > MAX_PREFIX_DIGITS)
        # each frame's digit window sums < 1e9: the wrapped difference
        # of two suffix-cumsum samples is exact (jnp-tier argument)
        val = _read1(suf_ref, posc) - _read1(
            suf_ref, jnp.minimum(sp_p, B - 1))
        body_start = sp_p + 1
        nxt = body_start + val
        frame_ok = prefix_ok & (~too_long) & (nxt <= rlen)
        live = frame_ok & (done == 0)
        rec = live & (k < ncap)
        si = jnp.minimum(k, ncap - 1)
        cur_s = _read1(starts_ref, si)
        cur_l = _read1(lens_ref, si)
        starts_ref[0, pl.ds(si, 1)] = jnp.where(
            rec, body_start, cur_s).reshape(1)
        lens_ref[0, pl.ds(si, 1)] = jnp.where(rec, val, cur_l).reshape(1)
        decline = decline | (live & (k >= ncap)).astype(_I32) \
            | (too_long & (done == 0)).astype(_I32)
        return (jnp.where(live, nxt, pos), count + live.astype(_I32),
                jnp.where(live, nxt, consumed),
                done | (~live).astype(_I32), decline)

    _, n, consumed, _, decline = jax.lax.fori_loop(
        0, ncap + 1, body, (zero, zero, zero, zero, zero))
    # stop analysis, mirroring the host scan (framing jnp tier): a
    # reachable space with a non-digit (or empty) prefix before it
    stop = jnp.clip(consumed, 0, B - 1)
    sp_stop = _read1(sp_ref, stop)
    nd_stop = _read1(nd_ref, stop)
    bad_prefix = (sp_stop < rlen) & ((nd_stop != sp_stop)
                                     | (sp_stop == consumed))
    err = ((consumed < rlen) & bad_prefix).astype(_I32)
    _store_meta(meta_ref, (n, consumed, err, decline))


@functools.partial(jax.jit, static_argnames=("ncap", "interpret"))
def frame_syslen_spans_pallas(region, rlen, ncap: int = 256,
                              interpret: bool = False):
    """Pallas tier of ``framing.frame_syslen_spans_jit``: identical
    output dict whenever ``decline`` is False (a declining region's
    exact ``n`` is unknowable to the bounded walk — both tiers raise
    FramingDeclined before anyone reads it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = region.shape[0]
    x = region.astype(_I32).reshape(1, B)
    rl = jnp.asarray(rlen, _I32).reshape(1, 1)
    starts, lens, meta = pl.pallas_call(
        functools.partial(_syslen_kernel, ncap=ncap),
        grid=(1,),
        in_specs=[pl.BlockSpec((1, B), lambda i: (0, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((1, ncap), lambda i: (0, 0)),
                   pl.BlockSpec((1, ncap), lambda i: (0, 0)),
                   pl.BlockSpec((1, 4), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, ncap), _I32),
                   jax.ShapeDtypeStruct((1, ncap), _I32),
                   jax.ShapeDtypeStruct((1, 4), _I32)],
        scratch_shapes=[pltpu.VMEM((1, B), _I32),
                        pltpu.VMEM((1, B), _I32),
                        pltpu.VMEM((1, B), _I32)],
        interpret=interpret,
    )(x, rl)
    return {"starts": starts[0], "lens": lens[0], "n": meta[0, 0],
            "consumed": meta[0, 1], "err": meta[0, 2] != 0,
            "decline": meta[0, 3] != 0}


# ---------------------------------------------------------------------------
# stage B: per-row gather (dynamic-slice copy from the VMEM region)

# rows per grid step: Mosaic wants the output block's second-minor dim
# divisible by 8 (or equal to the array's)
_GATHER_ROWG = 8


def _gather_kernel(r_ref, s_ref, l_ref, out_ref, *, max_len: int):
    from jax.experimental import pallas as pl

    pid = pl.program_id(0)
    col = jax.lax.broadcasted_iota(_I32, (1, max_len), 1)
    for j in range(_GATHER_ROWG):
        r = pid * _GATHER_ROWG + j
        s = _read1(s_ref, r)
        lv = l_ref[0, pl.ds(r, 1)].reshape(1, 1)
        seg = r_ref[0, pl.ds(s, max_len)].reshape(1, max_len)
        out_ref[pl.ds(j, 1), :] = jnp.where(
            col < jnp.minimum(lv, max_len), seg, 0)


@functools.partial(jax.jit, static_argnames=("max_len", "interpret"))
def frame_gather_pallas(region, starts, lens, max_len: int = 512,
                        interpret: bool = False):
    """Pallas tier of ``framing.frame_gather_jit``: dynamic-slice row
    copies from the VMEM-resident region, ``_GATHER_ROWG`` rows per
    grid step (the region is padded by ``max_len`` so a tail slice
    never clamps; rows are padded to the row-group)."""
    from jax.experimental import pallas as pl

    B = region.shape[0]
    rows = starts.shape[0]
    rows_p = -(-rows // _GATHER_ROWG) * _GATHER_ROWG
    x = jnp.pad(region.astype(_I32), (0, max_len)).reshape(1, B + max_len)
    s2 = jnp.pad(starts.astype(_I32), (0, rows_p - rows)).reshape(1, rows_p)
    l2 = jnp.pad(lens.astype(_I32), (0, rows_p - rows)).reshape(1, rows_p)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, max_len=max_len),
        grid=(rows_p // _GATHER_ROWG,),
        in_specs=[pl.BlockSpec((1, B + max_len), lambda i: (0, 0)),
                  pl.BlockSpec((1, rows_p), lambda i: (0, 0)),
                  pl.BlockSpec((1, rows_p), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((_GATHER_ROWG, max_len), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, max_len), _I32),
        interpret=interpret,
    )(x, s2, l2)
    return (out[:rows].astype(jnp.uint8),
            jnp.minimum(lens.astype(_I32), max_len))


# ---------------------------------------------------------------------------
# stage-1 structural classifier (jsonidx as a block kernel; the string
# machine is the compiled-NFA scan — jsonidx.NFA_TABLE)

_SI_KEYS_1D = ("ok", "n_fields")
_SI_KEYS_F = ("key_start", "key_end", "val_start", "val_end", "val_type",
              "key_esc", "val_esc")
_SI_BOOL = ("ok", "key_esc", "val_esc")

DEFAULT_BLOCK_ROWS = 256


def structural_index_pallas(batch, lens, max_fields: int,
                            nested: int = 0,
                            block_rows: int = DEFAULT_BLOCK_ROWS,
                            interpret: bool = False
                            ) -> Dict[str, jnp.ndarray]:
    """``jsonidx.structural_index`` as a Pallas block kernel: [br, L]
    byte tiles resident in VMEM, the compiled-NFA string machine, and
    manual/sum scan+extract forms — one HBM read of the bytes, one
    write of the packed index.  Channel-identical to the jnp screen
    (``scan_impl`` of either flavor) at ``extract_impl="sum"``."""
    from jax.experimental import pallas as pl

    N_orig, L = batch.shape
    N = N_orig
    br = min(block_rows, N)
    if N % br:
        pad = br - N % br
        batch = jnp.pad(batch, ((0, pad), (0, 0)))
        lens = jnp.pad(lens, (0, pad))
        N += pad
    x = batch.astype(_I32)
    lens2 = lens.astype(_I32).reshape(N, 1)
    F = max_fields

    def kernel(b_ref, l_ref, *outs):
        res = structural_index(b_ref[...], l_ref[...][:, 0], max_fields,
                               scan_impl="manual", extract_impl="sum",
                               nested=nested, string_impl="nfa")
        i = 0
        for k in _SI_KEYS_1D:
            outs[i][...] = res[k].astype(_I32).reshape(br, 1)
            i += 1
        for k in _SI_KEYS_F:
            outs[i][...] = res[k].astype(_I32)
            i += 1

    out_shape = (
        [jax.ShapeDtypeStruct((N, 1), _I32) for _ in _SI_KEYS_1D]
        + [jax.ShapeDtypeStruct((N, F), _I32) for _ in _SI_KEYS_F])
    out_specs = (
        [pl.BlockSpec((br, 1), lambda i: (i, 0)) for _ in _SI_KEYS_1D]
        + [pl.BlockSpec((br, F), lambda i: (i, 0)) for _ in _SI_KEYS_F])
    outs = pl.pallas_call(
        kernel,
        grid=(N // br,),
        in_specs=[pl.BlockSpec((br, L), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, lens2)
    res = {}
    i = 0
    for k in _SI_KEYS_1D:
        v = outs[i][:N_orig, 0]
        res[k] = (v != 0) if k in _SI_BOOL else v
        i += 1
    for k in _SI_KEYS_F:
        v = outs[i][:N_orig]
        res[k] = (v != 0) if k in _SI_BOOL else v
        i += 1
    return res


@functools.partial(jax.jit,
                   static_argnames=("max_fields", "nested", "interpret"))
def decode_jsonl_pallas(batch, lens, max_fields: int = None,
                        nested: int = None, interpret: bool = False):
    """The jsonl decode contract (``decode_jsonl_jit``) on the Pallas
    classifier."""
    from .jsonl import DEFAULT_MAX_FIELDS, NESTED_DEPTH

    if max_fields is None:
        max_fields = DEFAULT_MAX_FIELDS
    if nested is None:
        nested = NESTED_DEPTH
    return structural_index_pallas(batch, lens, max_fields,
                                   nested=nested, interpret=interpret)


# ---------------------------------------------------------------------------
# fused framing→decode entries: raw region bytes -> decode channels
# with the dense batch as an internal value (never a program output)

@functools.partial(jax.jit, static_argnames=(
    "sep", "strip_cr", "ncap", "max_len", "max_sd", "interpret"))
def fused_frame_decode_rfc5424(region, rlen, sep: int = 10,
                               strip_cr: bool = False, ncap: int = 256,
                               max_len: int = 512, max_sd: int = None,
                               interpret: bool = False):
    """line/nul-framed raw region -> rfc5424 decode channels in one
    program: spans walk, row gather, and the rfc5424 block kernel
    compose under one jit, so the [ncap, max_len] batch lives only
    between kernels.  Returns ``(spans, channels)``; rows past
    ``spans['n']`` decode padding and must be masked by the caller."""
    from .rfc5424 import DEFAULT_MAX_SD, decode_rfc5424_pallas

    if max_sd is None:
        max_sd = DEFAULT_MAX_SD
    spans = frame_sep_spans_pallas(region, rlen, sep=sep,
                                   strip_cr=strip_cr, ncap=ncap,
                                   interpret=interpret)
    batch, lens_c = frame_gather_pallas(region, spans["starts"],
                                        spans["lens"], max_len=max_len,
                                        interpret=interpret)
    dec = decode_rfc5424_pallas(batch, lens_c, max_sd=max_sd,
                                block_rows=min(DEFAULT_BLOCK_ROWS, ncap),
                                interpret=interpret)
    return spans, dec


@functools.partial(jax.jit, static_argnames=(
    "sep", "strip_cr", "ncap", "max_len", "max_fields", "nested",
    "interpret"))
def fused_frame_decode_jsonl(region, rlen, sep: int = 10,
                             strip_cr: bool = True, ncap: int = 256,
                             max_len: int = 512, max_fields: int = None,
                             nested: int = None,
                             interpret: bool = False):
    """line/nul-framed raw region -> jsonl structural index, dense
    batch internal (see ``fused_frame_decode_rfc5424``)."""
    spans = frame_sep_spans_pallas(region, rlen, sep=sep,
                                   strip_cr=strip_cr, ncap=ncap,
                                   interpret=interpret)
    batch, lens_c = frame_gather_pallas(region, spans["starts"],
                                        spans["lens"], max_len=max_len,
                                        interpret=interpret)
    dec = decode_jsonl_pallas(batch, lens_c, max_fields=max_fields,
                              nested=nested, interpret=interpret)
    return spans, dec


# ---------------------------------------------------------------------------
# decode-tier dispatch (probed by decode_*_submit between the AOT
# lookup and the jnp jit; never raises)

def _decode_state(fmt: str) -> Dict:
    return _DECODE_STATE.setdefault(fmt, {})


def decode_tier(fmt: str, batch_dev, lens_dev,
                max_sd: Optional[int] = None) -> Optional[Dict]:
    """Run one packed batch through the format's Pallas kernel, or
    return None (tier off, format unwired, cooldown, or a
    decline) — the caller falls to its ``decode_*_jit`` exactly like
    an AOT miss.  Failures ride the framing-style decline ladder:
    watchdogged first compile, DECLINE_LIMIT strikes then COOLDOWN
    batches of jnp decode before the next probe (compiled mode raises
    anything but a watchdog timeout — ``must_raise``)."""
    from ..obs import events as _events
    from ..utils.metrics import registry as _metrics
    from .device_common import guarded_compile_call
    from .framing import in_cooldown, note_decline, note_success

    if not engaged() or fmt not in ("rfc5424", "jsonl"):
        return None
    state = _decode_state(fmt)
    if in_cooldown(state):
        return None
    N, L = batch_dev.shape
    interp = interpret_mode()
    slot = f"pallas/decode_{fmt}:{N}x{L}"

    def run():
        # zero-JIT boot: a pallas-family AOT artifact replaces the
        # trace+compile (byte-identical by construction); None → live
        from . import aot as _aot

        out = _aot.pallas_call(f"decode_{fmt}",
                               (batch_dev, lens_dev),
                               _aot.pallas_statics(f"decode_{fmt}", N, 0))
        if out is not None:
            return out
        if fmt == "rfc5424":
            from .rfc5424 import DEFAULT_MAX_SD, decode_rfc5424_pallas

            return decode_rfc5424_pallas(
                batch_dev, lens_dev,
                max_sd=DEFAULT_MAX_SD if max_sd is None else max_sd,
                interpret=interp)
        return decode_jsonl_pallas(batch_dev, lens_dev, interpret=interp)

    try:
        out = guarded_compile_call(slot, run)
    except Exception as e:  # noqa: BLE001 - decline to the jnp tier, never lose the batch
        if must_raise(e):
            raise
        note_decline(state)
        _metrics.inc("pallas_declines")
        _events.emit("decode", "pallas_decline", route=fmt,
                     detail=f"{type(e).__name__}: {e}")
        return None
    note_success(state)
    _metrics.inc("pallas_rows", N)
    return out
