r"""Columnar generic JSON-lines decoder.

Scalar spec: flowgger_tpu/decoders/jsonl.py.  Stage 1 is the shared
simdjson-style structural index (tpu/jsonidx.py — the same quote
parity / bit-packed backslash ladder / packed-ordinal extractors the
GELF screen rides), run in **nested** mode: a structural-character
depth channel turns top-level container values (``"k": {...}`` /
``"k": [...]``) into VT_OBJECT/VT_ARRAY spans whose contents may nest
up to ``NESTED_DEPTH`` further levels; deeper rows — and anything
structurally surprising — flag to the scalar oracle.

Stage 2 (host, materialize_jsonl.py) slices spans, json-parses only
the tokens that need it (escaped strings, numbers, nested containers),
and routes the timestamp/host/message/level specials.

Two-tier field budget like tpu/gelf.py: rows with more than
DEFAULT_MAX_FIELDS keys (up to RESCUE_MAX_FIELDS) re-dispatch through
a lazily-compiled wider kernel in ``decode_jsonl_fetch``.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from .jsonidx import structural_index
from .rfc5424 import (
    best_extract_impl,
    best_scan_impl,
    rescue_refetch,
)

DEFAULT_MAX_FIELDS = 8
RESCUE_MAX_FIELDS = 24
# containers below the top-level object may nest this many levels; the
# structural index bounds total bracket depth at 1 + NESTED_DEPTH
NESTED_DEPTH = 4


def decode_jsonl(batch: jnp.ndarray, lens: jnp.ndarray,
                 max_fields: int = DEFAULT_MAX_FIELDS,
                 scan_impl: str = None,
                 extract_impl: str = None) -> Dict[str, jnp.ndarray]:
    if scan_impl is None:
        scan_impl = best_scan_impl()
    if extract_impl is None:
        extract_impl = best_extract_impl()
    return structural_index(batch, lens, max_fields, scan_impl,
                            extract_impl, nested=NESTED_DEPTH)


def decode_jsonl_submit(batch, lens, sharded=None):
    """Asynchronous dispatch (pair with decode_jsonl_fetch) — the jsonl
    leg of the block pipeline's double buffering.  The handle carries
    the caller's host arrays so the tier-2 rescue never pays a
    full-batch D2H just to slice a few rescue rows."""
    import jax.numpy as jnp

    if sharded is not None:
        b, ln = sharded.put(batch, lens)
        return (sharded.fn(b, ln), b, ln, batch, lens)
    from .aot import decode_call

    b, ln = jnp.asarray(batch), jnp.asarray(lens)
    # zero-JIT boot: a loaded AOT artifact replaces the trace+compile
    out = decode_call("jsonl", (b, ln))
    if out is None:
        out = decode_jsonl_jit(b, ln)
    return (out, b, ln, batch, lens)


_FIELD_KEYS = ("key_start", "key_end", "val_start", "val_end", "val_type",
               "key_esc", "val_esc")


def decode_jsonl_fetch(handle):
    """Block on a submitted decode; rows whose field count lies in
    (DEFAULT_MAX_FIELDS, RESCUE_MAX_FIELDS] re-dispatch through the
    wider tier-2 kernel so they stay on-device.  Field channels come
    back widened to RESCUE_MAX_FIELDS when tier 2 ran."""
    import numpy as np

    out, _b_dev, _ln_dev, batch, lens = handle
    host = {k: np.asarray(v) for k, v in out.items()}
    if host["key_start"].shape[1] >= RESCUE_MAX_FIELDS:
        return host
    nf = host["n_fields"]
    over = np.flatnonzero(~host["ok"] & (nf > DEFAULT_MAX_FIELDS)
                          & (nf <= RESCUE_MAX_FIELDS))

    def dispatch(sub_b, sub_l):
        out2 = decode_jsonl_jit(jnp.asarray(sub_b), jnp.asarray(sub_l),
                                max_fields=RESCUE_MAX_FIELDS)
        return {k: np.asarray(v) for k, v in out2.items()}

    return rescue_refetch(host, batch, lens, over, _FIELD_KEYS, dispatch,
                          RESCUE_MAX_FIELDS)


@functools.partial(jax.jit, static_argnames=("max_fields", "demand"))
def decode_jsonl_jit(batch, lens, max_fields=DEFAULT_MAX_FIELDS,
                     demand=None):
    """``demand`` (static frozenset): keep only the channels the
    consumer reads so XLA dead-code-eliminates the rest."""
    out = decode_jsonl(batch, lens, max_fields=max_fields)
    if demand is not None:
        out = {k: v for k, v in out.items() if k in demand}
    return out
