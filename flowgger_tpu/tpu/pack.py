"""Host-side batch packing: framed lines → dense [N, max_len] batches.

The arena replaces the reference's per-line ``Vec<u8>`` channel payloads
(mod.rs:461-468): lines live in one contiguous chunk described by
offset/length vectors; the dense pack is a native threaded memcpy
(flowgger_tpu/native.py) with a vectorized numpy fallback.  Shapes are
bucketed to bound XLA recompilations: by default every power of two,
or — with ``input.tpu_shape_buckets`` configured — a small geometric
grid (``configure_shape_buckets``) so steady-state traffic hits a
handful of compiled shapes instead of one per pow2 (simdjson's lesson:
the parallel-decode win evaporates when per-input setup cost isn't
amortized; each fresh (rows, max_len) shape is a fresh XLA compile).
Padding rows have length 0 and fall outside ``n_real``, so bucket
choice never changes emitted bytes.  Every packed shape is recorded in
the ``distinct_compiled_shapes`` gauge — the number to watch when a
varied-length stream is compile-thrashing.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Tuple

import numpy as np

_MIN_ROWS = 256
_MIN_BYTES = 1 << 14

# row-bucket grid (sorted tuple) set by configure_shape_buckets; None =
# legacy every-power-of-two bucketing.  Module-wide like _PACK_THREADS:
# only an explicit config key touches it (BatchHandler guards), so a
# default-configured handler never resets another handler's grid.
_SHAPE_BUCKETS: Optional[Tuple[int, ...]] = None

# every (rows, max_len) shape this process has packed — the gauge that
# proves (or disproves) shape-bucket amortization
_shapes_seen: set = set()
_shapes_lock = threading.Lock()

# thread-sliced pack (``input.pack_threads``): the dense pack is a pure
# bytes→ndarray scatter with no cross-row state, so rows slice evenly
# across threads.  1 = single Python-side slice (the native memcpy tier
# keeps its own internal default); >1 overrides the native thread count
# AND slices the numpy fallback, which otherwise runs single-threaded.
_PACK_THREADS = 1


def configure_pack_threads(n: int) -> None:
    global _PACK_THREADS
    _PACK_THREADS = max(1, int(n))


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def shape_bucket_grid(n_buckets: int, cap_rows: int) -> Tuple[int, ...]:
    """A geometric grid of ``n_buckets`` row counts from ``_MIN_ROWS``
    up to (the next power of two covering) ``cap_rows``, each rounded up
    to a power of two and deduplicated — so a grid request can yield
    fewer, never more, distinct shapes."""
    top = _next_pow2(max(int(cap_rows), _MIN_ROWS))
    if n_buckets <= 1 or top <= _MIN_ROWS:
        return (top,)
    ratio = (top / _MIN_ROWS) ** (1.0 / (n_buckets - 1))
    vals = {top}
    for i in range(n_buckets):
        vals.add(min(top, _next_pow2(int(round(_MIN_ROWS * ratio ** i)))))
    return tuple(sorted(vals))


def configure_shape_buckets(grid) -> None:
    """Install the row-bucket grid (an iterable of row counts), or
    ``None`` to restore legacy every-power-of-two bucketing."""
    global _SHAPE_BUCKETS
    _SHAPE_BUCKETS = (tuple(sorted({int(g) for g in grid}))
                      if grid else None)


def active_bucket_grid() -> Optional[Tuple[int, ...]]:
    return _SHAPE_BUCKETS


def bucket_rows(n: int) -> int:
    """Padded row count for ``n`` real rows: the smallest grid bucket
    that fits, or (legacy / beyond the grid top) the next power of two.
    Rows above the top can happen — a flush dispatches *all* pending
    lines, which can exceed ``tpu_batch_size`` when a large region
    arrives at once — and must still pack rather than truncate."""
    n = max(int(n), 1)
    if _SHAPE_BUCKETS:
        for b in _SHAPE_BUCKETS:
            if b >= n:
                return b
    return max(_MIN_ROWS, _next_pow2(n))


def shapes_seen() -> set:
    """Copy of every (rows, max_len) shape packed so far (tests diff
    this around a stream to bound compile churn)."""
    with _shapes_lock:
        return set(_shapes_seen)


def _note_shape(rows: int, max_len: int) -> None:
    with _shapes_lock:
        _shapes_seen.add((rows, max_len))
        count = len(_shapes_seen)
    from ..utils.metrics import registry as _metrics

    _metrics.set_gauge("distinct_compiled_shapes", count)


def _split_np(chunk: bytes, strip_cr: bool = True, sep: int = 10
              ) -> Tuple[np.ndarray, np.ndarray, int, bytes]:
    """Numpy separator scan: (starts, lens, n, carry) —
    BufRead::lines semantics for ``sep=\\n`` (one trailing CR stripped),
    BufRead::split semantics for other separators (nul framing)."""
    buf = np.frombuffer(chunk, dtype=np.uint8)
    nl = np.flatnonzero(buf == sep).astype(np.int32)
    n = int(nl.size)
    if n == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int32), 0, chunk
    starts = np.concatenate([np.zeros(1, np.int32), nl[:-1] + 1])
    ends = nl.copy()
    if strip_cr:
        has_cr = (ends > starts) & (buf[np.maximum(ends - 1, 0)] == 13)
        ends = ends - has_cr.astype(np.int32)
    return starts, ends - starts, n, chunk[int(nl[-1]) + 1:]


def _split(chunk: bytes, strip_cr: bool = True, sep: int = 10):
    from .. import native

    if sep == 10:
        res = native.split_chunk_native(chunk, strip_cr)
        if res is not None:
            return res
    return _split_np(chunk, strip_cr, sep)


def _pack_dense(chunk: bytes, starts: np.ndarray, lens: np.ndarray,
                max_len: int, np_rows: int) -> Tuple[np.ndarray, np.ndarray]:
    """(batch [np_rows, max_len] u8, clipped lens [np_rows]) — native
    threaded memcpy or the (optionally thread-sliced) numpy
    clip/mask/gather fallback."""
    from .. import native

    nt = _PACK_THREADS
    packed = native.pack_chunk_native(chunk, starts, lens, max_len, np_rows,
                                      n_threads=nt if nt > 1 else None)
    if packed is not None:
        return packed
    n = len(starts)
    buf = np.frombuffer(chunk, dtype=np.uint8)
    lens_c = np.minimum(lens, max_len)
    batch = np.zeros((np_rows, max_len), dtype=np.uint8)
    col = np.arange(max_len, dtype=np.int32)[None, :]

    def _fill(a: int, b: int) -> None:
        idx = starts[a:b, None] + col
        np.clip(idx, 0, max(buf.size - 1, 0), out=idx)
        mask = col < lens_c[a:b, None]
        np.multiply(buf[idx], mask, out=batch[a:b], casting="unsafe")

    if n:
        if nt > 1 and n >= 4 * nt:
            from concurrent.futures import ThreadPoolExecutor

            bounds = [(i * n // nt, (i + 1) * n // nt) for i in range(nt)]
            with ThreadPoolExecutor(max_workers=nt) as ex:
                list(ex.map(lambda ab: _fill(*ab), bounds))
        else:
            _fill(0, n)
    lens_p = np.zeros(np_rows, dtype=np.int32)
    lens_p[:n] = lens_c
    return batch, lens_p


def _note_stage(name: str, seconds: float) -> None:
    """Host pack-stage walls, split so the device-framing tier's win
    is visible per component: ``pack_slice_seconds`` (separator scan /
    span assembly) + ``pack_copy_seconds`` (dense arena memcpy) sum to
    ``pack_stage_seconds`` — the host stage device framing deletes."""
    from ..utils.metrics import registry as _metrics

    _metrics.add_seconds(name, seconds)
    _metrics.add_seconds("pack_stage_seconds", seconds)


def note_overlen(lens: np.ndarray, max_len: int) -> None:
    """Count one packed batch's rows longer than the device's row, and
    the bytes past ``max_len`` that the device never sees (the block
    encoders hand such a row to the scalar oracle whole)."""
    over = lens > max_len
    k = int(np.count_nonzero(over))
    if k:
        from ..utils.metrics import registry as _metrics

        _metrics.inc("overlen_rows", k)
        _metrics.inc("overlen_bytes_clipped",
                     int(lens[over].sum(dtype=np.int64)) - k * max_len)


def _finish(chunk: bytes, starts: np.ndarray, lens: np.ndarray, n: int,
            max_len: int):
    import time as _time

    np_rows = bucket_rows(n)
    _note_shape(np_rows, max_len)
    note_overlen(lens, max_len)
    t0 = _time.perf_counter()
    batch, lens_p = _pack_dense(chunk, starts, lens, max_len, np_rows)
    _note_stage("pack_copy_seconds", _time.perf_counter() - t0)
    starts_p = np.zeros(np_rows, dtype=np.int32)
    starts_p[:n] = starts
    return batch, lens_p, chunk, starts_p, np.asarray(lens, dtype=np.int32), n


def pack_lines_2d(lines: List[bytes], max_len: int):
    """Pack a list of framed lines.  Returns
    (batch, clipped_lens, chunk, starts, orig_lens, n_real) with row
    count bucketed to a power of two."""
    import time as _time

    t0 = _time.perf_counter()
    n = len(lines)
    chunk = b"".join(lines)
    orig_lens = np.fromiter((len(ln) for ln in lines), dtype=np.int32, count=n)
    starts = np.zeros(n, dtype=np.int32)
    if n > 1:
        np.cumsum(orig_lens[:-1], out=starts[1:])
    _note_stage("pack_slice_seconds", _time.perf_counter() - t0)
    return _finish(chunk, starts, orig_lens, n, max_len)


def pack_region_2d(region: bytes, max_len: int, sep: int = 10,
                   strip_cr: bool = True):
    """Pack a region of complete separator-terminated messages straight
    into a dense batch — the zero-per-line-Python fast path.  Same
    return contract as pack_lines_2d."""
    import time as _time

    t0 = _time.perf_counter()
    starts, lens, n, _carry = _split(region, strip_cr, sep)
    _note_stage("pack_slice_seconds", _time.perf_counter() - t0)
    return _finish(region, starts, lens, n, max_len)


def pack_spans_2d(chunks: List[bytes], span_sets: List[Tuple[np.ndarray, np.ndarray]],
                  max_len: int):
    """Pack pre-framed spans (syslen framing: the scanner already knows
    every message's offset/length) from one or more chunk fragments.
    Same return contract as pack_lines_2d."""
    import time as _time

    t0 = _time.perf_counter()
    if len(chunks) == 1:
        chunk = chunks[0]
        starts, lens = span_sets[0]
    else:
        offs = np.cumsum([0] + [len(c) for c in chunks[:-1]])
        chunk = b"".join(chunks)
        starts = np.concatenate(
            [s + np.int32(o) for (s, _), o in zip(span_sets, offs)]) \
            if span_sets else np.zeros(0, np.int32)
        lens = np.concatenate([l for _, l in span_sets]) \
            if span_sets else np.zeros(0, np.int32)
    _note_stage("pack_slice_seconds", _time.perf_counter() - t0)
    return _finish(chunk, np.asarray(starts, dtype=np.int32),
                   np.asarray(lens, dtype=np.int32), len(starts), max_len)


def subset_packed(packed, idx: np.ndarray):
    """Row-subset of a packed tuple (auto-detect partitioning): rows
    re-bucketed through the same grid so kernel shapes stay cached."""
    batch, lens, chunk, starts, orig_lens, _n = packed
    m = int(idx.size)
    rows = bucket_rows(m)
    _note_shape(rows, batch.shape[1])
    b2 = np.zeros((rows, batch.shape[1]), dtype=np.uint8)
    l2 = np.zeros(rows, dtype=np.int32)
    s2 = np.zeros(rows, dtype=np.int32)
    if m:
        b2[:m] = batch[idx]
        l2[:m] = lens[idx]
        s2[:m] = starts[idx]
    return b2, l2, chunk, s2, np.asarray(orig_lens)[idx], m


# kept for callers that want raw framing metadata (tests, future C++ IO)
def split_chunk(chunk: bytes, strip_cr: bool = True):
    """(starts, lens, n, carry) over a raw chunk."""
    return _split(chunk, strip_cr)


def pack_lines(lines: List[bytes]) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Legacy 1-D layout: (padded chunk u8[B], starts, lens, n_real) for
    the on-device pack path (graft entry / CPU backend)."""
    n = len(lines)
    chunk = b"".join(lines)
    lens = np.fromiter((len(ln) for ln in lines), dtype=np.int32, count=n)
    starts = np.zeros(n, dtype=np.int32)
    if n > 1:
        np.cumsum(lens[:-1], out=starts[1:])
    np_rows = max(_MIN_ROWS, _next_pow2(n))
    nb = max(_MIN_BYTES, _next_pow2(len(chunk)))
    buf = np.zeros(nb, dtype=np.uint8)
    if chunk:
        buf[: len(chunk)] = np.frombuffer(chunk, dtype=np.uint8)
    starts_p = np.zeros(np_rows, dtype=np.int32)
    lens_p = np.zeros(np_rows, dtype=np.int32)
    starts_p[:n] = starts
    lens_p[:n] = lens
    return buf, starts_p, lens_p, n
