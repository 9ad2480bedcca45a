#!/usr/bin/env python
"""Benchmark: batched RFC5424 decode + end-to-end pipeline throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} —
value is sustained on-device RFC5424 columnar decode throughput
(lines/sec/chip) for 1M-line batches; vs_baseline is the ratio against
BASELINE.json's 50M lines/sec north star.  Extra keys report the
end-to-end pipeline rate (stdin region → pack → device decode → columnar
GELF block encode → file sink), the host-stage-only rate (everything but
the device kernel — the number that matters once device decode
overlaps ingest), per-stage time shares, and the backend used.

Where it runs: the full run measures on a TPU and fails where JAX finds
none — there is no CPU fallback, and no CPU number is ever printed
under a device metric's name.  ``--smoke`` and ``FLOWGGER_BENCH_SMOKE=1``
are the CPU CI gate (correctness and guard-cost checks at tiny shapes);
their output says so and carries no device number.

Measurement methodology: the device number runs K decode iterations
chained by a data dependency inside ONE jitted fori_loop (iteration i+1
consumes a bit derived from iteration i's outputs) and fetches a scalar
digest at the end: wall time then provably covers K sequential decodes.
The e2e number drives the production BatchHandler (device-encode tier
with on-device row compaction, host tiers for fallback rows) and uses
the sink writes of the final framed bytes as its completion barrier —
every byte written came off the device.
"""

import json
import os
import random
import sys
import time

import numpy as np

BASELINE_LINES_PER_SEC = 50_000_000  # BASELINE.json north_star
BATCH_LINES = 1_000_000              # BASELINE.json metric: 1M-line batches
MAX_LEN = 256
CHAIN = 16
TRIALS = 3
E2E_BATCH = 262_144


def gen_lines(n: int) -> list:
    rng = random.Random(42)
    out = []
    for i in range(n):
        out.append(
            (
                f"<{rng.randrange(192)}>1 2015-08-05T15:53:45.637824Z "
                f"host{i % 100} app{i % 10} {i % 1000} MSGID "
                f'[ex@32473 iut="{i % 9}" eventSource="Application" '
                f'eventID="{1000 + i % 999}"] '
                f"An application event log entry number {i}"
            ).encode()
        )
    return out


def digest_all(jnp, out):
    """Fold EVERY kernel output channel into a scalar digest: a partial
    digest lets XLA dead-code-eliminate the channels it doesn't reach,
    and the benched kernel silently becomes a pruned subset of the one
    the pipeline runs (caught in round 2: a 3-channel digest made the
    kernel look 2.4x faster than it is)."""
    acc = jnp.int32(0)
    for v in out.values():
        acc = acc + v.astype(jnp.int32).sum()
    return acc


def bench_e2e(lines, jax, jnp, extra):
    """End-to-end through the production handler: complete-line regions
    → BatchHandler.ingest_chunk → _emit_fast (device-encode tier with
    on-device row compaction when it engages, host span tiers for
    fallback rows) → merger-framed EncodedBlocks on the queue → writer
    thread → file sink.  Reports device-encode engagement and D2H bytes
    per row alongside the rates."""
    import os
    import queue as queue_mod
    import tempfile
    import threading

    from flowgger_tpu.config import Config
    from flowgger_tpu.block import EncodedBlock
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.mergers import NulMerger
    from flowgger_tpu.utils.metrics import registry as metrics
    from flowgger_tpu.tpu.batch import BatchHandler

    region = b"".join(ln + b"\n" for ln in lines)
    n_lines = len(lines)
    batch_rows = min(n_lines, 65536)  # 4 in-flight windows over the corpus
    cfg = Config.from_string(
        f"[input]\ntpu_batch_size = {batch_rows}\n"
        f"tpu_max_line_len = {MAX_LEN}\n")
    sink_path = os.path.join(tempfile.gettempdir(), "flowgger_bench_out")
    _SHUTDOWN = object()

    best = None
    best_snap = None
    # two trials always: the first pays the jit compiles, best-of-2
    # reports the warm path (the degraded-CPU corpus is sized so both
    # fit the bench window)
    for trial in range(2):
        tx = queue_mod.Queue()
        handler = BatchHandler(
            tx, RFC5424Decoder(), GelfEncoder(Config.from_string("")),
            cfg, fmt="rfc5424", start_timer=False, merger=NulMerger())
        sink_s = [0.0]

        def writer():
            with open(sink_path, "wb") as sink:
                while True:
                    item = tx.get()
                    if item is _SHUTDOWN:
                        sink.flush()
                        os.fsync(sink.fileno())
                        return
                    t0 = time.perf_counter()
                    sink.write(item.data if isinstance(item, EncodedBlock)
                               else item)
                    sink_s[0] += time.perf_counter() - t0

        wt = threading.Thread(target=writer)
        snap0 = metrics.snapshot()
        t0 = time.perf_counter()
        wt.start()
        # feed region slices sized to one batch window so the handler's
        # in-flight window overlap actually runs
        approx = max(1, len(region) // max(1, n_lines // batch_rows))
        pos = 0
        while pos < len(region):
            cut = region.rfind(b"\n", pos, pos + approx)
            if cut < 0:
                # no newline inside the window: take the next one forward
                # instead of swallowing the rest of the region in one
                # chunk (ADVICE r4 — keeps the double-buffer overlap real)
                cut = region.find(b"\n", pos + approx)
            cut = len(region) if cut < 0 else cut + 1
            handler.ingest_chunk(region[pos:cut])
            pos = cut
        handler.flush()
        tx.put(_SHUTDOWN)
        wt.join()
        handler.close()
        total = time.perf_counter() - t0
        if best is None or total < best:
            best = total
            snap1 = metrics.snapshot()
            best_snap = {k: snap1.get(k, 0) - snap0.get(k, 0)
                         for k in ("device_fetch_seconds", "encode_seconds",
                                   "device_encode_declined_seconds",
                                   "device_encode_rows", "fallback_rows",
                                   "device_encode_scalar_rows",
                                   "device_encode_fetch_bytes",
                                   "device_encode_out_bytes",
                                   "device_encode_declined")}
            best_snap["sink_seconds"] = sink_s[0]
    os.unlink(sink_path)

    e2e_rate = n_lines / best
    dev_s = best_snap["device_fetch_seconds"]
    host_time = max(best - dev_s, 1e-9)
    host_rate = n_lines / host_time
    dev_rows = int(best_snap["device_encode_rows"])
    fetch_per_row = (best_snap["device_encode_fetch_bytes"] / dev_rows
                     if dev_rows else 0.0)
    out_per_row = (best_snap["device_encode_out_bytes"] / dev_rows
                   if dev_rows else 0.0)
    print(
        f"e2e pipeline (BatchHandler): {best:.2f}s for {n_lines} lines -> "
        f"{e2e_rate / 1e6:.2f}M lines/s "
        f"(device+fetch {dev_s:.2f}s, encode "
        f"{best_snap['encode_seconds']:.2f}s, sink "
        f"{best_snap['sink_seconds']:.2f}s); "
        f"host stages only: {host_rate / 1e6:.2f}M lines/s; "
        f"device-encode rows {dev_rows}/{n_lines} "
        f"({fetch_per_row:.0f} B/row fetched vs {out_per_row:.0f} B/row "
        f"emitted)",
        file=sys.stderr,
    )
    extra["e2e_lines_per_sec"] = round(e2e_rate)
    extra["e2e_host_stages_lines_per_sec"] = round(host_rate)
    extra["e2e_device_encode_rows"] = dev_rows
    extra["e2e_rows"] = n_lines
    extra["e2e_fallback_rows"] = int(best_snap["fallback_rows"])
    extra["e2e_device_encode_declined"] = int(
        best_snap["device_encode_declined"])
    extra["e2e_fetch_bytes_per_row"] = round(fetch_per_row, 1)
    extra["e2e_out_bytes_per_row"] = round(out_per_row, 1)
    extra["e2e_stage_seconds"] = {
        "device_fetch": round(dev_s, 3),
        "encode": round(best_snap["encode_seconds"], 3),
        "declined": round(best_snap["device_encode_declined_seconds"], 3),
        "sink": round(best_snap["sink_seconds"], 3),
    }


def bench_e2e_overlap(lines, extra, smoke, lanes=1, trials=2):
    """End-to-end rate of the overlap executor: the same pipeline as
    bench_e2e but driven the way production streams it — a long run of
    window-sized batches through ONE handler, so the bounded in-flight
    window (input.tpu_inflight, default 2) overlaps batch N+1's
    pack/dispatch with batch N's fetch/encode/sink, and the
    device-vs-host encode-route economics operate across batches.
    ``lanes > 1`` engages multi-device lane dispatch (input.tpu_lanes):
    batches round-robin across per-device lanes and the result rides
    the ``e2e_multilane_lines_per_sec`` key instead.

    The serial number keeps its historical meaning (one full-corpus
    batch, fresh handler per trial: every stage's latency summed);
    this one answers "what does the executor sustain".  Batches are
    sized to the fallback-corpora shape so the kernels for
    [OVERLAP_BATCH, MAX_LEN] are already warm."""
    import os
    import queue as queue_mod
    import tempfile
    import threading

    from flowgger_tpu.block import EncodedBlock
    from flowgger_tpu.config import Config
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.mergers import NulMerger
    from flowgger_tpu.tpu.batch import BatchHandler
    from flowgger_tpu.utils.metrics import registry as metrics

    # smoke compares the executor against the serial path at the SAME
    # batch shape (the win measured is pure pipelining); the full run
    # streams 8192-row batches — the executor's operating point — so
    # the window sees a long steady stream
    batch_rows = len(lines) if smoke else 8_192
    # smoke gates on rate ratios: longer streams drown the fill/drain
    # and scheduler noise that make short windows flap
    repeats = 8 if smoke else 4
    region = b"".join(ln + b"\n" for ln in lines)
    n_lines = len(lines) * repeats
    cfg = Config.from_string(
        f"[input]\ntpu_batch_size = {batch_rows}\n"
        f"tpu_max_line_len = {MAX_LEN}\n"
        "tpu_inflight = 2\n"
        + (f"tpu_lanes = {lanes}\n" if lanes > 1 else ""))
    sink_path = os.path.join(tempfile.gettempdir(), "flowgger_bench_ovl")
    _SHUTDOWN = object()

    best = None
    best_snap = None
    for trial in range(trials):
        tx = queue_mod.Queue()
        handler = BatchHandler(
            tx, RFC5424Decoder(), GelfEncoder(Config.from_string("")),
            cfg, fmt="rfc5424", start_timer=False, merger=NulMerger())

        def writer():
            with open(sink_path, "wb") as sink:
                while True:
                    item = tx.get()
                    if item is _SHUTDOWN:
                        sink.flush()
                        os.fsync(sink.fileno())
                        return
                    sink.write(item.data if isinstance(item, EncodedBlock)
                               else item)

        wt = threading.Thread(target=writer)
        # feed exactly batch_rows lines per slice so every size-
        # triggered flush dispatches one [batch_rows, MAX_LEN] batch —
        # the shape the fallback-corpora section already compiled —
        # and the in-flight window sees a steady stream
        import numpy as _np

        nl = _np.frombuffer(region, dtype=_np.uint8) == 10
        ends = (_np.flatnonzero(nl) + 1).tolist()
        cuts = [0] + ends[batch_rows - 1::batch_rows]
        if cuts[-1] != len(region):
            cuts.append(len(region))
        snap0 = metrics.snapshot()
        t0 = time.perf_counter()
        wt.start()
        for _ in range(repeats):
            for a, b in zip(cuts, cuts[1:]):
                handler.ingest_chunk(region[a:b])
        handler.flush()
        tx.put(_SHUTDOWN)
        wt.join()
        handler.close()
        total = time.perf_counter() - t0
        if best is None or total < best:
            best = total
            snap1 = metrics.snapshot()
            lane_keys = tuple(f"lane{i}_rows" for i in range(lanes))
            best_snap = {k: snap1.get(k, 0) - snap0.get(k, 0)
                         for k in ("dispatch_seconds", "fetch_seconds",
                                   "overlap_stall_seconds",
                                   "device_fetch_seconds", "encode_seconds",
                                   "encode_route_device",
                                   "encode_route_host",
                                   "device_encode_rows", "fallback_rows",
                                   "batches", "fetch_bytes_saved")
                         + lane_keys}
            best_econ = ([e.snapshot() for e in handler._econs]
                         if lanes > 1 else handler._econ.snapshot())

    os.unlink(sink_path)
    rate = n_lines / best
    serial = extra.get("e2e_lines_per_sec", 0)
    print(
        f"e2e overlap executor ({lanes} lane{'s' if lanes > 1 else ''}): "
        f"{best:.2f}s for {n_lines} lines "
        f"({int(best_snap['batches'])} batches of {batch_rows}, window 2) "
        f"-> {rate / 1e6:.2f}M lines/s "
        + (f"({rate / serial:.1f}x serial)" if serial else ""),
        file=sys.stderr,
    )
    print(
        f"  stages: dispatch {best_snap['dispatch_seconds']:.2f}s, "
        f"fetch-behind {best_snap['fetch_seconds']:.2f}s, "
        f"stall {best_snap['overlap_stall_seconds']:.2f}s; "
        f"routes: device {int(best_snap['encode_route_device'])} / "
        f"host {int(best_snap['encode_route_host'])} batches; "
        f"econ {best_econ}",
        file=sys.stderr,
    )
    stage_seconds = {
        "dispatch": round(best_snap["dispatch_seconds"], 3),
        "fetch_behind": round(best_snap["fetch_seconds"], 3),
        "stall": round(best_snap["overlap_stall_seconds"], 3),
        "device_fetch": round(best_snap["device_fetch_seconds"], 3),
        "encode": round(best_snap["encode_seconds"], 3),
    }
    routes = {
        "device_batches": int(best_snap["encode_route_device"]),
        "host_batches": int(best_snap["encode_route_host"]),
        "device_rows": int(best_snap["device_encode_rows"]),
        "fetch_bytes_saved": int(best_snap["fetch_bytes_saved"]),
    }
    if lanes > 1:
        per_lane = {f"lane{i}": int(best_snap.get(f"lane{i}_rows", 0))
                    for i in range(lanes)}
        print(f"  per-lane rows: {per_lane}", file=sys.stderr)
        extra["e2e_multilane_lines_per_sec"] = round(rate)
        extra["e2e_multilane_lanes_run"] = lanes
        extra["e2e_multilane_lane_rows"] = per_lane
        single = extra.get("e2e_overlap_lines_per_sec", 0)
        extra["e2e_multilane_vs_single_lane"] = (round(rate / single, 2)
                                                 if single else None)
        extra["e2e_multilane_stage_seconds"] = stage_seconds
        return
    extra["e2e_overlap_lines_per_sec"] = round(rate)
    extra["e2e_overlap_rows"] = n_lines
    extra["e2e_overlap_lanes_run"] = lanes
    extra["e2e_overlap_batches"] = int(best_snap["batches"])
    extra["e2e_overlap_vs_serial"] = (round(rate / serial, 2)
                                      if serial else None)
    extra["e2e_overlap_stage_seconds"] = stage_seconds
    extra["e2e_overlap_routes"] = routes


def bench_fallback_corpora(jax, jnp, extra, small: bool):
    """Tier-economics measurement (VERDICT r3 #5): adversarial corpora
    through the device-encode route, reporting device-tier residency,
    decline rate, and scalar-fallback share — the numbers that justify
    FALLBACK_FRAC / E_CAP / the 6-pair tier, instead of guessing."""
    from flowgger_tpu.config import Config
    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.mergers import LineMerger
    from flowgger_tpu.tpu import device_gelf, pack, rfc5424
    from flowgger_tpu.utils.metrics import registry as metrics

    n = 2_048 if small else 65_536
    rng = random.Random(9)

    def syslog(i, sd, msg):
        return (f'<{i % 192}>1 2023-09-20T12:35:45.{i % 1000:03d}Z '
                f'h{i % 50} app {i} m {sd} {msg}').encode()

    corpora = {
        # the flagship corpus: everything should stay on the device tier
        "clean": [syslog(i, f'[sd@1 k="{i}" x="y"]', f"event {i}")
                  for i in range(n)],
        # escaped quotes in values: val_has_esc rows leave the device
        # tier (host span tiers), E_CAP bounds the escape ladder
        "escape_heavy": [
            syslog(i, f'[sd@1 k="a\\"b{i}" x="c\\\\d"]', "esc " * 3)
            for i in range(n)],
        # 8 pairs: beyond the 6-pair base tier — the wide (16-pair)
        # escalation kernel keeps these on-device (round 5)
        "pairs8": [
            syslog(i, "[sd@1 " + " ".join(
                f'k{j}="{j}"' for j in range(8)) + "]", "multi")
            for i in range(n)],
        # 20 pairs: beyond rescue — scalar oracle rows
        "pairs20": [
            syslog(i, "[sd@1 " + " ".join(
                f'k{j}="{j}"' for j in range(20)) + "]", "multi")
            for i in range(n)],
        # near-unique sub-second stamps: the native timestamp formatter
        # path (dedup would save nothing here)
        "unique_ts": [
            (f'<13>1 2023-09-20T12:35:45.{rng.randrange(10**9):09d}Z '
             f'h app {i} m [sd@1 k="v"] unique stamp {i}').encode()
            for i in range(n)],
    }

    enc = GelfEncoder(Config.from_string(""))
    merger = LineMerger()
    # warmup: compile the decode + both encode-kernel phases once (same
    # [n, MAX_LEN] shape as every corpus) so the first corpus'
    # encode_ms is execution, not compilation
    warm = pack.pack_lines_2d(corpora["clean"], MAX_LEN)
    device_gelf.fetch_encode(
        rfc5424.decode_rfc5424_submit(warm[0], warm[1]), warm, enc,
        merger, route_state={})
    results = {}
    for name, lines in corpora.items():
        packed = pack.pack_lines_2d(lines, MAX_LEN)
        handle = rfc5424.decode_rfc5424_submit(packed[0], packed[1])
        snap0 = metrics.snapshot()
        t0 = time.perf_counter()
        res, _ = device_gelf.fetch_encode(handle, packed, enc, merger,
                                          route_state={})
        dt = time.perf_counter() - t0
        snap1 = metrics.snapshot()
        d = {k: snap1.get(k, 0) - snap0.get(k, 0)
             for k in ("device_encode_rows", "device_encode_scalar_rows",
                       "device_encode_declined")}
        if res is None:
            # declined: the span-fetch host path takes over
            results[name] = {"declined": True,
                             "device_rows_pct": 0.0,
                             "route": "host-span"}
        else:
            total = max(1, len(lines))
            results[name] = {
                "declined": False,
                "device_rows_pct": round(
                    100.0 * d["device_encode_rows"] / total, 1),
                "scalar_rows_pct": round(
                    100.0 * d["device_encode_scalar_rows"] / total, 1),
                "encode_ms": round(dt * 1e3, 1),
            }
        print(f"corpus {name}: {results[name]}", file=sys.stderr)

    # ltsv + rfc3164 tier residency (VERDICT r4 weak #3: the corpora
    # were rfc5424-only, so nothing measured how often the other device
    # tiers actually engage)
    from flowgger_tpu.decoders.ltsv import LTSVDecoder
    from flowgger_tpu.tpu import (device_ltsv, device_rfc3164, ltsv,
                                  rfc3164)

    ltsv_dec = LTSVDecoder(Config.from_string(""))

    def ltsv_line(i, stamp):
        return (f"time:{stamp}\thost:h{i % 50}\tstatus:{i % 600}\t"
                f"path:/api/{i % 97}\tmessage:request {i}").encode()

    other = {
        # rfc3339 stamps: the original device tier
        "ltsv_rfc3339": [
            ltsv_line(i, f"2023-09-20T12:35:45.{i % 1000:03d}Z")
            for i in range(n)],
        # unix-literal stamps — LTSV's first-listed, most common form
        # (ltsv_decoder.rs:224-267); round 5 put these on-device
        "ltsv_unix_ts": [
            ltsv_line(i, f"17319{i % 100000:05d}.{i % 1000:03d}")
            for i in range(n)],
        # apache-english stamps: per-row host parses, off-tier by design
        "ltsv_apache_ts": [
            ltsv_line(i, "[20/Sep/2023:12:35:45 +0000]")
            for i in range(n)],
        "rfc3164": [
            (f"<{i % 192}>Sep 20 12:35:{i % 60:02d} h{i % 50} "
             f"app[{i}]: event {i}").encode()
            for i in range(n)],
    }
    routes = {
        "ltsv": (ltsv.decode_ltsv_submit, device_ltsv.fetch_encode,
                 {"decoder": ltsv_dec}),
        "rfc3164": (rfc3164.decode_rfc3164_submit,
                    device_rfc3164.fetch_encode, {}),
    }
    for name, lines in other.items():
        fmt = "rfc3164" if name.startswith("rfc3164") else "ltsv"
        submit, dev_fetch, kw = routes[fmt]
        packed = pack.pack_lines_2d(lines, MAX_LEN)
        handle = submit(packed[0], packed[1])
        snap0 = metrics.snapshot()
        t0 = time.perf_counter()
        res, _ = dev_fetch(handle, packed, enc, merger, route_state={},
                           **kw)
        dt = time.perf_counter() - t0
        snap1 = metrics.snapshot()
        d = {k: snap1.get(k, 0) - snap0.get(k, 0)
             for k in ("device_encode_rows", "device_encode_scalar_rows")}
        total = max(1, len(lines))
        results[name] = {
            "declined": res is None,
            "device_rows_pct": round(
                100.0 * d["device_encode_rows"] / total, 1),
            "scalar_rows_pct": round(
                100.0 * d["device_encode_scalar_rows"] / total, 1),
            "encode_ms": round(dt * 1e3, 1),
        }
        print(f"corpus {name}: {results[name]}", file=sys.stderr)
    extra["fallback_corpora"] = results


def bench_host_scaling(lines, extra, smoke):
    """Host-stage thread scaling (VERDICT r4 #8): native pack and the
    segment-gather assembler at n_threads = 1,2,4,8 (bounded by the
    host's cores x2 so oversubscription is visible), keyed by nproc —
    the first multi-core session produces the >=5M lines/s host-stages
    evidence automatically instead of re-deferring."""
    import os as _os

    from flowgger_tpu import native
    from flowgger_tpu.tpu import pack

    ncpu = _os.cpu_count() or 1
    region = b"".join(ln + b"\n" for ln in lines)
    n_lines = len(lines)
    rng = np.random.default_rng(3)
    seg_len = rng.integers(16, 120, 3 * n_lines).astype(np.int64)
    seg_src = rng.integers(0, max(1, len(region) - 130),
                           3 * n_lines).astype(np.int64)
    dst = np.concatenate([[0], np.cumsum(seg_len)])
    total = int(dst[-1])
    src_arr = np.frombuffer(region, dtype=np.uint8)

    table = {}
    threads_run = []
    old = native._DEFAULT_THREADS
    try:
        for nt in (1, 2, 4, 8):
            if nt > 2 * ncpu:
                break
            threads_run.append(nt)
            native._DEFAULT_THREADS = nt
            pack.configure_pack_threads(nt)
            trials = 1 if smoke else 3
            best_p = best_c = None
            for _ in range(trials):
                t0 = time.perf_counter()
                pack.pack_region_2d(region, MAX_LEN)
                dt = time.perf_counter() - t0
                best_p = dt if best_p is None else min(best_p, dt)
                t0 = time.perf_counter()
                out = native.concat_segments_native(
                    src_arr, seg_src, seg_len, dst[:-1], total)
                dt = time.perf_counter() - t0
                best_c = dt if best_c is None else min(best_c, dt)
            row = {"pack_mlps": round(n_lines / best_p / 1e6, 2)}
            if out is not None:
                row["concat_gbps"] = round(total / best_c / 1e9, 2)
            table[str(nt)] = row
    finally:
        native._DEFAULT_THREADS = old
        pack.configure_pack_threads(1)
    # nproc is the real os.cpu_count(); nproc_available the scheduler
    # affinity mask (cgroup-limited containers differ), and threads_run
    # the thread counts this table actually measured — the old report
    # said "nproc: 1" while benchmarking 2 pack threads
    try:
        avail = len(_os.sched_getaffinity(0))
    except AttributeError:
        avail = ncpu
    extra["host_scaling"] = {"nproc": ncpu, "nproc_available": avail,
                             "threads_run": threads_run,
                             "by_threads": table}
    print(f"host scaling (nproc={ncpu}, available={avail}, "
          f"threads_run={threads_run}): {table}", file=sys.stderr)


def bench_other_configs(jax, jnp, dev, smoke, extra):
    """BASELINE.json configs beyond #1: LTSV (#2), GELF (#3), multi-SD
    extraction (#4), auto-detect dispatch (#5) — sustained device decode
    lines/s for each, via the same chained-iteration methodology."""
    from flowgger_tpu.tpu import gelf as gelf_k
    from flowgger_tpu.tpu import ltsv as ltsv_k
    from flowgger_tpu.tpu import pack, rfc5424

    if smoke:
        n_lines, chain = 8_192, 2
    else:
        n_lines, chain = 1_000_000, 8

    def chained_rate(decode_fn, digest_fn, batch, lens):
        def jf_fn(b, ln):
            def body(i, carry):
                out = decode_fn(
                    jnp.bitwise_xor(b, (carry % 2).astype(jnp.uint8)), ln)
                return carry + (digest_fn(out) & 1)

            return jax.lax.fori_loop(0, chain, body, jnp.int32(0))

        jf = jax.jit(jf_fn)
        db = jax.device_put(batch, dev)
        dl = jax.device_put(lens, dev)
        int(jf(db, dl))
        t0 = time.perf_counter()
        int(jf(db, dl))
        return n_lines / ((time.perf_counter() - t0) / chain)

    # LTSV (#2)
    ltsv_lines = [
        (f"host:web{i % 20}\ttime:2015-08-05T15:53:45Z\tstatus:200"
         f"\tpath:/api/{i}\tmessage:request {i}").encode()
        for i in range(n_lines)
    ]
    b, l, *_ = pack.pack_lines_2d(ltsv_lines, MAX_LEN)
    rate = chained_rate(
        lambda bb, ll: ltsv_k.decode_ltsv(bb, ll),
        lambda o: digest_all(jnp, o),
        jnp.asarray(b), jnp.asarray(l))
    extra["ltsv_device_lines_per_sec"] = round(rate)
    print(f"ltsv device decode: {rate / 1e6:.1f}M lines/s", file=sys.stderr)

    # GELF (#3)
    gelf_lines = [
        (b'{"version":"1.1","host":"h%d","short_message":"event %d",'
         b'"timestamp":1438790025.%03d,"level":5}' % (i % 9, i, i % 1000))
        for i in range(n_lines)
    ]
    b, l, *_ = pack.pack_lines_2d(gelf_lines, MAX_LEN)
    rate = chained_rate(
        lambda bb, ll: gelf_k.decode_gelf(bb, ll),
        lambda o: digest_all(jnp, o),
        jnp.asarray(b), jnp.asarray(l))
    extra["gelf_device_lines_per_sec"] = round(rate)
    print(f"gelf device decode: {rate / 1e6:.1f}M lines/s", file=sys.stderr)

    # multi-SD extraction (#4): 3 SD blocks, 6 pairs total
    sd_lines = [
        (f'<13>1 2015-08-05T15:53:45.{i % 1000:03d}Z h{i % 9} app {i} m '
         f'[a@1 x="{i}" y="2"][b@2 z="3" w="4"][c@3 u="5" v="6"] '
         f'multi-sd event {i}').encode()
        for i in range(n_lines)
    ]
    b, l, *_ = pack.pack_lines_2d(sd_lines, MAX_LEN)
    rate = chained_rate(
        lambda bb, ll: rfc5424.decode_rfc5424(bb, ll),
        lambda o: digest_all(jnp, o),
        jnp.asarray(b), jnp.asarray(l))
    extra["multisd_device_lines_per_sec"] = round(rate)
    print(f"multi-SD device decode: {rate / 1e6:.1f}M lines/s",
          file=sys.stderr)

    # auto-detect dispatch (#5): device classification rate (the
    # production path for real batches; classify_packed routes there)
    from flowgger_tpu.tpu.autodetect import classify_device

    syslog_lines = gen_lines((n_lines + 2) // 3)
    mixed = [
        (syslog_lines[i // 3], ltsv_lines[i], gelf_lines[i])[i % 3]
        for i in range(n_lines)
    ]
    packed = pack.pack_lines_2d(mixed, MAX_LEN)
    rate = chained_rate(
        lambda bb, ll: {"cls": classify_device(bb, ll)},
        lambda o: o["cls"].astype(jnp.int32).sum(),
        jnp.asarray(packed[0]), jnp.asarray(packed[1]))
    extra["auto_classify_lines_per_sec"] = round(rate)
    print(f"auto-detect classification: {rate / 1e6:.1f}M lines/s "
          "(device)", file=sys.stderr)


def bench_tenancy(extra, lines):
    """Tenancy smoke gates (multi-tenant serving PR):

    1. Admission overhead on the single-tenant default path must stay
       under 3% — measured as the per-chunk cost the AdmissionHandler
       adds (unlimited default tenant, the production default when
       ``[tenants]`` is configured but a source is unmatched) relative
       to the measured per-chunk cost of the overlap e2e pipeline.
       Isolating the wrapper's own cost keeps the 3% bar meaningful on
       noisy 2-core CI boxes where two full e2e runs jitter by ±10%.
    2. Template mining: templates/sec on the smoke corpus, the
       ``tenant_templates_distinct`` gauge, and ID stability — two runs
       over the same corpus must assign identical template IDs.
    3. Zero residue when off: a default-config pipeline must build the
       pre-tenancy objects (PolicyQueue, unwrapped scalar handler, no
       miners on the batch handler).
    """
    from flowgger_tpu.config import Config
    from flowgger_tpu.tenancy.admission import AdmissionHandler
    from flowgger_tpu.tenancy.registry import TenantRegistry
    from flowgger_tpu.tenancy.templates import TemplateMinerSet
    from flowgger_tpu.utils.metrics import registry as metrics

    region = b"".join(ln + b"\n" for ln in lines)
    # ~8 KiB chunks approximate socket reads (admission charges once
    # per chunk, so chunk size sets the amortization the gate measures)
    chunk_size = 8192
    chunks = [region[i:i + chunk_size]
              for i in range(0, len(region), chunk_size)]
    lines_per_chunk = max(1, len(lines) / len(chunks))

    class _NoopIngest:
        quiet_empty = False
        bare_errors = False
        ingest_sep = b"\n"
        ingest_strip_cr = True
        count = 0

        def ingest_chunk(self, chunk):
            self.count += len(chunk)

        def flush(self):
            pass

    reg = TenantRegistry.from_config(
        Config.from_string("[tenants.other]\npeers = [\"203.0.113.1\"]\n"))
    wrapped_inner = _NoopIngest()
    wrapped = AdmissionHandler(wrapped_inner, reg.resolve(None))
    plain = _NoopIngest()
    repeats = 20
    best_plain = best_wrapped = None
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(repeats):
            for c in chunks:
                plain.ingest_chunk(c)
        t_plain = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(repeats):
            for c in chunks:
                wrapped.ingest_chunk(c)
        t_wrapped = time.perf_counter() - t0
        best_plain = t_plain if best_plain is None else min(best_plain, t_plain)
        best_wrapped = (t_wrapped if best_wrapped is None
                        else min(best_wrapped, t_wrapped))
    n_calls = repeats * len(chunks)
    admission_s_per_chunk = max(0.0, (best_wrapped - best_plain) / n_calls)
    e2e_rate = extra.get("e2e_overlap_lines_per_sec", 0) or 1
    e2e_s_per_chunk = lines_per_chunk / e2e_rate
    overhead_ratio = admission_s_per_chunk / e2e_s_per_chunk
    admission_ok = overhead_ratio < 0.03

    # template mining rate + cross-run ID stability
    msgs = [ln.split(b"] ", 1)[-1] for ln in lines]

    def mine():
        miners = TemplateMinerSet.from_config(
            Config.from_string('[tenant]\ntemplates = "on"\n'))
        t0 = time.perf_counter()
        for i in range(0, len(msgs), 1024):
            miners.observe_rows(msgs[i:i + 1024], None)
        return time.perf_counter() - t0, miners.miner("default").templates()

    wall1, templates1 = mine()
    _wall2, templates2 = mine()
    templates_stable = templates1 == templates2
    templates_per_sec = len(msgs) / max(wall1, 1e-9)
    distinct = metrics.get_gauge("tenant_templates_distinct")

    # off-path structure: default config builds pre-tenancy objects
    from flowgger_tpu.pipeline import Pipeline
    from flowgger_tpu.splitters import ScalarHandler
    from flowgger_tpu.utils.bounded_queue import PolicyQueue

    p = Pipeline(Config.from_string(
        '[input]\ntype = "stdin"\n[output]\ntype = "debug"\n'))
    off_clean = (p.tenants is None and type(p.tx) is PolicyQueue
                 and type(p.handler_factory()) is ScalarHandler)

    ok = admission_ok and templates_stable and off_clean
    extra.update({
        "tenancy_admission_overhead_ratio": round(overhead_ratio, 6),
        "tenancy_admission_ns_per_chunk": round(admission_s_per_chunk * 1e9),
        "templates_per_sec": round(templates_per_sec),
        "tenant_templates_distinct": distinct,
        "templates_stable": templates_stable,
        "tenancy_off_path_clean": off_clean,
        "tenancy_ok": ok,
    })
    print(json.dumps({
        "metric": "tenancy_smoke",
        "admission_overhead_ratio": round(overhead_ratio, 6),
        "admission_gate": "< 0.03 of per-chunk e2e cost",
        "admission_ok": admission_ok,
        "templates_per_sec": round(templates_per_sec),
        "tenant_templates_distinct": distinct,
        "templates_stable": templates_stable,
        "off_path_clean": off_clean,
        "ok": ok,
    }))
    return ok


def bench_obs(extra, lines):
    """Observability (flight recorder) smoke gates:

    1. Tracing-off overhead: the per-batch cost of the tracer guard
       sequence a block batch executes when ``[metrics] trace = "off"``
       (one ``begin`` returning None plus the span/end guards) must
       stay under 1% of the measured per-chunk e2e cost.  Same
       isolation logic as the PR 6 admission gate: the guard cost is
       measured directly (micro-differential) because two full e2e
       runs jitter ±10% on 2-core CI boxes while the guard cost is
       nanoseconds.
    2. Ring-mode per-batch recording cost: measured and recorded (not
       gated — ring mode is opt-in diagnostics, but the number belongs
       in the BENCH record).
    3. Journal + exposition sanity: a degradation event lands in the
       ring and the registry renders non-empty exposition text (the
       strict format parser lives in tests/test_obs.py).
    4. SLO-plane guard cost: the per-batch hot-path additions the SLO
       engine feeds on (_finish_batch's route_rows_{route} inc + the
       e2e_batch_seconds_{route} family observe) must stay under 1%
       of per-chunk e2e cost, like the trace guard.
    5. Regression sentinel: seeded from the COMMITTED BENCH series,
       a playback of this run's measured live rate must report ZERO
       perf_regression events (an unmodified run is not a regression —
       and a future PR that tanks the hot path fails right here), while
       a synthetic 10x throttle must raise one with measured-vs-
       baseline cost (the detector actually detects).
    """
    from flowgger_tpu.obs import events as obs_events
    from flowgger_tpu.obs import prom as obs_prom
    from flowgger_tpu.obs.sentinel import Sentinel
    from flowgger_tpu.obs.trace import tracer
    from flowgger_tpu.utils.metrics import Registry as _Registry
    from flowgger_tpu.utils.metrics import registry as _reg

    # the guard sequence one block batch pays: mint + the instrumented
    # stages' span guards + the finish guard (tpu/batch.py)
    span_guards = 8
    loops = 50_000

    def batch_guard_cost():
        best = None
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(loops):
                bid = tracer.begin("bench")
                for _ in range(span_guards):
                    tracer.span(bid, "pack", 0.0, 1.0)
                tracer.end(bid)
            wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
        return best / loops

    tracer.configure("off")
    off_s_per_batch = batch_guard_cost()
    tracer.configure("ring")
    ring_loops = 5_000
    t0 = time.perf_counter()
    for _ in range(ring_loops):
        bid = tracer.begin("bench")
        for _ in range(span_guards):
            tracer.span(bid, "pack", 0.0, 1.0, rows=1024)
        tracer.end(bid)
    ring_s_per_batch = (time.perf_counter() - t0) / ring_loops
    tracer.configure("off")

    # per-chunk e2e denominator, same chunking as the admission gate
    # (~8 KiB ≈ one socket read); a batch spans MANY chunks, so gating
    # the per-BATCH guard cost against the per-CHUNK e2e cost is the
    # strict reading of the <1% bar
    region_len = sum(len(ln) + 1 for ln in lines)
    lines_per_chunk = max(1.0, len(lines) / max(1, region_len / 8192))
    e2e_rate = extra.get("e2e_overlap_lines_per_sec", 0) or 1
    e2e_s_per_chunk = lines_per_chunk / e2e_rate
    overhead_ratio = off_s_per_batch / e2e_s_per_chunk
    off_ok = overhead_ratio < 0.01

    # journal + exposition sanity
    obs_events.emit("queue", "queue_drop", detail="bench", cost=1,
                    cost_unit="items")
    ring = obs_events.journal.snapshot()
    journal_ok = bool(ring) and ring[-1]["reason"] == "queue_drop"
    text = obs_prom.render()
    prom_ok = ("# TYPE flowgger_input_lines_total counter" in text
               and "flowgger_degradation_events_by_reason_total" in text
               and "_sample_count" in text)

    # SLO-plane per-batch guard cost (one family counter inc + one
    # family histogram observe per finished batch)
    slo_loops = 50_000
    slo_best = None
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(slo_loops):
            _reg.inc("route_rows_bench", 1024)
            _reg.observe("e2e_batch_seconds_bench", 0.001)
        wall = time.perf_counter() - t0
        slo_best = wall if slo_best is None else min(slo_best, wall)
    slo_s_per_batch = slo_best / slo_loops
    slo_ratio = slo_s_per_batch / e2e_s_per_chunk
    slo_ok = slo_ratio < 0.01

    # regression sentinel: committed-series seed, live-rate playback
    import os as _os

    repo = _os.path.dirname(_os.path.abspath(__file__))
    sreg = _Registry()
    clock = [0.0]
    sent = Sentinel(registry=sreg, clock=lambda: clock[0])
    sent.configure(enabled=True, interval_s=1, drop=0.5, sustain=2,
                   min_rows=64)
    seeded = sent.seed_from_bench(repo)

    def regressions():
        return len([ev for ev in obs_events.journal.snapshot()
                    if ev["reason"] == "perf_regression"])

    before = regressions()
    live_rate = max(1, int(e2e_rate))
    for _ in range(10):
        clock[0] += 1.0
        sreg.inc("route_rows_rfc5424", live_rate)
        sent.tick()
    sentinel_clean = regressions() == before
    # synthetic 10x throttle: 10s ticks give the 30s-tau EWMA time to
    # converge onto the throttled rate within the playback
    for _ in range(30):
        clock[0] += 10.0
        sreg.inc("route_rows_rfc5424", live_rate)  # live/10 per second
        sent.tick()
    sentinel_detects = regressions() > before
    sentinel_ok = bool(seeded.get("rfc5424")) and sentinel_clean \
        and sentinel_detects

    ok = off_ok and journal_ok and prom_ok and slo_ok and sentinel_ok
    extra.update({
        "obs_trace_off_ns_per_batch": round(off_s_per_batch * 1e9),
        "obs_trace_ring_ns_per_batch": round(ring_s_per_batch * 1e9),
        "obs_trace_off_overhead_ratio": round(overhead_ratio, 6),
        "obs_slo_guard_ns_per_batch": round(slo_s_per_batch * 1e9),
        "obs_sentinel_baseline_lps": seeded.get(
            "rfc5424", {}).get("lines_per_sec"),
        "obs_ok": ok,
    })
    print(json.dumps({
        "metric": "obs_smoke",
        "trace_off_ns_per_batch": round(off_s_per_batch * 1e9),
        "trace_ring_ns_per_batch": round(ring_s_per_batch * 1e9),
        "trace_off_overhead_ratio": round(overhead_ratio, 6),
        "trace_off_gate": "< 0.01 of per-chunk e2e cost",
        "trace_off_ok": off_ok,
        "slo_guard_ns_per_batch": round(slo_s_per_batch * 1e9),
        "slo_guard_overhead_ratio": round(slo_ratio, 6),
        "slo_guard_ok": slo_ok,
        "sentinel_seeded_baseline_lps": seeded.get(
            "rfc5424", {}).get("lines_per_sec"),
        "sentinel_live_lps": live_rate,
        "sentinel_clean_on_unmodified_run": sentinel_clean,
        "sentinel_detects_throttle": sentinel_detects,
        "sentinel_ok": sentinel_ok,
        "journal_ok": journal_ok,
        "exposition_ok": prom_ok,
        "ok": ok,
    }))
    return ok


def bench_durability(extra, lines):
    """Zero-loss ingestion (WAL spill tier) smoke gates:

    1. Disarmed-watermark overhead: the per-dispatch cost of the
       ``should_spill()`` guard a durability-armed handler pays while
       the queue sits BELOW the watermark (the steady state — one
       fill-fraction read and a compare) must stay under 1% of the
       measured per-chunk e2e cost.  Same micro-differential isolation
       as the admission/trace gates: two full e2e runs jitter ±10% on
       2-core CI boxes while the guard costs nanoseconds.
    2. Spill + replay byte identity: a corpus forced through the spill
       tier (saturated queue, every batch appended to WAL segments)
       and then replayed through a fresh handler must emit exactly the
       bytes of a straight no-spill run, the replay cursor must drain
       to zero unacked records on sink acks, and the fully-acked
       segments must be unlinked from disk.
    """
    import queue as _q
    import shutil
    import tempfile

    from flowgger_tpu.block import EncodedBlock
    from flowgger_tpu.config import Config
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.durability import DurabilityManager
    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.mergers import LineMerger
    from flowgger_tpu.outputs import ack_item
    from flowgger_tpu.tpu.batch import BatchHandler
    from flowgger_tpu.utils.bounded_queue import PolicyQueue

    # gate 1: guard cost below the watermark (the always-on price)
    idle_q = PolicyQueue(10_000)
    tmp = tempfile.mkdtemp(prefix="flowgger_dur_bench_")
    mgr = DurabilityManager("spill", tmp, start_watchdog=False)
    mgr.attach_queue(idle_q)
    loops = 100_000
    best = None
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(loops):
            mgr.should_spill()
        wall = time.perf_counter() - t0
        best = wall if best is None else min(best, wall)
    guard_s = best / loops
    region_len = sum(len(ln) + 1 for ln in lines)
    lines_per_chunk = max(1.0, len(lines) / max(1, region_len / 8192))
    e2e_rate = extra.get("e2e_overlap_lines_per_sec", 0) or 1
    e2e_s_per_chunk = lines_per_chunk / e2e_rate
    overhead_ratio = guard_s / e2e_s_per_chunk
    guard_ok = overhead_ratio < 0.01

    # gate 2: spill → replay byte identity vs a straight run
    corpus = lines[:2_048]
    region = b"".join(ln + b"\n" for ln in corpus)
    cfg = Config.from_string(
        "[input]\ntpu_batch_size = 256\ntpu_max_line_len = 256\n")

    def collect(tx):
        got = []
        while not tx.empty():
            item = tx.get_nowait()
            if isinstance(item, EncodedBlock):
                got.extend(item.iter_framed())
                ack_item(item)
            else:
                got.append(LineMerger().frame(item))
        return b"".join(got)

    def fresh_handler(tx):
        return BatchHandler(tx, RFC5424Decoder(), GelfEncoder(
            Config.from_string("")), cfg, fmt="rfc5424",
            start_timer=False, merger=LineMerger())

    tx0 = _q.Queue()
    h0 = fresh_handler(tx0)
    h0.ingest_chunk(region)
    h0.flush()
    h0.close()
    want = collect(tx0)

    class _Saturated:
        """A queue past its watermark whose put must never fire: with
        the spill tier armed, every dispatch lands in the WAL."""

        @staticmethod
        def fill_fraction():
            return 1.0

        def put(self, item):
            raise AssertionError("dispatch leaked past the spill tier")

    sat = _Saturated()
    mgr.attach_queue(sat)  # past the watermark: should_spill() arms
    h1 = fresh_handler(sat)
    h1.durability = mgr
    h1.ingest_chunk(region)
    h1.flush()
    h1.close()
    stats = mgr.backlog_stats()
    spilled_segments = stats["segments"]
    spilled_bytes = stats["bytes"]

    tx2 = _q.Queue()
    h2 = fresh_handler(tx2)
    h2.durability = mgr
    replayed = h2.replay_spilled()
    h2.close()
    got = collect(tx2)
    mgr.stop()
    drained = mgr.unacked() == 0 and not mgr.backlog()
    wal_empty = not any(f.endswith(".seg") for f in os.listdir(tmp))
    identical = got == want and len(want) > 0
    shutil.rmtree(tmp, ignore_errors=True)
    replay_ok = identical and drained and wal_empty \
        and replayed == len(corpus)

    ok = guard_ok and replay_ok
    extra.update({
        "durability_guard_ns_per_dispatch": round(guard_s * 1e9),
        "durability_guard_overhead_ratio": round(overhead_ratio, 6),
        "durability_spilled_segments": spilled_segments,
        "durability_spilled_bytes": spilled_bytes,
        "durability_replayed_lines": replayed,
        "durability_replay_byte_identical": bool(identical),
        "durability_ok": ok,
    })
    print(json.dumps({
        "metric": "durability_smoke",
        "guard_ns_per_dispatch": round(guard_s * 1e9),
        "guard_overhead_ratio": round(overhead_ratio, 6),
        "guard_gate": "< 0.01 of per-chunk e2e cost",
        "guard_ok": guard_ok,
        "spilled_segments": spilled_segments,
        "spilled_bytes": spilled_bytes,
        "replayed_lines": replayed,
        "replay_byte_identical": bool(identical),
        "cursor_drained": bool(drained),
        "wal_empty_after_ack": bool(wal_empty),
        "ok": ok,
    }))
    return ok


def bench_control(extra, lines):
    """Control-plane smoke gates (closing-the-loop PR):

    1. Disarmed guard cost: the per-chunk admission delta between a
       controller-touched tenant state (armed-idle: a ControlPlane
       exists, ``set_rate_factor`` was exercised, factor back at 1.0)
       and a never-governed state must stay under 1% of the measured
       per-chunk e2e cost.  The admit hot path reads nothing from the
       controller — the factor lands by re-rating the buckets in
       place — so this delta is the entire hot-path price of the
       feedback layer.
    2. Disarmed structure: a default (no ``[control]``) pipeline builds
       no plane, no ticker thread, no proxy thread.
    3. Reaction time: with real short SLO windows (fast 0.4s / slow
       1.2s), a sustained tenant flood must drive the AIMD loop to a
       tightened rate factor within 5 s of the first shed — the
       closed-loop latency an operator would actually see, measured
       through the real SloEngine -> burn_states -> tick path.
    """
    import threading as _threading

    from flowgger_tpu.config import Config
    from flowgger_tpu.control import ControlPlane, ControlSpec
    from flowgger_tpu.obs import events as obs_events
    from flowgger_tpu.obs.slo import Objective, SloEngine
    from flowgger_tpu.tenancy.admission import AdmissionHandler
    from flowgger_tpu.tenancy.registry import TenantRegistry

    region = b"".join(ln + b"\n" for ln in lines)
    chunk_size = 8192
    chunks = [region[i:i + chunk_size]
              for i in range(0, len(region), chunk_size)]
    lines_per_chunk = max(1, len(lines) / len(chunks))

    class _NoopIngest:
        quiet_empty = False
        bare_errors = False
        ingest_sep = b"\n"
        ingest_strip_cr = True

        def ingest_chunk(self, chunk):
            pass

        def flush(self):
            pass

    # rate high enough that the flood never trips the buckets: both
    # runs stay on the admit-success path, so the delta isolates the
    # control layer's attribute cost, not denial-path work
    reg = TenantRegistry.from_config(Config.from_string(
        "[tenants.plain]\nrate = 1000000000\n"
        "[tenants.armed]\nrate = 1000000000\n"))
    plain = AdmissionHandler(_NoopIngest(), reg.state("plain"))
    plane = ControlPlane(ControlSpec(admission=True, interval_s=0),
                         tenants=reg, burn_source=lambda: [])
    armed_state = reg.state("armed")
    armed_state.set_rate_factor(0.5)   # exercise the re-rate path...
    armed_state.set_rate_factor(1.0)   # ...then idle at the ceiling
    armed = AdmissionHandler(_NoopIngest(), armed_state)
    repeats = 20
    best_plain = best_armed = None
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(repeats):
            for c in chunks:
                plain.ingest_chunk(c)
        t_plain = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(repeats):
            for c in chunks:
                armed.ingest_chunk(c)
        t_armed = time.perf_counter() - t0
        best_plain = t_plain if best_plain is None else min(best_plain,
                                                            t_plain)
        best_armed = t_armed if best_armed is None else min(best_armed,
                                                            t_armed)
    n_calls = repeats * len(chunks)
    guard_s = max(0.0, (best_armed - best_plain) / n_calls)
    e2e_rate = extra.get("e2e_overlap_lines_per_sec", 0) or 1
    e2e_s_per_chunk = lines_per_chunk / e2e_rate
    overhead_ratio = guard_s / e2e_s_per_chunk
    guard_ok = overhead_ratio < 0.01

    # disarmed structure: default config builds no control plane and
    # starts no control/proxy threads
    from flowgger_tpu.pipeline import Pipeline

    before = {t.name for t in _threading.enumerate()}
    p = Pipeline(Config.from_string(
        '[input]\ntype = "stdin"\n[output]\ntype = "debug"\n'))
    new_threads = {t.name for t in _threading.enumerate()} - before
    disarmed_clean = (p.control is None and not any(
        n.startswith(("control-plane", "steer-")) for n in new_threads))

    # flood-to-tighten reaction time on real short windows
    obs_events.journal.reset()
    obs_events.journal.configure()
    reg2 = TenantRegistry.from_config(Config.from_string(
        "[tenants.noisy]\nrate = 2000\n"))
    eng = SloEngine()
    eng.configure([Objective(
        name="noisy_sheds", kind="events", metric="events_tenant_shed",
        max_per_sec=10.0, tenant="noisy",
        fast_window_s=0.4, slow_window_s=1.2)], interval_s=0)
    plane2 = ControlPlane(ControlSpec(admission=True, interval_s=0),
                          tenants=reg2, burn_source=eng.burn_states)
    noisy = reg2.state("noisy")
    stop_flood = _threading.Event()

    def flood():
        while not stop_flood.is_set():
            noisy.admit(64, 4096)   # far over rate: sustained sheds
            time.sleep(0.002)

    flooder = _threading.Thread(target=flood, daemon=True)
    t_flood = time.perf_counter()
    flooder.start()
    reaction_s = None
    deadline = t_flood + 10.0
    while time.perf_counter() < deadline:
        eng.tick()
        plane2.tick()
        if noisy.rate_factor < 1.0:
            reaction_s = time.perf_counter() - t_flood
            break
        time.sleep(0.1)
    stop_flood.set()
    flooder.join(timeout=2)
    eng.stop()
    tightened = reaction_s is not None
    reaction_ok = tightened and reaction_s < 5.0
    tighten_events = sum(
        1 for e in obs_events.journal.snapshot()
        if e["reason"] == "admission_tighten")
    obs_events.journal.reset()
    obs_events.journal.configure()

    ok = guard_ok and disarmed_clean and reaction_ok
    extra.update({
        "control_guard_ns_per_chunk": round(guard_s * 1e9),
        "control_guard_overhead_ratio": round(overhead_ratio, 6),
        "control_disarmed_clean": disarmed_clean,
        "control_reaction_s": (round(reaction_s, 3)
                               if tightened else None),
        "control_tighten_events": tighten_events,
        "control_ok": ok,
    })
    print(json.dumps({
        "metric": "control_smoke",
        "guard_ns_per_chunk": round(guard_s * 1e9),
        "guard_overhead_ratio": round(overhead_ratio, 6),
        "guard_gate": "< 0.01 of per-chunk e2e cost",
        "guard_ok": guard_ok,
        "disarmed_clean": disarmed_clean,
        "reaction_s": round(reaction_s, 3) if tightened else None,
        "reaction_gate": "flood tightens the tenant factor in < 5 s",
        "reaction_ok": reaction_ok,
        "tighten_events": tighten_events,
        "ok": ok,
    }))
    return ok


def bench_fused_routes(extra, smoke):
    """Fused decode→encode route matrix (tpu/fused_routes.py): per
    route, emit the fused tier's fetched-vs-emitted bytes/row, the
    split host path's fetched bytes/row (every decode channel crosses
    D2H there), eager lines/s, and two gates:

    1. the fused output is byte-identical to the split path's on the
       corpus (framing included), and
    2. fused fetched bytes/row <= the split DEVICE path's (the
       two-program decode→encode pipeline the fusion replaces) AND
       below the route's own emitted bytes/row (the device-resident
       span channels + constant-elision claim).  The split HOST path's
       span-channel fetch rides along as context — it can be smaller
       than output-sized on channel-light formats (rfc3164) because it
       re-assembles output host-side from the host-resident chunk,
       which is exactly the host CPU cost the fused tier removes.

    The fused programs run eagerly (``jax.disable_jit()``) where this
    host's XLA cannot compile them — rates are then labeled
    ``cpu-eager`` and are NOT the accelerator claim, but the
    byte-level gates hold identically in both modes."""
    import numpy as np

    import jax

    from flowgger_tpu.config import Config
    from flowgger_tpu.decoders.gelf import GelfDecoder
    from flowgger_tpu.decoders.ltsv import LTSVDecoder
    from flowgger_tpu.decoders.rfc3164 import RFC3164Decoder
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.encoders.capnp import CapnpEncoder
    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.encoders.ltsv import LTSVEncoder
    from flowgger_tpu.encoders.rfc5424 import RFC5424Encoder
    from flowgger_tpu.mergers import LineMerger
    from flowgger_tpu.tpu import fused_routes, gelf, ltsv, pack, rfc3164, rfc5424
    from flowgger_tpu.tpu.batch import block_fetch_encode, block_submit
    from flowgger_tpu.utils.metrics import registry as reg

    cfg = Config.from_string("")
    enc = GelfEncoder(cfg)
    merger = LineMerger()
    n = 512 if smoke else 1024
    lines_5424 = [
        f'<34>1 2015-08-05T15:53:45.8Z host{i % 3} app 42 m '
        f'[x@9 a="v{i}" b="w{i}"] hello msg {i}'.encode()
        for i in range(n)]
    lines_3164 = [
        f'<34>Aug  5 15:53:45 host{i % 3} app[42]: legacy message '
        f'body {i}'.encode() for i in range(n)]
    dec_5424 = RFC5424Decoder(cfg)
    dec_3164 = RFC3164Decoder(cfg)
    # route name -> (fmt, decoder, encoder, corpus); the output leg of
    # each route keys on the concrete encoder type (route_for)
    corpora = {
        "rfc5424_gelf": ("rfc5424", dec_5424, enc, lines_5424),
        "rfc3164_gelf": ("rfc3164", dec_3164, enc, lines_3164),
        "ltsv_gelf": ("ltsv", LTSVDecoder(cfg), enc, [
            f'host:h{i % 3}\ttime:2015-08-05T15:53:45Z\tuser:u{i % 7}\t'
            f'req:GET /idx {i}\tstatus:200\tmessage:done {i}'.encode()
            for i in range(n)]),
        "gelf_gelf": ("gelf", GelfDecoder(cfg), enc, [
            ('{"version":"1.1","host":"h%d","short_message":"request %d '
             'done","timestamp":1438790025.5,"_user":"u%d",'
             '"_status":"200"}' % (i % 3, i, i % 7)).encode()
            for i in range(n)]),
        # PR 19 non-GELF output legs (the N×M closure): byte blobs are
        # compared whole — capnp is binary, so re-splitting the framed
        # stream would be framing-dependent
        "rfc5424_rfc5424": ("rfc5424", dec_5424, RFC5424Encoder(cfg),
                            lines_5424),
        "rfc3164_rfc5424": ("rfc3164", dec_3164, RFC5424Encoder(cfg),
                            lines_3164),
        "rfc5424_ltsv": ("rfc5424", dec_5424, LTSVEncoder(cfg),
                         lines_5424),
        "rfc5424_capnp": ("rfc5424", dec_5424, CapnpEncoder(cfg),
                          lines_5424),
    }
    fetchers = {"rfc5424": rfc5424.decode_rfc5424_fetch,
                "rfc3164": rfc3164.decode_rfc3164_fetch,
                "ltsv": ltsv.decode_ltsv_fetch,
                "gelf": gelf.decode_gelf_fetch}
    # the fused byte-gates need the device-encode tier armed and, on
    # hosts whose XLA can't compile the fused programs, an inline eager
    # run instead of a watchdog decline
    saved = {k: os.environ.get(k) for k in
             ("FLOWGGER_DEVICE_ENCODE", "FLOWGGER_COMPILE_TIMEOUT_MS",
              "FLOWGGER_FUSED_COMPILE_TIMEOUT_MS")}
    os.environ["FLOWGGER_DEVICE_ENCODE"] = "1"
    os.environ["FLOWGGER_COMPILE_TIMEOUT_MS"] = "0"
    os.environ["FLOWGGER_FUSED_COMPILE_TIMEOUT_MS"] = "0"
    routes_out = {}
    ok = True
    try:
        for name, (fmt, decoder, enc_r, lines) in corpora.items():
            packed = pack.pack_lines_2d(lines, 256)
            ltsv_dec = decoder if fmt == "ltsv" else None
            route = fused_routes.route_for(fmt, enc_r, merger, ltsv_dec)
            # split HOST reference: block-path bytes + its span-channel
            # D2H volume (context only — it trades D2H for host CPU)
            handle = block_submit(fmt, packed)
            host_bpr = sum(np.asarray(v).nbytes for v in
                           fetchers[fmt](handle).values()) / n
            res_split, _, _ = block_fetch_encode(
                fmt, handle, packed, enc_r, merger, ltsv_dec,
                route_state={}, allow_device=False)
            # split DEVICE reference: the two-program decode→encode
            # pipeline the fusion replaces; counter delta = exact D2H
            dev0 = reg.get("device_encode_fetch_bytes")
            with jax.disable_jit():
                res_dev, _, _ = block_fetch_encode(
                    fmt, block_submit(fmt, packed), packed, enc_r,
                    merger, ltsv_dec, route_state={}, allow_device=True)
            split_dev_bpr = (reg.get("device_encode_fetch_bytes")
                             - dev0) / n
            fus0 = reg.get("device_encode_fetch_bytes")
            t0 = time.perf_counter()
            with jax.disable_jit():
                fh = fused_routes.submit(route, packed)
                res_fused, _ = fused_routes.fetch_encode(
                    fh, packed, enc_r, merger, ltsv_dec, {})
            wall = time.perf_counter() - t0
            fused_bytes = reg.get("device_encode_fetch_bytes") - fus0
            identical = (
                res_fused is not None
                and res_fused.block.data == res_split.block.data
                and res_dev is not None
                and res_dev.block.data == res_split.block.data)
            fetch_bpr = reg.get_gauge(f"fetch_bytes_per_row_{name}")
            emit_bpr = reg.get_gauge(f"emit_bytes_per_row_{name}")
            routes_out[name] = {
                "fetch_bytes_per_row": fetch_bpr,
                "emit_bytes_per_row": emit_bpr,
                "split_device_fetch_bytes_per_row":
                    round(split_dev_bpr, 1),
                "split_host_fetch_bytes_per_row": round(host_bpr, 1),
                "fetch_under_emit": bool(fetch_bpr < emit_bpr),
                "byte_identical_to_split": bool(identical),
                "lines_per_sec": round(n / max(wall, 1e-9)),
            }
            ok &= identical and fused_bytes <= split_dev_bpr * n \
                and fetch_bpr < emit_bpr
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    payload = {
        "metric": "fused_routes",
        "rows": n,
        # eager execution of the fused programs — NOT an accelerator
        # rate; the byte/fetch gates are mode-independent
        "backend": "cpu-eager",
        "routes": routes_out,
        "ok": bool(ok),
    }
    extra["fused_routes"] = routes_out
    print(json.dumps(payload))
    return ok


AOT_BOOT_LINES = 50


def _aot_boot_script(framing: str, art_dir: str) -> str:
    """A cold-boot worker: rfc5424→GELF over the given framing, with
    (artifact boot) or without (JIT boot) ``input.tpu_aot_dir``.
    Prints one JSON line: counters + emitted bytes + the wall time
    from interpreter start to the first fully-emitted batch."""
    aot_key = f'tpu_aot_dir = "{art_dir}"\n' if art_dir else ""
    return (
        "import time; T0 = time.time()\n"
        "import json, queue\n"
        "from flowgger_tpu.config import Config\n"
        "from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder\n"
        "from flowgger_tpu.encoders.gelf import GelfEncoder\n"
        "from flowgger_tpu.mergers import LineMerger, NulMerger, "
        "SyslenMerger\n"
        "from flowgger_tpu.outputs import stream_bytes\n"
        "from flowgger_tpu.tpu.batch import BatchHandler\n"
        "from flowgger_tpu.utils.metrics import registry\n"
        "merger = {'line': LineMerger, 'nul': NulMerger, "
        f"'syslen': SyslenMerger}}[{framing!r}]()\n"
        "cfg = Config.from_string(\n"
        "    '[input]\\ntpu_batch_size = 64\\ntpu_max_line_len = 64\\n'\n"
        "    'tpu_shape_buckets = 1\\ntpu_prewarm = false\\n'\n"
        f"    {aot_key!r})\n"
        "tx = queue.Queue()\n"
        "h = BatchHandler(tx, RFC5424Decoder(cfg), GelfEncoder(cfg), "
        "cfg, fmt='rfc5424', start_timer=False, merger=merger)\n"
        "h.ingest_chunk(b''.join(\n"
        "    b'<13>1 2024-01-01T00:00:00Z h a p m - msg %d\\n' % i\n"
        f"    for i in range({AOT_BOOT_LINES})))\n"
        "h.flush(); h.close()\n"
        "t_first = time.time() - T0\n"
        "out = b''\n"
        "while not tx.empty():\n"
        "    data, _ = stream_bytes(tx.get_nowait(), merger)\n"
        "    out += data\n"
        "print(json.dumps({'misses': registry.get("
        "'compile_cache_misses'), 'hits': registry.get("
        "'compile_cache_hits'), 'aot_hits': registry.get('aot_hits'), "
        "'aot_rejects': registry.get('aot_rejects'), "
        "'first_batch_s': round(t_first, 2), 'out': out.hex()}))\n")


FLEET_LINES = 40_000     # per host; ~2s of scalar decode on a small box
#                          (long enough to amortize startup jitter)
FLEET_GATE = 1.5          # aggregate 2-host lines/s vs best single-host
FLEET_GATE_SHARED = 1.1   # documented 2-core tolerance: two workers +
#                           the bench parent share two cores, so
#                           perfect 2x is unreachable (measured band
#                           1.15-1.25x on this container).  1.1x still
#                           proves real scale-out — >1.0x is impossible
#                           without genuine parallelism (same precedent
#                           as LANE_TOL).
FLEET_GATE_DEGRADED = 0.85  # cpu-throttled container (cgroup shares on
#                           a noisy shared host): the box cannot run
#                           even two busy processes concurrently, so a
#                           throughput ratio says nothing about
#                           federation — gate byte identity +
#                           membership convergence + "not
#                           catastrophically slower", and report the
#                           ratio.  The tier is chosen by a MEASURED
#                           3-way parallel-headroom probe at bench
#                           time, not by os.cpu_count(): this
#                           container's effective cores swing with
#                           neighbors (observed 1.84x two-way headroom
#                           in quiet windows, ~1.0x under load, same
#                           cpu_count throughout).


def _parallel_headroom(n: int = 3) -> float:
    """Measured n-way process parallelism available RIGHT NOW, in
    [1, n]: wall of one busy subprocess vs n concurrent ones.  ~2s.
    The children are bare interpreter loops: they never import JAX."""
    import subprocess

    code = ("import time\nt0 = time.perf_counter()\nx = 0\n"
            "for i in range(6_000_000):\n    x += i\n"
            "print(time.perf_counter() - t0)")

    def walls(k):
        procs = [subprocess.Popen([sys.executable, "-c", code],
                                  stdout=subprocess.PIPE, text=True)
                 for _ in range(k)]
        out = []
        for p in procs:
            stdout, _ = p.communicate(timeout=120)
            out.append(float(stdout))
        return out

    solo = min(walls(1)[0], walls(1)[0])  # best of 2: startup jitter
    concurrent = max(walls(n))
    return max(1.0, min(float(n), n * solo / max(concurrent, 1e-9)))


def fleet_worker_main(argv):
    """``bench.py --fleet-worker RANK PORT COORDPORT NLINES OUT``: one
    fleet-bench host — scalar rfc5424→GELF pipeline over its own
    deterministic corpus, fleet heartbeats alongside (PORT=0 +
    COORDPORT=none → solo baseline, no fleet at all).  Prints one JSON
    line; the parent gates on it.  Deliberately jax-free: the fleet
    claim under test is process scale-out + membership, and the scalar
    path keeps the smoke inside its budget."""
    rank, port, coordport, n_lines, out_path = argv
    rank, n_lines = int(rank), int(n_lines)

    from flowgger_tpu.config import Config
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.mergers import LineMerger

    fleet = None
    if port != "0" or coordport != "none":
        from flowgger_tpu.fleet import Fleet

        coord = ("" if rank == 0 else
                 f'tpu_fleet_coordinator = "127.0.0.1:{coordport}"\n')
        # production-shaped heartbeat cadence: an aggressive (100ms)
        # interval measurably taxes the GIL during the decode window
        # and the bench would gate federation *overhead*, not scale-out
        fleet = Fleet.from_config(Config.from_string(
            f"[input]\ntpu_fleet = true\ntpu_fleet_rank = {rank}\n"
            f"tpu_fleet_hosts = 2\ntpu_fleet_port = {port}\n{coord}"
            "tpu_fleet_heartbeat_ms = 250\ntpu_fleet_suspect_ms = 1000\n"
            "tpu_fleet_evict_ms = 3000\n"))
        fleet.start()
        if not fleet.wait_active(2, 30):
            print(json.dumps({"rank": rank, "error": "no rendezvous"}))
            sys.exit(1)

    rng = random.Random(4200 + rank)  # per-host stream, deterministic
    lines = [
        (f"<{rng.randrange(192)}>1 2015-08-05T15:53:45.{i % 1000:03d}Z "
         f"fleet{rank} app{i % 10} {i % 1000} MSGID "
         f'[ex@32473 iut="{i % 9}" eventID="{1000 + i % 999}"] '
         f"host {rank} event {i}")
        for i in range(n_lines)
    ]
    # convergence is sampled AT THE BARRIER: after decode the faster
    # host has already departed and the count would race to 1
    peers_active = (fleet.membership.counts()["active"]
                    if fleet is not None else 1)
    decoder = RFC5424Decoder()
    encoder = GelfEncoder(Config.from_string(""))
    merger = LineMerger()
    t0 = time.perf_counter()
    out = b"".join(merger.frame(encoder.encode(decoder.decode(ln)))
                   for ln in lines)
    wall = time.perf_counter() - t0
    with open(out_path, "wb") as fd:
        fd.write(out)
    if fleet is not None:
        fleet.shutdown()
    print(json.dumps({"rank": rank, "lines": n_lines,
                      "wall_s": round(wall, 4),
                      "lines_per_sec": round(n_lines / wall, 1),
                      "bytes": len(out), "peers_active": peers_active}))


def bench_fleet(extra, smoke):
    """Fleet federation smoke gates (multi-host scale-out PR):

    1. two solo baselines (one per host stream, sequential, no fleet);
    2. a 2-process localhost fleet (heartbeats + rendezvous barrier,
       concurrent decode): **aggregate** lines/s must reach the gate
       for this box's *measured* parallel headroom —
       ``FLEET_GATE``x the best single-host rate where 3-way
       parallelism exists, ``FLEET_GATE_SHARED`` on a 2-core box,
       ``FLEET_GATE_DEGRADED`` (correctness-only) when the container
       is cpu-throttled below 2-way headroom (tolerances documented at
       the constants) — with retries for scheduler jitter;
    3. byte identity: each host's fleet-run output file equals its
       solo-run file — federation must not perturb a single byte;
    4. both workers saw 2 active members at the barrier (the
       membership layer actually converged, the rate is not two
       unfederated processes);
    5. self-healing (PR 14): one bounded ``tools/chaos.py`` drill —
       SIGKILL the coordinator of a 2-process fleet under sustained
       ingest via the self-selecting ``coordinator_kill`` site — must
       leave survivors byte-clean and reach an agreed fallback
       rendezvous; the reconvergence time gates against the
       heartbeat-ladder bound, tiered by the same headroom probe
       (correctness-only when the container is cpu-throttled).
    """
    import subprocess
    import tempfile

    def free_port():
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def run_worker(rank, port, coordport, out_path, timeout=120):
        argv = [sys.executable, os.path.abspath(__file__),
                "--fleet-worker", str(rank), str(port), str(coordport),
                str(FLEET_LINES), out_path]
        # the workers exercise the host planes (scalar decode + fleet
        # heartbeats): pinned to the CPU in their own environment, so
        # none can ever ask for a chip the parent may hold
        return subprocess.Popen(argv, text=True, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE,
                                env={**os.environ, "JAX_PLATFORMS": "cpu"})

    def finish(proc, label):
        try:
            stdout, stderr = proc.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            proc.kill()
            print(f"fleet worker [{label}] timed out", file=sys.stderr)
            return None
        if proc.returncode != 0:
            print(f"fleet worker [{label}] failed:\n{stderr}",
                  file=sys.stderr)
            return None
        for ln in reversed(stdout.strip().splitlines()):
            try:
                return json.loads(ln)
            except ValueError:
                continue
        print(f"fleet worker [{label}] printed no JSON", file=sys.stderr)
        return None

    tmp = tempfile.mkdtemp(prefix="flowgger_fleet_bench_")
    solo = {}
    for rank in (0, 1):
        r = finish(run_worker(rank, "0", "none",
                              os.path.join(tmp, f"solo_{rank}.bin")),
                   f"solo {rank}")
        if r is None:
            return False
        solo[rank] = r
    best_solo = max(solo[0]["lines_per_sec"], solo[1]["lines_per_sec"])

    headroom = _parallel_headroom()
    if headroom >= 2.5:
        gate, tier = FLEET_GATE, "standard"
    elif headroom >= 1.45:
        gate, tier = FLEET_GATE_SHARED, "2-core tolerance"
    else:
        gate, tier = FLEET_GATE_DEGRADED, "cpu-throttled: correctness-only"
    aggregate = ratio = 0.0
    fleet_res = {}
    ok = ident = converged = False
    for attempt in range(3):
        p0_port, p1_port = free_port(), free_port()
        procs = [run_worker(0, p0_port, "none",
                            os.path.join(tmp, "fleet_0.bin")),
                 run_worker(1, p1_port, p0_port,
                            os.path.join(tmp, "fleet_1.bin"))]
        results = [finish(p, f"fleet {i}") for i, p in enumerate(procs)]
        if any(r is None for r in results):
            return False
        fleet_res = {r["rank"]: r for r in results}
        # aggregate over the slowest wall: both streams done by then
        slowest = max(r["wall_s"] for r in results)
        aggregate = sum(r["lines"] for r in results) / slowest
        ratio = aggregate / max(best_solo, 1)
        converged = all(r["peers_active"] >= 2 for r in results)
        ident = all(
            open(os.path.join(tmp, f"fleet_{rank}.bin"), "rb").read()
            == open(os.path.join(tmp, f"solo_{rank}.bin"), "rb").read()
            for rank in (0, 1))
        ok = ratio >= gate and ident and converged
        if ok:
            break
        print("fleet smoke: a gate missed, retrying once for jitter",
              file=sys.stderr)

    # self-healing drill: coordinator_kill on a 2-process fleet under
    # sustained ingest (tools/chaos.py asserts survivor byte-cleanness,
    # one agreed fallback rendezvous, and the journaled transitions
    # itself — here we gate its reconvergence time).  The ladder bound
    # is evict + depart + slack at the chaos workers' own timings; the
    # tiering mirrors the scale-out gate: hard bound with real
    # headroom, 2x on a 2-core box, correctness-only (drill must still
    # SUCCEED inside its window) when cpu-throttled.
    failover = {"ok": False}
    for attempt in range(2):
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "chaos.py"),
             "--hosts", "2", "--events", "1",
             "--sites", "coordinator_kill", "--window", "60",
             "--json"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        try:
            stdout, stderr = proc.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            # SIGTERM first: the harness's handler tears its worker
            # fleet down (a bare kill would orphan 2 fsync-looping
            # workers under every later gate on this box)
            proc.terminate()
            try:
                proc.communicate(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.communicate()
            print("fleet smoke: chaos drill timed out", file=sys.stderr)
            break
        try:
            report = json.loads(stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            print(f"fleet smoke: chaos drill printed no report "
                  f"(rc={proc.returncode}):\n{stderr[-2000:]}",
                  file=sys.stderr)
            break
        bound = report.get("ladder_bound_s") or 10.0
        if headroom >= 2.5:
            reconverge_gate, fo_tier = bound, "standard"
        elif headroom >= 1.45:
            reconverge_gate, fo_tier = bound * 2, "2-core tolerance"
        else:
            reconverge_gate, fo_tier = None, \
                "cpu-throttled: correctness-only"
        reconverge = report.get("max_reconverge_s")
        fo_ok = bool(report.get("ok")) and proc.returncode == 0 and (
            reconverge_gate is None
            or (reconverge is not None and reconverge <= reconverge_gate))
        failover = {
            "drill": "coordinator_kill",
            "reconverge_s": reconverge,
            "ladder_bound_s": bound,
            "reconverge_gate_s": reconverge_gate,
            "gate_note": fo_tier,
            "drill_report_ok": bool(report.get("ok")),
            "ok": fo_ok,
        }
        if fo_ok:
            break
        print("fleet smoke: failover drill missed its gate, retrying "
              "once for jitter", file=sys.stderr)
    ok = ok and failover["ok"]

    payload = {
        "metric": "fleet_smoke",
        "hosts": 2,
        "lines_per_host": FLEET_LINES,
        "solo_lines_per_sec": [solo[0]["lines_per_sec"],
                               solo[1]["lines_per_sec"]],
        "aggregate_lines_per_sec": round(aggregate, 1),
        "aggregate_vs_single_host": round(ratio, 2),
        "parallel_headroom_3way": round(headroom, 2),
        "gate": gate,
        "gate_note": tier,
        "byte_identical_vs_solo": ident,
        "membership_converged": converged,
        "failover": failover,
        "ok": bool(ok),
    }
    print(json.dumps(payload))
    extra["fleet_smoke"] = payload
    return ok


def bench_aot(extra, smoke):
    """Zero-JIT boot (tpu/aot.py) smoke gates:

    1. build + **warm** a CPU-platform decode artifact set in a temp
       dir (in a subprocess — the builder points JAX's persistent
       cache inside the artifact dir, which must not leak here);
    2. per framing (line/nul/syslen), boot a COLD subprocess with
       ``input.tpu_aot_dir``: gate ``compile_cache_misses == 0`` AND
       ``aot_hits > 0`` (zero fresh kernel compiles — the exported
       programs' StableHLO→executable step hits the warmed xla-cache
       shipped in the artifact dir) AND the emitted bytes are
       byte-identical to the scalar oracle;
    3. boot a cold JIT subprocess of the same config for the
       time-to-first-emitted-batch comparison (BENCH_r08.json);
    4. TPU-platform fused-route artifacts build-only on this host:
       serialize + deserialize/manifest round trip (`validate`).
    """
    import subprocess
    import tempfile

    from flowgger_tpu.config import Config
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.mergers import LineMerger, NulMerger, SyslenMerger

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "FLOWGGER_DEVICE_ENCODE": "0"}

    def run(code):
        try:
            r = subprocess.run([sys.executable, "-c", code], env=env,
                               capture_output=True, text=True,
                               timeout=300)
        except subprocess.TimeoutExpired:
            # a wedged boot must fail THIS gate, not abort the whole
            # smoke before the summary JSON prints
            print("aot smoke subprocess timed out (300s)",
                  file=sys.stderr)
            return None
        if r.returncode != 0:
            print(f"aot smoke subprocess failed:\n{r.stderr}",
                  file=sys.stderr)
            return None
        lines = r.stdout.strip().splitlines()
        if not lines:
            print("aot smoke subprocess printed nothing",
                  file=sys.stderr)
            return None
        return lines[-1]

    with tempfile.TemporaryDirectory() as td:
        art = os.path.join(td, "artifacts")
        t0 = time.perf_counter()
        built = run(
            "from flowgger_tpu.tpu import aot\n"
            f"aot.build_artifacts({art!r}, platforms=('cpu',), "
            "families=('decode',), formats=('rfc5424',), "
            "rows_grid=(256,), max_len=64, framings=('line',), "
            "warm=True, quiet=True)\n"
            "print('built')\n")
        build_s = time.perf_counter() - t0
        if built is None:
            print(json.dumps({"metric": "aot_smoke", "ok": False,
                              "stage": "build"}))
            return False

        # scalar-oracle expected bytes per framing
        cfg0 = Config.from_string("")
        dec, enc = RFC5424Decoder(cfg0), GelfEncoder(cfg0)
        lines = [b"<13>1 2024-01-01T00:00:00Z h a p m - msg %d" % i
                 for i in range(AOT_BOOT_LINES)]
        mergers = {"line": LineMerger(), "nul": NulMerger(),
                   "syslen": SyslenMerger()}
        expected = {
            fr: b"".join(m.frame(enc.encode(dec.decode(ln.decode())))
                         for ln in lines)
            for fr, m in mergers.items()}

        boots = {}
        ok = True
        for fr in ("line", "nul", "syslen"):
            line_out = run(_aot_boot_script(fr, art))
            if line_out is None:
                ok = False
                continue
            b = json.loads(line_out)
            b["oracle_identical"] = bytes.fromhex(b.pop("out")) == \
                expected[fr]
            b["zero_fresh_compiles"] = (b["misses"] == 0
                                        and b["aot_hits"] > 0
                                        and b["aot_rejects"] == 0)
            boots[fr] = b
            ok = ok and b["oracle_identical"] and b["zero_fresh_compiles"]

        jit_line = run(_aot_boot_script("line", ""))
        jit_boot = json.loads(jit_line) if jit_line else {}
        if jit_line:
            ok = ok and bytes.fromhex(
                jit_boot.pop("out")) == expected["line"]
        else:
            ok = False

        # TPU-platform export is build-only here (this host cannot
        # execute it): the acceptance is serialize + deserialize +
        # manifest-validation round trip for all four fused routes
        tpu_art = os.path.join(td, "tpu-artifacts")
        t1 = time.perf_counter()
        tpu_ok = run(
            "from flowgger_tpu.tpu import aot\n"
            f"aot.build_artifacts({tpu_art!r}, platforms=('tpu',), "
            "families=('fused',), rows_grid=(256,), max_len=64, "
            "framings=('line',), quiet=True)\n"
            f"s = aot.validate_artifacts({tpu_art!r}, quiet=True)\n"
            "assert all(s[f'tpu/fused_{r}'] == 2 for r in "
            "aot.FUSED_ROUTES), s\n"
            "print('tpu-roundtrip-ok')\n") == "tpu-roundtrip-ok"
        tpu_s = time.perf_counter() - t1
        ok = ok and tpu_ok

    aot_first = boots.get("line", {}).get("first_batch_s")
    jit_first = jit_boot.get("first_batch_s")
    payload = {
        "metric": "aot_smoke",
        # cpu: decode-family artifacts on the CPU backend —
        # boot-time ratio is the claim, not an accelerator rate
        "backend": "cpu",
        "build_warm_seconds": round(build_s, 1),
        "tpu_export_roundtrip_seconds": round(tpu_s, 1),
        "tpu_fused_roundtrip_ok": tpu_ok,
        "boots": boots,
        "jit_boot_first_batch_s": jit_first,
        "aot_boot_first_batch_s": aot_first,
        "ok": bool(ok),
    }
    print(json.dumps(payload))
    extra["aot_smoke"] = payload
    return bool(ok)


def bench_new_formats(extra, smoke):
    """jsonl/dns block routes (PR 10): byte identity vs the scalar
    pipeline and block-route throughput at or above the scalar path.

    Clean corpora (the tier's target workload) through the full
    BatchHandler with a GELF/line sink vs the per-line
    decoder→encoder→merger reference.  The first block pass pays the
    one bucket shape's kernel compile (excluded from the rate); the
    gate retries once for scheduler jitter before failing."""
    import queue as _q

    from flowgger_tpu.block import EncodedBlock
    from flowgger_tpu.config import Config
    from flowgger_tpu.decoders import DNSDecoder, JSONLDecoder
    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.mergers import LineMerger
    from flowgger_tpu.tpu.batch import BatchHandler

    import jax

    # gate tiering (the bench_fleet precedent: hard gate where the
    # hardware can honor it, correctness floor + recorded ratio where
    # it cannot): on the CPU backend the JSON structural-index
    # kernel loses to C json.loads by design — the vectorized win is
    # the accelerator's — so the jsonl throughput gate drops to a
    # structural-regression floor there; the dns fixed-grammar kernel
    # beats the scalar path even on cpu and keeps the hard gate
    cpu_fallback = jax.default_backend() == "cpu"
    floors = {"jsonl": 0.25 if cpu_fallback else 1.0, "dns": 1.0}
    n = 4_096 if smoke else 16_384
    cfg = Config.from_string(
        f"[input]\ntpu_batch_size = {n}\ntpu_max_line_len = 192\n")
    corp = {
        "jsonl": [(f'{{"timestamp":14387900{i % 100:02d}.25,'
                   f'"host":"h{i % 5}",'
                   f'"message":"request served {i}","level":{i % 8},'
                   f'"path":"/api/v{i % 3}","ms":{i % 250}}}').encode()
                  for i in range(n)],
        "dns": [(f"14387900{i % 100:02d}.5\t10.0.{i % 256}.{i % 100}\t"
                 f"svc{i % 40}.example.com.\tA\tNOERROR\t"
                 f"{1 + i % 9000}").encode()
                for i in range(n)],
    }
    decs = {"jsonl": JSONLDecoder(cfg), "dns": DNSDecoder(cfg)}
    merger = LineMerger()
    sections = {}
    ok = True
    for fmt, lines in corp.items():
        dec = decs[fmt]
        enc = GelfEncoder(cfg)
        t0 = time.perf_counter()
        want = [merger.frame(enc.encode(dec.decode(ln.decode())))
                for ln in lines]
        scalar_rate = len(lines) / (time.perf_counter() - t0)

        def run_block():
            tx = _q.Queue()
            h = BatchHandler(tx, dec, enc, cfg, fmt=fmt,
                             start_timer=False, merger=merger)
            chunk = b"".join(ln + b"\n" for ln in lines)
            t1 = time.perf_counter()
            h.ingest_chunk(chunk)
            h.flush()
            dt = time.perf_counter() - t1
            h.close()
            got = []
            while not tx.empty():
                item = tx.get_nowait()
                if isinstance(item, EncodedBlock):
                    got.extend(item.iter_framed())
                else:
                    got.append(merger.frame(item))
            return got, len(lines) / dt

        floor = floors[fmt]
        run_block()  # warmup: the bucket shape's kernel compile
        got, block_rate = run_block()
        identical = got == want
        if not identical or block_rate < floor * scalar_rate:
            # one retry for scheduler jitter on small shared boxes
            got, block_rate = run_block()
            identical = got == want
        fmt_ok = identical and block_rate >= floor * scalar_rate
        ok &= fmt_ok
        sections[fmt] = {
            "scalar_lines_per_sec": round(scalar_rate),
            "block_lines_per_sec": round(block_rate),
            "block_vs_scalar": round(block_rate / max(scalar_rate, 1), 2),
            "gate_floor": floor,
            "byte_identical": bool(identical),
            "ok": bool(fmt_ok),
        }
        print(f"new-format {fmt}: scalar {scalar_rate / 1e3:.0f}K "
              f"lines/s, block {block_rate / 1e3:.0f}K lines/s "
              f"({block_rate / max(scalar_rate, 1):.1f}x), "
              f"identical={identical}", file=sys.stderr)
    payload = {"metric": "new_formats", "lines": n, **sections,
               "ok": bool(ok)}
    print(json.dumps(payload))
    extra["new_formats"] = payload
    return bool(ok)


def bench_framing(extra, smoke):
    """Device-resident framing gates (tpu/framing.py):

    1. Byte identity: the device-framed pipeline (raw chunks → on-device
       span kernel + gather) must emit exactly the host-splitter
       pipeline's bytes on line, nul, AND syslen framing (hard gate).
    2. Span-metadata economics: the framing path fetches only the span
       vectors (8 B/row + scalars); fetched bytes/row must stay under
       emitted bytes/row (hard gate — this is the D2H the tier saves).
    3. Throughput: device-framed e2e >= host-pack e2e on >= 1 framing.
       Tiered like the fleet/new-format gates: hard on an accelerator
       backend; on the CPU the jnp span kernels legitimately lose
       to the native memcpy pack, so the gate drops to a structural
       floor with the ratio always recorded (the economics arm routes
       real traffic to the winner either way).
    """
    import queue as _q

    import jax

    from flowgger_tpu.block import EncodedBlock
    from flowgger_tpu.config import Config
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.encoders.ltsv import LTSVEncoder
    from flowgger_tpu.mergers import LineMerger
    from flowgger_tpu.splitters import (LineSplitter, NulSplitter,
                                        SyslenSplitter)
    from flowgger_tpu.tpu.batch import BatchHandler
    from flowgger_tpu.utils.metrics import registry as _registry

    cpu_fallback = jax.default_backend() == "cpu"
    # CPU-backend floor: a structural smoke-out, not a perf claim —
    # the jnp span kernels lose to the native memcpy pack here by
    # design (BENCH_r12) and the economics arm routes production
    # traffic to the winner.  Calibration: syslen (XLA-scatter-bound
    # pointer doubling) measured 0.13x at PR 12 and 0.09x in later
    # shared-container windows with the identical code — 0.1 flapped
    # on neighbor load, so the floor sits at 0.04 (a structural
    # regression, e.g. a decline loop re-framing every batch, lands
    # well below it; the ratio itself is always in the JSON line)
    rate_floor = 0.04 if cpu_fallback else 1.0
    n = 4_096 if smoke else 16_384
    lines = [(f"<34>1 2023-10-11T22:14:15.00{i % 10}Z host{i % 7} app "
              f"{i} ID47 - request served in {i % 900}us path=/v{i % 4}"
              ).encode() for i in range(n)]
    streams = {
        "line": (LineSplitter, b"".join(ln + b"\n" for ln in lines)),
        "nul": (NulSplitter, b"".join(ln + b"\0" for ln in lines)),
        "syslen": (SyslenSplitter,
                   b"".join(b"%d %s" % (len(ln), ln) for ln in lines)),
    }
    base = (f"[input]\ntpu_batch_size = {n}\ntpu_max_line_len = 192\n"
            'tpu_fuse = "off"\n')

    class _Chunked:
        def __init__(self, data):
            self.data, self.pos = data, 0

        def read(self, nbytes):
            out = self.data[self.pos:self.pos + (1 << 16)]
            self.pos += len(out)
            return out

    def run(framing_cfg, splitter_cls, stream):
        # the "on" runs pin the framing tier (economics off) so the
        # measured rate is the pure device-framed path — in production
        # the economics arm routes each flush to the winner, which on a
        # CPU box is usually the host pack this gate records
        cfg = Config.from_string(
            base + f'tpu_framing = "{framing_cfg}"\n'
            + ("tpu_encode_economics = false\n"
               if framing_cfg == "on" else ""))
        tx = _q.Queue()
        h = BatchHandler(tx, RFC5424Decoder(), LTSVEncoder(cfg), cfg,
                         fmt="rfc5424", start_timer=False,
                         merger=LineMerger())
        t0 = time.perf_counter()
        splitter_cls().run(_Chunked(stream), h)
        dt = time.perf_counter() - t0
        h.close()
        got = []
        while not tx.empty():
            item = tx.get_nowait()
            got.extend(item.iter_framed()
                       if isinstance(item, EncodedBlock) else [item])
        return got, n / dt

    sections = {}
    ok = True
    any_faster = False
    for name, (splitter_cls, stream) in streams.items():
        run("on", splitter_cls, stream)   # warmup: framing + decode
        run("off", splitter_cls, stream)  # compiles out of the rates
        want, host_rate = run("off", splitter_cls, stream)
        _registry.reset()
        got, dev_rate = run("on", splitter_cls, stream)
        identical = got == want
        rows = _registry.get("framing_rows")
        emitted = sum(len(g) for g in got)
        fetch_pr = (_registry.get("framing_span_fetch_bytes")
                    / max(rows, 1))
        emit_pr = emitted / max(len(got), 1)
        engaged = rows >= n
        fetch_ok = fetch_pr < emit_pr
        ratio = dev_rate / max(host_rate, 1)
        any_faster |= engaged and ratio >= 1.0
        fr_ok = identical and engaged and fetch_ok \
            and ratio >= rate_floor
        ok &= fr_ok
        sections[name] = {
            "host_pack_lines_per_sec": round(host_rate),
            "device_framed_lines_per_sec": round(dev_rate),
            "device_vs_host": round(ratio, 2),
            "framing_rows": rows,
            "span_fetch_bytes_per_row": round(fetch_pr, 1),
            "emit_bytes_per_row": round(emit_pr, 1),
            "byte_identical": bool(identical),
            "ok": bool(fr_ok),
        }
        print(f"framing {name}: host-pack {host_rate / 1e3:.0f}K "
              f"lines/s, device-framed {dev_rate / 1e3:.0f}K lines/s "
              f"({ratio:.2f}x), span fetch {fetch_pr:.0f} B/row vs "
              f"emit {emit_pr:.0f} B/row, identical={identical}",
              file=sys.stderr)
    if not cpu_fallback and not any_faster:
        ok = False
    # the deleted host stage, by component (observability satellite):
    # slice (separator scan) + copy (arena memcpy) walls from the host
    # runs above — on an engaged device-framing run both stay ~0
    payload = {"metric": "framing_smoke",
               "gate_tier": ("cpu-correctness" if cpu_fallback
                             else "accelerator"),
               "lines": n,
               "device_ge_host_on_some_framing": bool(any_faster),
               **sections, "ok": bool(ok)}
    print(json.dumps(payload))
    extra["framing_smoke"] = payload
    return bool(ok)


def smoke_main():
    """``bench.py --smoke``: the CI gate for the overlap executor.

    Tiny corpus on a forced 4-device CPU backend with the device-encode
    tier's kill switch thrown (those kernels compile for minutes on
    small hosts and have their own differential tests on capable ones):
    runs the serial e2e, the 1-lane overlap e2e, and the 2-lane
    multi-device e2e; asserts the overlap executor sustains at least
    the serial rate AND that 2-lane dispatch sustains the 1-lane rate
    (within LANE_TOL measurement noise — on a 2-core host the
    concurrency ceiling is ~1.26x and run-to-run jitter is ~±10%, so a
    hard >=1.0 gate flaps; a structural lane regression shows up far
    below the tolerance), and bounds the whole run under 120s."""
    import os

    t_start = time.perf_counter()
    os.environ["JAX_PLATFORMS"] = "cpu"
    print("bench.py --smoke: CPU CI gate (correctness and guard-cost "
          "checks); no number below is a device number", file=sys.stderr)
    os.environ.setdefault("FLOWGGER_DEVICE_ENCODE", "0")
    # a virtual multi-device CPU backend so the lane-dispatch claim is
    # exercised for real (must land before jax initializes)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()

    # fleet federation FIRST, before jax ever loads here: the section
    # is jax-free subprocesses, and the later fused-route section
    # leaves background XLA compiles chewing both cores of a small box
    # for minutes (watchdog-declined but still warming) — measured, it
    # halves the fleet workers' rates and compresses the scale-out
    # ratio toward 1.0 regardless of real federation behavior
    fleet_extra = {}
    fleet_ok = bench_fleet(fleet_extra, smoke=True)

    import jax

    # persistent compile cache, placed by the program's own rule
    # (JAX_COMPILATION_CACHE_DIR as it is, else the in-checkout default)
    from flowgger_tpu.tpu.device_common import enable_compile_cache

    enable_compile_cache()

    global E2E_BATCH
    E2E_BATCH = 8_192
    LANE_TOL = 0.92
    lines = gen_lines(E2E_BATCH)
    serial = overlap = multilane = 0
    ok = lanes_ok = False
    for attempt in range(2):
        extra = {}
        bench_e2e(lines, jax, None, extra)
        bench_e2e_overlap(lines, extra, smoke=True, trials=3)
        bench_e2e_overlap(lines, extra, smoke=True, lanes=2, trials=3)
        serial = extra["e2e_lines_per_sec"]
        overlap = extra["e2e_overlap_lines_per_sec"]
        multilane = extra["e2e_multilane_lines_per_sec"]
        ok = overlap >= serial
        lanes_ok = multilane >= LANE_TOL * overlap
        if ok and lanes_ok:
            break
        # noisy single-box measurements: retry the set once before
        # failing the gate on scheduler jitter
        print("smoke: a gate missed, retrying once for jitter",
              file=sys.stderr)
    # tenancy section: admission-overhead micro-gate (<3% of per-chunk
    # e2e cost), template mining rate + ID stability, off-path structure
    tenancy_ok = bench_tenancy(extra, lines)
    # observability section: tracing-off guard cost < 1% of per-chunk
    # e2e cost, ring-mode cost recorded, journal + exposition sanity
    obs_ok = bench_obs(extra, lines)
    # durability section: disarmed-watermark guard cost < 1% of
    # per-chunk e2e cost + spill→replay byte identity with a drained
    # cursor and an empty WAL after sink acks
    durability_ok = bench_durability(extra, lines)
    # control plane: disarmed guard cost < 1% of per-chunk e2e,
    # disarmed structure (no plane, no ticker/proxy threads), and the
    # flood-to-tighten closed-loop reaction time on real short windows
    control_ok = bench_control(extra, lines)
    # jsonl/dns block routes: byte identity vs the scalar pipeline +
    # block throughput >= scalar (runs BEFORE the fused section, whose
    # declined background compiles would chew the cores under it)
    newfmt_ok = bench_new_formats(extra, smoke=True)
    # device-resident framing: byte identity vs the host splitters on
    # all three framings + span-metadata fetch under emit bytes/row
    # (runs before the fused section for the same clean-machine reason)
    framing_ok = bench_framing(extra, smoke=True)
    # fused route matrix: byte-identical to the split path + fetched
    # bytes/row at or under the split path's (and under emitted)
    fused_ok = bench_fused_routes(extra, smoke=True)
    # zero-JIT boot: artifact-booted cold subprocess must perform zero
    # fresh kernel compiles and match the scalar oracle per framing;
    # TPU fused artifacts must round-trip build-only
    aot_ok = bench_aot(extra, smoke=True)
    # fleet federation ran first (clean machine); fold its record into
    # the final extra dict, which the retry loop above resets
    extra.update(fleet_extra)
    wall = time.perf_counter() - t_start
    # the fused gates run the four fused programs eagerly where this
    # host can't compile them (~40s on a 2-core box), the AOT section
    # adds ~5 cold subprocess boots + the TPU export (~80s), the fleet
    # section 6 jax-free subprocess runs (~15s), and the new-format
    # section two foreground kernel compiles (~60s), and the framing
    # section ~9 short e2e passes + three span-kernel compiles (~40s),
    # so the smoke budget is 630s — still bounded, still CI-friendly
    budget = 630
    print(json.dumps({
        "metric": "e2e_overlap_smoke",
        "e2e_lines_per_sec": serial,
        "e2e_overlap_lines_per_sec": overlap,
        "e2e_multilane_lines_per_sec": multilane,
        "lanes_run": 2,
        "overlap_vs_serial": round(overlap / max(serial, 1), 2),
        "multilane_vs_single_lane": round(multilane / max(overlap, 1), 2),
        "wall_seconds": round(wall, 1),
        "ok": bool(ok and lanes_ok and tenancy_ok and obs_ok
                   and durability_ok and control_ok and newfmt_ok
                   and framing_ok and fused_ok and aot_ok
                   and fleet_ok and wall < budget),
    }))
    if not framing_ok:
        print("SMOKE FAIL: device-framing gates missed (byte identity "
              "vs the host splitters on line/nul/syslen, span-metadata "
              "fetch bytes/row above emitted, or throughput below the "
              "backend-tiered floor — see the framing_smoke JSON line)",
              file=sys.stderr)
        sys.exit(1)
    if not newfmt_ok:
        print("SMOKE FAIL: jsonl/dns block-route gates missed (byte "
              "identity vs the scalar pipeline, or block throughput "
              "below the backend-tiered floor of the scalar path — "
              "see the new_formats JSON line)", file=sys.stderr)
        sys.exit(1)
    if not fleet_ok:
        print("SMOKE FAIL: fleet federation gates missed (aggregate "
              "2-host rate vs single host, byte identity vs the solo "
              "runs, membership never converged, or the "
              "coordinator-kill failover drill missed its tiered "
              "reconvergence bound — see the fleet_smoke JSON line)",
              file=sys.stderr)
        sys.exit(1)
    if not aot_ok:
        print("SMOKE FAIL: zero-JIT boot gates missed (fresh compiles "
              "on an artifact boot, scalar-oracle mismatch, or the "
              "TPU fused-route export round trip — see the aot_smoke "
              "JSON line)", file=sys.stderr)
        sys.exit(1)
    if not fused_ok:
        print("SMOKE FAIL: fused-route gates missed (byte identity vs "
              "the split path, or fetched bytes/row above the split "
              "path's / the emitted bytes/row — see the fused_routes "
              "JSON line)", file=sys.stderr)
        sys.exit(1)
    if not tenancy_ok:
        print("SMOKE FAIL: tenancy gates missed (admission overhead, "
              "template stability, or off-path residue — see the "
              "tenancy_smoke JSON line)", file=sys.stderr)
        sys.exit(1)
    if not obs_ok:
        print("SMOKE FAIL: observability gates missed (tracing-off or "
              "SLO-plane guard cost above 1% of per-chunk e2e, the "
              "BENCH-seeded sentinel flagged this run as a perf "
              "regression — or failed to flag a synthetic throttle — "
              "or journal/exposition sanity — see the obs_smoke JSON "
              "line)", file=sys.stderr)
        sys.exit(1)
    if not durability_ok:
        print("SMOKE FAIL: durability gates missed (disarmed-watermark "
              "guard cost above 1% of per-chunk e2e, spill→replay "
              "bytes diverged from the straight run, or the WAL did "
              "not drain on sink acks — see the durability_smoke JSON "
              "line)", file=sys.stderr)
        sys.exit(1)
    if not control_ok:
        print("SMOKE FAIL: control gates missed (disarmed guard cost "
              "above 1% of per-chunk e2e, control-plane residue on a "
              "default pipeline, or the flood-to-tighten reaction "
              "exceeded its bound — see the control_smoke JSON line)",
              file=sys.stderr)
        sys.exit(1)
    if not ok:
        print("SMOKE FAIL: overlap executor slower than the serial path",
              file=sys.stderr)
        sys.exit(1)
    if not lanes_ok:
        print(f"SMOKE FAIL: 2-lane dispatch below {LANE_TOL:.2f}x the "
              "1-lane rate", file=sys.stderr)
        sys.exit(1)
    if wall >= budget:
        print(f"SMOKE FAIL: {wall:.0f}s exceeds the {budget}s budget",
              file=sys.stderr)
        sys.exit(1)


def main():
    import argparse
    import os

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="overlap-executor CI smoke: tiny batch, CPU "
                         "backend, asserts overlap >= serial e2e, <60s")
    ap.add_argument("--fleet-worker", nargs=5,
                    metavar=("RANK", "PORT", "COORDPORT", "NLINES", "OUT"),
                    help="internal: one fleet-bench host (see "
                         "fleet_worker_main)")
    args = ap.parse_args()
    if args.fleet_worker:
        fleet_worker_main(args.fleet_worker)
        return
    if args.smoke:
        smoke_main()
        return

    smoke = bool(os.environ.get("FLOWGGER_BENCH_SMOKE"))
    if smoke:
        # the CPU CI gate: tiny shapes, no device number
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    dev = jax.devices()[0]
    if smoke:
        print("bench.py (FLOWGGER_BENCH_SMOKE): CPU CI gate; no number "
              "below is a device number", file=sys.stderr)
    elif dev.platform != "tpu":
        # a measurement path that finds no chip fails: it does not
        # fall back, and it prints no result
        print(f"bench.py measures on a TPU and JAX found none (first "
              f"device: {dev.platform} {dev.device_kind}).  --smoke or "
              "FLOWGGER_BENCH_SMOKE=1 runs the CPU CI gate instead.",
              file=sys.stderr)
        sys.exit(1)
    # persistent compile cache, placed by the program's own rule
    # (JAX_COMPILATION_CACHE_DIR as it is, else the in-checkout default)
    from flowgger_tpu.tpu.device_common import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp

    from flowgger_tpu.tpu import pack, rfc5424

    print(f"bench device: {dev}", file=sys.stderr)

    global BATCH_LINES, CHAIN, TRIALS, E2E_BATCH
    if smoke:
        # CI smoke: tiny shapes, just prove the full path runs
        BATCH_LINES, CHAIN, TRIALS, E2E_BATCH = 8_192, 2, 1, 8_192

    lines = gen_lines(BATCH_LINES)
    t0 = time.perf_counter()
    batch, lens, chunk, starts, orig_lens, n = pack.pack_lines_2d(lines, MAX_LEN)
    t_pack = time.perf_counter() - t0
    print(f"host pack: {t_pack:.2f}s ({n / t_pack / 1e6:.2f}M lines/s host-side)",
          file=sys.stderr)

    def chained(b, ln):
        def body(i, carry):
            out = rfc5424.decode_rfc5424(
                jnp.bitwise_xor(b, (carry % 2).astype(jnp.uint8)), ln)
            c = digest_all(jnp, out) & 1
            return carry + c

        return jax.lax.fori_loop(0, CHAIN, body, jnp.int32(0))

    jf = jax.jit(chained)
    db = jax.device_put(jnp.asarray(batch), dev)
    dl = jax.device_put(jnp.asarray(lens), dev)
    int(jf(db, dl))  # H2D + compile + warmup

    best = None
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        int(jf(db, dl))  # scalar D2H = true completion barrier
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    per_batch = best / CHAIN
    lines_per_sec = n / per_batch
    print(
        f"device decode: {per_batch * 1e3:.1f}ms per {n}-line batch "
        f"(chain of {CHAIN}) -> {lines_per_sec / 1e6:.1f}M lines/s",
        file=sys.stderr,
    )

    # batch decode latency incl. the dispatch round trip — a real p99
    # (BASELINE.json metric: "p99 decode latency @ 1M-line batch"):
    # >= 100 trials on the device, 3 in the CPU smoke
    lat_trials = 3 if smoke else 100
    lat = []
    single = jax.jit(lambda b, ln: digest_all(
        jnp, rfc5424.decode_rfc5424(b, ln)))
    int(single(db, dl))
    for _ in range(lat_trials):
        t0 = time.perf_counter()
        int(single(db, dl))
        lat.append(time.perf_counter() - t0)
    lat.sort()
    p50 = lat[len(lat) // 2]
    p99 = lat[min(len(lat) - 1, max(0, -(-99 * len(lat) // 100) - 1))]
    print(
        f"single-batch decode latency (incl. dispatch rtt, "
        f"{lat_trials} trials): p50={p50 * 1e3:.0f}ms "
        f"p99={p99 * 1e3:.0f}ms max={lat[-1] * 1e3:.0f}ms",
        file=sys.stderr,
    )

    lat_ms = {"p50": round(p50 * 1e3, 1),
              "max": round(lat[-1] * 1e3, 1),
              "trials": lat_trials,
              "batch_lines": n}
    # the 3-trial smoke has no real tail: report its sample max under
    # a distinct name so it is never comparable-by-name with the
    # 100-trial device p99 (ADVICE r4)
    if lat_trials >= 50:
        lat_ms["p99"] = round(p99 * 1e3, 1)
    else:
        lat_ms["latency_sample_max_ms"] = round(p99 * 1e3, 1)
    extra = {"batch_latency_ms": lat_ms}
    bench_fallback_corpora(jax, jnp, extra, smoke)
    bench_host_scaling(lines[:65_536], extra, smoke)
    # jsonl/dns block routes (PR 10): identity + throughput vs scalar
    bench_new_formats(extra, smoke)
    # device-resident framing (PR 12): identity + span-fetch economics
    # + device-framed vs host-pack e2e per framing
    bench_framing(extra, smoke)
    # fused decode→encode route matrix (before the overlap sections:
    # its eager fallback leaves no background compiles behind, but the
    # overlap section's cold device-encode shapes must still run last)
    bench_fused_routes(extra, smoke)
    bench_e2e(lines[:E2E_BATCH], jax, jnp, extra)
    bench_other_configs(jax, jnp, dev, smoke, extra)
    # last: a cold device-encode shape here leaves a background compile
    # running (watchdog single-flight) that must not pollute the
    # sections above
    bench_e2e_overlap(lines[:E2E_BATCH], extra, smoke)
    ndev = jax.local_device_count()
    if ndev > 1:
        # multi-device lane dispatch: one batch stream round-robined
        # across per-chip lanes (input.tpu_lanes)
        bench_e2e_overlap(lines[:E2E_BATCH], extra, smoke,
                          lanes=min(4, ndev))
    # durability (WAL spill tier): guard cost + spill→replay identity —
    # the smoke gates these; the full run records the numbers
    bench_durability(extra, lines[:E2E_BATCH])

    # scalar CPU baseline (the reference's per-line architecture)
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder

    oracle = RFC5424Decoder()
    sample = [ln.decode() for ln in lines[:20000]]
    t0 = time.perf_counter()
    for ln in sample:
        oracle.decode(ln)
    scalar_rate = len(sample) / (time.perf_counter() - t0)
    print(f"scalar python decode: {scalar_rate / 1e3:.0f}K lines/s "
          f"(device path = {lines_per_sec / scalar_rate:.0f}x)", file=sys.stderr)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if smoke:
        # the CPU gate ran to its end; its rates are CPU rates and get
        # no device metric's name
        print(json.dumps({
            "metric": "cpu_ci_smoke", "ok": True, "device": device,
            "cpu_decode_lines_per_sec": round(lines_per_sec),
            **extra,
        }))
        return
    print(json.dumps({
        "metric": "rfc5424_decode_lines_per_sec_per_chip",
        "value": round(lines_per_sec),
        "unit": "lines/sec",
        "vs_baseline": round(lines_per_sec / BASELINE_LINES_PER_SEC, 3),
        "device": device,
        **extra,
    }))


if __name__ == "__main__":
    main()
