#!/usr/bin/env python
"""Dump flight-recorder batch traces as Chrome trace-event JSON.

Three sources, one output (Perfetto / chrome://tracing loadable):

    # a live collector's completed-batch ring (GET /trace on the fleet
    # health server or the standalone [metrics] prom_port listener)
    python tools/trace_dump.py --url http://127.0.0.1:8476/trace -o t.json

    # a [metrics] trace = "jsonl" capture (one batch-trace object per
    # line, written by obs/trace.py as batches complete)
    python tools/trace_dump.py --jsonl trace.jsonl -o t.json

    # the WHOLE fleet: walk the seed host's roster, pull every
    # routable host's ring, and merge into one document with one
    # process lane per host (pid = fleet rank, labeled "rank N @
    # addr") — the span timelines are wall-clock anchored per process,
    # so two hosts' batches lay side by side on one timeline
    python tools/trace_dump.py --fleet 127.0.0.1:8476 -o fleet.json

A batch's sub-spans (``window_wait``, ``h2d``, ``device_wait``, one
``d2h`` per copy, one ``splice`` where rows took the scalar oracle)
follow its stages as ``cat: "sub"`` events with their ``parent`` stage
in ``args``: same thread, contained in time, so the viewer nests them
under it.

Without ``-o`` the document prints to stdout.  Exit codes: 0 dumped,
2 unreadable source / bad arguments (lint-style, so a soak-run script
can gate on it; ``--fleet`` tolerates individual dead hosts but fails
only when NO host's ring was reachable).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _from_url(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=5) as resp:
        doc = json.loads(resp.read())
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("endpoint did not return a trace document "
                         "(expected a traceEvents object)")
    return doc


def _from_jsonl(path: str) -> dict:
    from flowgger_tpu.obs.trace import chrome_events

    traces = []
    with open(path, "r") as fd:
        for i, line in enumerate(fd, 1):
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if not isinstance(rec, dict) or "spans" not in rec:
                raise ValueError(f"line {i}: not a batch-trace object")
            traces.append(rec)
    return {"traceEvents": chrome_events(traces), "displayTimeUnit": "ms"}


def _from_fleet(seed: str) -> dict:
    """Merge every routable fleet host's /trace ring into one document
    with per-host process lanes: the seed's /healthz roster names the
    hosts, each host's events are re-homed to ``pid = rank`` and a
    ``process_name`` metadata event labels the lane."""
    with urllib.request.urlopen(f"http://{seed}/healthz",
                                timeout=5) as resp:
        health = json.loads(resp.read())
    peers = (health.get("fleet") or {}).get("peers") or []
    if not peers:
        raise ValueError(f"{seed}: /healthz carries no fleet roster")
    merged = []
    pulled = 0
    for peer in sorted(peers, key=lambda p: p.get("rank", 1 << 30)):
        rank, addr = peer.get("rank"), peer.get("addr")
        if peer.get("state") == "departed" or not addr:
            continue
        try:
            doc = _from_url(f"http://{addr}/trace")
        except (OSError, ValueError, urllib.error.URLError) as e:
            print(f"trace_dump: rank {rank} ({addr}) unreachable: {e}",
                  file=sys.stderr)
            continue
        pulled += 1
        merged.append({"name": "process_name", "ph": "M", "pid": rank,
                       "tid": 0, "args": {"name": f"rank {rank} @ {addr}"}})
        for event in doc.get("traceEvents", []):
            if isinstance(event, dict):
                event = dict(event)
                event["pid"] = rank
                merged.append(event)
    if pulled == 0:
        raise ValueError("no fleet host's trace ring was reachable")
    return {"traceEvents": merged, "displayTimeUnit": "ms"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--url", help="live /trace endpoint to fetch")
    src.add_argument("--jsonl", help="[metrics] trace_path capture file")
    src.add_argument("--fleet", metavar="HOST:PORT",
                     help="merge every routable fleet host's ring "
                          "(walks this seed host's /healthz roster)")
    ap.add_argument("-o", "--out", help="write here instead of stdout")
    args = ap.parse_args(argv)
    try:
        if args.url:
            doc = _from_url(args.url)
        elif args.fleet:
            doc = _from_fleet(args.fleet)
        else:
            doc = _from_jsonl(args.jsonl)
    except (OSError, ValueError, urllib.error.URLError) as e:
        print(f"trace_dump: {e}", file=sys.stderr)
        return 2
    rendered = json.dumps(doc)
    if args.out:
        with open(args.out, "w") as fd:
            fd.write(rendered)
        print(f"trace_dump: {len(doc['traceEvents'])} events -> "
              f"{args.out}", file=sys.stderr)
    else:
        print(rendered)
    return 0


if __name__ == "__main__":
    sys.exit(main())
