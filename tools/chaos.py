#!/usr/bin/env python3
"""chaos — the self-healing-fleet drill harness (ISSUE 14 tentpole).

Runs a real N-process localhost fleet under sustained ingest and
injects one fault after another through the deterministic
``utils/faultinject.py`` sites, asserting after EVERY event that the
fleet reconverges — with zero operator action — within a bounded
window:

- every survivor answers ``GET /healthz`` 200 with all live hosts
  active in its view;
- all survivors agree on ONE rendezvous, and it is the lowest live
  active rank (``fleet.rendezvous`` in the health document);
- traffic shares over the routable set sum to ~1 on every survivor
  (the live-rebalance contract);
- no lost lines: every host's fsynced output is a clean prefix of its
  deterministic reference stream, and survivors' outputs keep growing
  (ingest never stopped);
- the transitions are journaled: ``rendezvous_failover`` /
  ``fleet_rebalance`` / ``roster_restore`` events (obs/events.py) are
  observable through the survivors' health documents.

Fault sites exercised (armed at runtime over the chaos-only
``POST /fault`` leg — workers run with ``tpu_fleet_chaos = true``):

``host_kill``         SIGKILL a non-rendezvous host mid-stream; the
                      survivors evict it, shares redistribute, and a
                      replacement (same rank, same roster journal)
                      boots one incarnation later and is re-admitted.
``coordinator_kill``  SIGKILL the host currently holding the
                      rendezvous (the site self-selects); survivors
                      elect the next-lowest active rank, and a
                      BRAND-NEW host (fresh journal) must join through
                      the fallback rendezvous.
``peer_partition``    cut one host off (inbound 503 + outbound replies
                      dropped) long enough to be seen suspect, then
                      heal; suspicion must cure without data loss.
``roster_corrupt``    truncate a host's next roster-journal write,
                      then drain it (SIGTERM); its replacement must
                      boot CLEANLY off the corrupt journal
                      (``fleet_roster_load_errors`` counted, plain
                      coordinator walk, reconverges).

Usage::

    python tools/chaos.py [--hosts 3] [--events 4] [--window 60]
                          [--sites coordinator_kill,host_kill,...]
                          [--json] [--keep-dir]

``--durability`` runs the kill-mid-spill / kill-mid-replay WAL drill
instead; ``--control`` runs the closed-control-loop drills (a flooding
tenant must be burn-tightened within the reaction bound while a calm
tenant's bytes stay identical and its SLO green; a degrading host's
advertised share must decay at its peers BEFORE its decode breaker
trips) — see ``control_main``.

``--events K`` cycles K events through ``--sites`` and exits 0 only if
every drill reconverged and every integrity check held.  ``--json``
prints one machine-readable report line (bench.py consumes
``max_reconverge_s`` for the BENCH_r14 gate).

Internal: ``--worker ...`` is one fleet host (scalar rfc5424→GELF over
a deterministic per-(rank, generation) stream, fsynced per chunk,
fleet heartbeats alongside) — spawned by the harness, never by hand.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# worker fleet timings: fast enough that the full missed-heartbeat
# ladder (evict + depart ~= 2.5s) fits many drills into one CI step,
# slow enough that a loaded 2-core container's scheduling jitter
# cannot fake a missed heartbeat (suspect >> heartbeat)
HB_MS, SUSPECT_MS, EVICT_MS, DEPART_MS, REJOIN_MS = 150, 900, 2200, 900, 200
CHUNK_LINES = 16
CHUNK_SLEEP_S = 0.06  # ~270 lines/s/host of sustained ingest

DEFAULT_SITES = ("coordinator_kill", "host_kill", "peer_partition",
                 "roster_corrupt")


def _line(rank: int, gen: int, i: int) -> str:
    """Deterministic line ``i`` of host ``rank``'s generation ``gen``
    stream — the harness regenerates the same stream to verify clean
    prefixes, so nothing here may depend on time or randomness."""
    return (f"<{(5 * i + rank) % 192}>1 2023-09-20T12:35:45.{i % 1000:03d}Z "
            f"chaos{rank} app{i % 7} {i % 1000} MSGID "
            f'[ex@32473 k="{i}" gen="{gen}"] host {rank} gen {gen} '
            f"line {i}")


# --------------------------------------------------------------- worker

def worker_main(args) -> int:
    """One chaos fleet host (see module doc).  Streams its generation's
    lines forever; SIGTERM = drain-on-departure and clean exit."""
    sys.path.insert(0, _REPO)
    from flowgger_tpu.config import Config
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.fleet import Fleet
    from flowgger_tpu.mergers import LineMerger

    coord = ("" if args.coordinator == "none" else
             f'tpu_fleet_coordinator = "{args.coordinator}"\n')
    roster = ("" if args.roster == "none" else
              f'tpu_fleet_roster_path = "{args.roster}"\n')
    cfg = Config.from_string(
        f"[input]\ntpu_fleet = true\ntpu_fleet_rank = {args.rank}\n"
        f"tpu_fleet_hosts = {args.hosts}\n"
        f"tpu_fleet_port = {args.port}\n{coord}{roster}"
        "tpu_fleet_chaos = true\n"
        f"tpu_fleet_heartbeat_ms = {HB_MS}\n"
        f"tpu_fleet_suspect_ms = {SUSPECT_MS}\n"
        f"tpu_fleet_evict_ms = {EVICT_MS}\n"
        f"tpu_fleet_depart_ms = {DEPART_MS}\n"
        f"tpu_fleet_rejoin_backoff_ms = {REJOIN_MS}\n")
    fleet = Fleet.from_config(cfg)
    fleet.start()

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    parent = os.getppid()

    decoder, encoder, merger = (RFC5424Decoder(),
                                GelfEncoder(Config.from_string("")),
                                LineMerger())
    i = 0
    with open(args.out, "wb") as fd:
        while not stop.is_set():
            if os.getppid() != parent:
                # the harness died without tearing us down (external
                # timeout SIGKILL): a chaos worker must never outlive
                # its run — orphans would fsync forever and tax every
                # later gate on a shared box
                print("chaos-worker: harness gone, draining out",
                      file=sys.stderr)
                stop.set()
                break
            for _ in range(CHUNK_LINES):
                fd.write(merger.frame(encoder.encode(
                    decoder.decode(_line(args.rank, args.gen, i)))))
                i += 1
            # fsync per chunk: whatever a SIGKILL leaves on disk must
            # be an uncorrupted prefix of the reference stream
            fd.flush()
            os.fsync(fd.fileno())
            stop.wait(CHUNK_SLEEP_S)
        fd.flush()
        os.fsync(fd.fileno())
    fleet.enter_draining()
    fleet.shutdown()
    print(json.dumps({"rank": args.rank, "gen": args.gen, "lines": i}),
          flush=True)
    return 0


# -------------------------------------------------------------- harness

class Host:
    """One live worker process the harness tracks."""

    def __init__(self, rank: int, gen: int, port: int, proc, out_path,
                 log_path, roster_path):
        self.rank = rank
        self.gen = gen
        self.port = port
        self.proc = proc
        self.out_path = out_path
        self.log_path = log_path
        self.roster_path = roster_path
        self.last_size = 0


class ChaosError(AssertionError):
    pass


class Harness:
    def __init__(self, hosts: int, window: float, workdir: str,
                 verbose: bool = True):
        self.n = hosts
        self.window = window
        self.dir = workdir
        self.verbose = verbose
        self.hosts: dict = {}  # rank -> Host
        self._ref_cache: dict = {}  # (rank, gen) -> bytes built so far
        self._ref_idx: dict = {}
        self._encode = None

    def log(self, msg: str) -> None:
        if self.verbose:
            print(f"chaos: {msg}", file=sys.stderr, flush=True)

    # -- worker lifecycle --------------------------------------------------
    def _free_port(self) -> int:
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def spawn(self, rank: int, gen: int, coordinator: str,
              fresh_roster: bool = False) -> Host:
        port = self._free_port()
        out = os.path.join(self.dir, f"out_r{rank}_g{gen}.bin")
        log = os.path.join(self.dir, f"log_r{rank}_g{gen}.txt")
        roster = os.path.join(
            self.dir,
            f"roster_r{rank}{f'_g{gen}' if fresh_roster else ''}.json")
        env = {k: v for k, v in os.environ.items()
               if k not in ("FLOWGGER_FAULTS", "FLOWGGER_PARTITION_PEER")}
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"  # host-plane workers, never the chip
        with open(log, "ab") as logfd:
            proc = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--worker",
                 "--rank", str(rank), "--hosts", str(self.n),
                 "--port", str(port), "--coordinator", coordinator,
                 "--roster", roster, "--out", out, "--gen", str(gen)],
                env=env, cwd=_REPO, stdout=logfd,
                stderr=subprocess.STDOUT)
        host = Host(rank, gen, port, proc, out, log, roster)
        self.hosts[rank] = host
        self.log(f"spawned rank {rank} gen {gen} (port {port}, "
                 f"coordinator {coordinator})")
        return host

    def sigterm(self, host: Host, wait_s: float = 20.0) -> None:
        host.proc.send_signal(signal.SIGTERM)
        try:
            rc = host.proc.wait(timeout=wait_s)
        except subprocess.TimeoutExpired:
            host.proc.kill()
            raise ChaosError(
                f"rank {host.rank}: SIGTERM drain never finished "
                f"({self._tail(host)})")
        if rc != 0:
            raise ChaosError(f"rank {host.rank}: drain exit {rc} "
                             f"({self._tail(host)})")

    def _tail(self, host: Host, n: int = 12) -> str:
        try:
            with open(host.log_path, "rb") as fd:
                return b"\n".join(
                    fd.read().splitlines()[-n:]).decode(errors="replace")
        except OSError:
            return "<no log>"

    # -- health polling ----------------------------------------------------
    def health(self, host: Host):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{host.port}/healthz",
                    timeout=2) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            try:
                return e.code, json.loads(e.read())
            except (ValueError, OSError):
                return e.code, None
        except (OSError, ValueError):
            return None, None

    def post_fault(self, host: Host, site: str, spec: str) -> None:
        body = json.dumps({"site": site, "spec": spec}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{host.port}/fault", data=body,
            method="POST", headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=5) as resp:
            doc = json.loads(resp.read())
            if not doc.get("ok"):
                raise ChaosError(f"fault arm refused: {doc}")
        self.log(f"armed [{site}={spec}] on rank {host.rank}")

    # -- convergence predicate --------------------------------------------
    def _converged_view(self, doc, live_ranks) -> bool:
        if doc is None:
            return False
        fleet = doc.get("fleet", {})
        peers = {p["rank"]: p for p in fleet.get("peers", [])}
        if not all(r in peers and peers[r]["state"] == "active"
                   for r in live_ranks):
            return False
        # no ghost actives: everything not live must be non-routable
        for r, p in peers.items():
            if r not in live_ranks and p["state"] in ("joining", "active"):
                return False
        rdv = fleet.get("rendezvous", {})
        if rdv.get("rank") != min(live_ranks):
            return False
        shares = fleet.get("shares", {})
        if set(shares) != {str(r) for r in live_ranks}:
            return False
        if abs(sum(shares.values()) - 1.0) > 0.01:
            return False
        return True

    def wait_converged(self, note: str, deadline_s: float = None) -> float:
        """Block until EVERY live host's health document shows all live
        hosts active, one agreed rendezvous (the lowest live rank), and
        shares summing to 1 over exactly the live set.  Returns the
        seconds it took."""
        deadline_s = self.window if deadline_s is None else deadline_s
        live = sorted(self.hosts)
        t0 = time.monotonic()
        last_bad = "no poll yet"
        while time.monotonic() - t0 < deadline_s:
            oks = 0
            for rank in live:
                status, doc = self.health(self.hosts[rank])
                if status == 200 and self._converged_view(doc, live):
                    oks += 1
                else:
                    last_bad = (f"rank {rank}: status={status} "
                                f"doc={'yes' if doc else 'no'}")
            if oks == len(live):
                dt = time.monotonic() - t0
                self.log(f"reconverged after {note} in {dt:.1f}s "
                         f"({len(live)} hosts, rendezvous rank "
                         f"{min(live)})")
                return dt
            time.sleep(0.1)
        tails = "\n".join(f"-- rank {r}:\n{self._tail(self.hosts[r])}"
                          for r in live)
        raise ChaosError(
            f"fleet failed to reconverge within {deadline_s:.0f}s after "
            f"{note} (last: {last_bad})\n{tails}")

    def wait_dead(self, host: Host, expect_sig: bool) -> None:
        try:
            rc = host.proc.wait(timeout=self.window)
        except subprocess.TimeoutExpired:
            host.proc.kill()
            raise ChaosError(f"rank {host.rank} never died "
                             f"({self._tail(host)})")
        if expect_sig and rc != -9:
            raise ChaosError(
                f"rank {host.rank}: expected SIGKILL death, rc={rc} "
                f"({self._tail(host)})")

    # -- integrity ---------------------------------------------------------
    def _reference_prefix(self, rank: int, gen: int, length: int) -> bytes:
        """The first ``length`` bytes of (rank, gen)'s reference
        stream, built incrementally and cached across checks."""
        if self._encode is None:
            sys.path.insert(0, _REPO)
            from flowgger_tpu.config import Config
            from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
            from flowgger_tpu.encoders.gelf import GelfEncoder
            from flowgger_tpu.mergers import LineMerger

            decoder, encoder, merger = (RFC5424Decoder(),
                                        GelfEncoder(Config.from_string("")),
                                        LineMerger())
            self._encode = lambda r, g, i: merger.frame(
                encoder.encode(decoder.decode(_line(r, g, i))))
        key = (rank, gen)
        buf = self._ref_cache.get(key, b"")
        i = self._ref_idx.get(key, 0)
        while len(buf) < length:
            buf += self._encode(rank, gen, i)
            i += 1
        self._ref_cache[key], self._ref_idx[key] = buf, i
        return buf[:length]

    def check_outputs(self, require_growth: bool = True) -> None:
        """No lost lines: every live host's fsynced output is a clean
        prefix of its reference stream — and still growing (ingest
        survived the event)."""
        for host in self.hosts.values():
            data = open(host.out_path, "rb").read() \
                if os.path.exists(host.out_path) else b""
            want = self._reference_prefix(host.rank, host.gen, len(data))
            if data != want:
                raise ChaosError(
                    f"rank {host.rank} gen {host.gen}: output is NOT a "
                    f"clean prefix of its reference stream "
                    f"({len(data)} bytes)")
            if require_growth and len(data) <= host.last_size:
                raise ChaosError(
                    f"rank {host.rank}: ingest stalled at "
                    f"{len(data)} bytes")
            host.last_size = len(data)
        self.log("output integrity: every stream is a clean, growing "
                 "prefix")

    def check_file_prefix(self, host: Host) -> None:
        """A dead host's fsynced bytes must still be an uncorrupted
        prefix (possibly cut mid-record by the kill)."""
        data = open(host.out_path, "rb").read() \
            if os.path.exists(host.out_path) else b""
        want = self._reference_prefix(host.rank, host.gen, len(data))
        if data != want:
            raise ChaosError(
                f"dead rank {host.rank} gen {host.gen}: pre-kill output "
                "is not a clean prefix of its reference stream")

    def journal_counts(self, host: Host) -> dict:
        _, doc = self.health(host)
        if doc is None:
            return {}
        return doc.get("events", {}).get("counts", {})

    def metrics(self, host: Host) -> dict:
        _, doc = self.health(host)
        return (doc or {}).get("metrics", {})

    def rendezvous_addr(self) -> str:
        for host in self.hosts.values():
            _, doc = self.health(host)
            if doc is not None:
                rdv = doc.get("fleet", {}).get("rendezvous", {})
                if rdv.get("rank", -1) >= 0:
                    return rdv["addr"]
        raise ChaosError("no live host could name a rendezvous")

    def require_journaled(self, reason: str) -> None:
        """Some live host must have journaled the typed event."""
        seen = {r: self.journal_counts(h).get(reason, 0)
                for r, h in self.hosts.items()}
        if not any(seen.values()):
            raise ChaosError(
                f"no live host journaled a {reason} event ({seen})")
        self.log(f"journal: {reason} observed ({seen})")


# -- the drills --------------------------------------------------------

def drill_host_kill(h: Harness) -> float:
    """SIGKILL a non-rendezvous host mid-stream; survivors reconverge
    and rebalance; the SAME host (next generation, same roster
    journal) boots one incarnation later and is re-admitted —
    bootstrapping from its durable roster, not the (possibly dead)
    configured coordinator."""
    victim_rank = max(r for r in h.hosts
                      if r != min(h.hosts))  # keep the rendezvous
    victim = h.hosts[victim_rank]
    h.post_fault(victim, "host_kill", "once:1")
    h.wait_dead(victim, expect_sig=True)
    t0 = time.monotonic()
    del h.hosts[victim_rank]
    h.check_file_prefix(victim)
    dt = h.wait_converged(f"host_kill of rank {victim_rank}")
    h.require_journaled("fleet_rebalance")
    # replacement: same rank, same roster journal, dead-end
    # coordinator ("none") — it MUST bootstrap via the persisted roster
    h.spawn(victim_rank, victim.gen + 1, "none")
    h.wait_converged(f"rank {victim_rank} replacement join")
    replacement = h.hosts[victim_rank]
    if not h.journal_counts(replacement).get("roster_restore"):
        raise ChaosError("replacement joined without a roster_restore "
                         "event — did it really use the journal?")
    return dt if dt > 0 else time.monotonic() - t0


def drill_coordinator_kill(h: Harness) -> float:
    """SIGKILL the host holding the rendezvous (the self-selecting
    ``coordinator_kill`` site); survivors elect the next-lowest active
    rank as fallback, and a BRAND-NEW host (fresh journal) joins
    through the fallback rendezvous — the ISSUE 14 acceptance drill."""
    coord_rank = min(h.hosts)
    coord = h.hosts[coord_rank]
    # armed only on the host that IS the rendezvous: arming fleet-wide
    # would cascade — each successor rendezvous would fire the site on
    # its own first tick as coordinator
    h.post_fault(coord, "coordinator_kill", "once:1")
    h.wait_dead(coord, expect_sig=True)
    t0 = time.monotonic()
    del h.hosts[coord_rank]
    h.check_file_prefix(coord)
    dt = h.wait_converged(f"coordinator_kill of rank {coord_rank}")
    h.require_journaled("rendezvous_failover")
    h.require_journaled("fleet_rebalance")
    # a brand-new joiner (fresh roster journal) admitted by the
    # FALLBACK rendezvous — the coordinator everybody was configured
    # with is dead
    fallback = h.rendezvous_addr()
    h.spawn(coord_rank, coord.gen + 1, fallback, fresh_roster=True)
    h.wait_converged(
        f"new joiner rank {coord_rank} via fallback {fallback}")
    return dt if dt > 0 else time.monotonic() - t0


def drill_peer_partition(h: Harness) -> float:
    """Cut one non-rendezvous host off (both directions) long enough
    to be seen suspect, then heal; suspicion must cure with no
    eviction needed and no lost lines."""
    target_rank = max(r for r in h.hosts if r != min(h.hosts))
    target = h.hosts[target_rank]
    h.post_fault(target, "peer_partition", "every:1")
    deadline = time.monotonic() + h.window
    seen = False
    while time.monotonic() < deadline:
        for rank, host in h.hosts.items():
            if rank == target_rank:
                continue
            _, doc = h.health(host)
            if doc is None:
                continue
            peers = {p["rank"]: p["state"]
                     for p in doc["fleet"].get("peers", [])}
            if peers.get(target_rank) == "suspect":
                seen = True
        if seen:
            break
        time.sleep(0.05)
    if not seen:
        raise ChaosError(
            f"partitioned rank {target_rank} was never seen suspect")
    h.log(f"rank {target_rank} seen suspect under partition; healing")
    h.post_fault(target, "peer_partition", "off")
    return h.wait_converged(f"partition heal of rank {target_rank}")


def drill_roster_corrupt(h: Harness) -> float:
    """Corrupt a host's roster journal via the ``roster_corrupt`` site
    (its drain-time saves write a truncated file), drain it out, and
    prove its replacement boots CLEANLY off the corrupt journal: the
    load error is counted, the plain coordinator walk takes over, the
    fleet reconverges."""
    target_rank = max(r for r in h.hosts if r != min(h.hosts))
    target = h.hosts[target_rank]
    h.post_fault(target, "roster_corrupt", "every:1")
    # voluntary drain: mark_draining/mark_departed both re-derive and
    # journal the roster, so the armed site corrupts the file on disk
    h.sigterm(target)
    t0 = time.monotonic()
    del h.hosts[target_rank]
    dt = h.wait_converged(f"drain of rank {target_rank}")
    # journal really is corrupt?
    try:
        json.loads(open(target.roster_path, "rb").read())
        raise ChaosError("roster_corrupt armed but the journal still "
                         "parses — the site never fired")
    except ValueError:
        pass
    rdv = h.rendezvous_addr()
    h.spawn(target_rank, target.gen + 1, rdv)
    h.wait_converged(f"rank {target_rank} rejoin off a corrupt journal")
    replacement = h.hosts[target_rank]
    if not h.metrics(replacement).get("fleet_roster_load_errors"):
        raise ChaosError("corrupt journal was not counted as a "
                         "fleet_roster_load_errors load")
    if h.journal_counts(replacement).get("roster_restore"):
        raise ChaosError("corrupt journal must NOT produce a "
                         "roster_restore event")
    return dt if dt > 0 else time.monotonic() - t0


DRILLS = {
    "host_kill": drill_host_kill,
    "coordinator_kill": drill_coordinator_kill,
    "peer_partition": drill_peer_partition,
    "roster_corrupt": drill_roster_corrupt,
}


# ----------------------------------------------- durability (WAL) drills
#
# Socket-free, single-host, three-phase crash drill for the zero-loss
# ingestion tier (ISSUE 16): SIGKILL a worker mid-spill, SIGKILL its
# successor mid-replay, then let a third worker finish — and assert
# byte-exact no-loss: every line the WAL durably owned at the first
# kill appears in the final sink output at least once, nothing foreign
# appears, and the at-least-once window duplicates each line at most
# once (one crash mid-flight = one possible redelivery).
#
#   python tools/chaos.py --durability [--kill-records 25] [--json]

DUR_CHUNK_LINES = 8          # lines per spilled record
DUR_REPLAY_PAUSE_MS = 120    # phase-B pacing so the kill lands mid-replay


def _dur_line(i: int) -> bytes:
    """Deterministic rfc5424 line ``i`` — PassthroughEncoder + LineMerger
    make the sink output byte-identical to this input."""
    return (f"<{(3 * i) % 192}>1 2023-09-20T12:35:45.{i % 1000:03d}Z "
            f"durhost app{i % 5} {i % 1000} MSGID "
            f'[ex@32473 k="{i}"] durability line {i}').encode()


def _wal_lines(spill_dir: str) -> list:
    """Every line the WAL durably owns right now (clean-prefix scan:
    a torn tail record was never durable, so it is not owed)."""
    if not os.path.isdir(spill_dir):
        return []
    sys.path.insert(0, _REPO)
    from flowgger_tpu.durability import list_segments, read_segment

    lines = []
    for _seq, path in list_segments(spill_dir):
        records, _clean = read_segment(path)
        for hdr, body in records:
            for s, ln in zip(hdr["starts"], hdr["lens"]):
                lines.append(bytes(body[s:s + ln]))
    return lines


def durability_worker_main(args) -> int:
    """One durability drill worker: ``--phase spill`` streams lines
    into the WAL forever (the harness SIGKILLs it); ``--phase replay``
    replays the WAL through a real FileOutput sink, optionally paced
    (``--replay-pause-ms``) so the harness can SIGKILL it mid-replay."""
    sys.path.insert(0, _REPO)
    from flowgger_tpu.config import Config
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.durability.manager import DurabilityManager
    from flowgger_tpu.encoders.passthrough import PassthroughEncoder
    from flowgger_tpu.mergers import LineMerger
    from flowgger_tpu.tpu.batch import BatchHandler

    cfg = Config.from_string("")

    def make_handler(tx, mgr, merger):
        h = BatchHandler(tx, RFC5424Decoder(cfg), PassthroughEncoder(cfg),
                         cfg, fmt="rfc5424", start_timer=False,
                         merger=merger)
        h.ingest_sep = b"\n"
        h.ingest_strip_cr = True
        h.durability = mgr
        return h

    if args.phase == "spill":
        mgr = DurabilityManager("spill", args.spill_dir,
                                start_watchdog=False)

        class FullQueue:
            """Pinned past the watermark: every batch must spill."""

            @staticmethod
            def put(item):
                raise AssertionError("a batch leaked past the spill tier")

            @staticmethod
            def fill_fraction():
                return 1.0

        tx = FullQueue()
        mgr.attach_queue(tx)
        h = make_handler(tx, mgr, LineMerger(cfg))
        i = 0
        while True:  # the harness SIGKILLs us mid-spill
            region = b"".join(_dur_line(i + j) + b"\n"
                              for j in range(DUR_CHUNK_LINES))
            h.ingest_chunk(region)
            h.flush()
            i += DUR_CHUNK_LINES

    # -- phase == "replay" -------------------------------------------------
    from flowgger_tpu.obs.events import journal
    from flowgger_tpu.outputs import SHUTDOWN
    from flowgger_tpu.outputs.file_output import FileOutput
    from flowgger_tpu.utils.bounded_queue import PolicyQueue

    out_cfg = Config.from_string(
        f'[output]\nfile_path = "{args.out}"\n')
    merger = LineMerger(cfg)
    tx = PolicyQueue(maxsize=10_000)
    output = FileOutput(out_cfg)
    thread = output.start(tx, merger)
    mgr = DurabilityManager("spill", args.spill_dir, start_watchdog=False)
    mgr.attach_queue(tx)
    h = make_handler(tx, mgr, merger)
    total = 0
    while mgr.backlog():
        total += h.replay_spilled(limit=1)
        if args.replay_pause_ms:
            time.sleep(args.replay_pause_ms / 1000.0)
    # replay enqueued everything; now wait for the sink acks to settle
    # the persisted cursor (outputs ack after the flushed write)
    deadline = time.monotonic() + 30
    while mgr.unacked() and time.monotonic() < deadline:
        time.sleep(0.02)
    tx.put(SHUTDOWN)
    thread.join(timeout=20)
    mgr.stop()
    print(json.dumps({
        "phase": "replay", "replayed_lines": total,
        "unacked": mgr.unacked(),
        "replay_complete": journal.counts().get("replay_complete", 0),
        "stats": mgr.backlog_stats()}), flush=True)
    return 0 if mgr.unacked() == 0 else 1


def durability_main(args) -> int:
    """Three-phase kill-mid-spill / kill-mid-replay acceptance drill."""
    workdir = args.dir or tempfile.mkdtemp(prefix="flowgger_dur_")
    os.makedirs(workdir, exist_ok=True)
    spill_dir = os.path.join(workdir, "wal")
    out_path = os.path.join(workdir, "sink.out")
    report = {"metric": "durability_chaos", "ok": False, "phases": []}
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the drills exercise the host planes: their processes are pinned
    # to the CPU whatever the caller exports, so none asks for a chip
    env["JAX_PLATFORMS"] = "cpu"
    t_run = time.monotonic()

    def log(msg):
        if not args.json or args.verbose:
            print(f"chaos-durability: {msg}", file=sys.stderr, flush=True)

    def spawn(phase, pause_ms=0, tag=""):
        logf = open(os.path.join(workdir, f"log_{phase}{tag}.txt"), "ab")
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--durability-worker", "--phase", phase,
             "--spill-dir", spill_dir, "--out", out_path,
             "--replay-pause-ms", str(pause_ms)],
            env=env, cwd=_REPO, stdout=subprocess.PIPE, stderr=logf)

    def out_lines():
        if not os.path.exists(out_path):
            return []
        with open(out_path, "rb") as fd:
            return [ln for ln in fd.read().split(b"\n") if ln]

    proc = None
    try:
        # phase A: spill under a pinned-full queue, SIGKILL mid-spill
        proc = spawn("spill")
        deadline = time.monotonic() + args.window
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise ChaosError(
                    f"spill worker exited early (rc={proc.returncode})")
            if len(_wal_lines(spill_dir)) >= args.kill_records \
                    * DUR_CHUNK_LINES:
                break
            time.sleep(0.02)
        else:
            raise ChaosError("spill worker never reached the kill point")
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait()
        expected = _wal_lines(spill_dir)
        if len(expected) < DUR_CHUNK_LINES:
            raise ChaosError("WAL owned almost nothing at the kill")
        log(f"phase A: SIGKILL mid-spill; WAL owns {len(expected)} "
            f"line(s) across {len(os.listdir(spill_dir))} file(s)")
        report["phases"].append({"phase": "kill_mid_spill",
                                 "wal_lines": len(expected)})

        # phase B: paced replay through a real FileOutput, SIGKILL
        # once output proves the replay is mid-flight
        proc = spawn("replay", pause_ms=DUR_REPLAY_PAUSE_MS, tag="_b")
        deadline = time.monotonic() + args.window
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise ChaosError(
                    "replay worker finished before the mid-replay kill "
                    f"(rc={proc.returncode}) — pacing too fast")
            n = len(out_lines())
            if 0 < n < len(expected):
                break
            time.sleep(0.01)
        else:
            raise ChaosError("replay worker never emitted mid-replay")
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait()
        mid = len(out_lines())
        log(f"phase B: SIGKILL mid-replay after {mid} line(s) reached "
            "the sink")
        report["phases"].append({"phase": "kill_mid_replay",
                                 "lines_at_kill": mid})

        # phase C: a fresh worker finishes the replay and drains clean
        proc = spawn("replay", pause_ms=0, tag="_c")
        try:
            stdout, _ = proc.communicate(timeout=args.window)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise ChaosError("phase C replay never finished")
        if proc.returncode != 0:
            raise ChaosError(
                f"phase C exited {proc.returncode} (cursor not settled)")
        doc = json.loads(stdout.splitlines()[-1])
        if not doc.get("replay_complete"):
            raise ChaosError("phase C never journaled replay_complete")
        if _wal_lines(spill_dir):
            raise ChaosError("fully-acked WAL still holds records")
        report["phases"].append({"phase": "replay_to_completion",
                                 **{k: doc[k] for k in
                                    ("replayed_lines", "replay_complete")}})

        # byte-exact no-loss: every owed line >= 1x, nothing foreign,
        # each line duplicated at most once (one crash window)
        final = out_lines()
        counts: dict = {}
        for ln in final:
            counts[ln] = counts.get(ln, 0) + 1
        owed = set(expected)
        missing = [ln for ln in owed if ln not in counts]
        foreign = [ln for ln in counts if ln not in owed]
        over = {ln: c for ln, c in counts.items() if c > 2}
        if missing:
            raise ChaosError(
                f"LOST {len(missing)} line(s), e.g. {missing[0]!r}")
        if foreign:
            raise ChaosError(
                f"{len(foreign)} foreign line(s) in the sink, "
                f"e.g. {foreign[0]!r}")
        if over:
            ln, c = next(iter(over.items()))
            raise ChaosError(
                f"{len(over)} line(s) delivered >2x (e.g. {c}x {ln!r}) "
                "— dispatch-once-per-process is broken")
        dups = sum(c - 1 for c in counts.values())
        log(f"no-loss held: {len(owed)} owed, {len(final)} delivered, "
            f"{dups} duplicate(s) inside the at-least-once window")
        report.update(ok=True, owed_lines=len(owed),
                      delivered_lines=len(final), duplicates=dups)
    except ChaosError as e:
        report["error"] = str(e)
        print(f"chaos-durability: FAILED: {e}", file=sys.stderr)
    except Exception as e:  # harness bug: report it, don't hang CI
        import traceback

        traceback.print_exc()
        report["error"] = f"harness error: {e!r}"
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
    report["wall_s"] = round(time.monotonic() - t_run, 1)
    if not args.keep_dir and report["ok"]:
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)
    else:
        report["dir"] = workdir
    print(json.dumps(report))
    return 0 if report["ok"] else 1


# -- the control-loop drill (--control) --------------------------------------

def control_main(args) -> int:
    """In-process closed-loop drills (``--control``):

    Drill A — flood-to-tighten with a calm bystander.  A rate-limited
    noisy tenant floods 10x over its rate while a calm tenant streams
    steadily; a real SloEngine (short windows) feeds the control
    plane's admission loop.  Asserts the flooder's bucket rate is
    controller-tightened within the reaction bound, the
    ``admission_tighten`` event journals, the calm tenant's delivered
    bytes are identical to a no-flood reference run, and the calm
    tenant's own SLO never burns.

    Drill B — share decay beats the breaker.  A degrading device feed
    (journaled ``device_error`` events + slow ``DecodeBreaker``
    failures) pressures the share loop; the decayed capacity weight is
    gossiped to a peer Membership via the ordinary heartbeat fields.
    Asserts the peer's view of this host's traffic share drops BEFORE
    the breaker reaches OPEN — the fleet sheds load off a degrading
    host while it can still serve.
    """
    sys.path.insert(0, _REPO)
    os.environ["JAX_PLATFORMS"] = "cpu"  # a host-plane drill, never the chip

    from flowgger_tpu import tenancy
    from flowgger_tpu.config import Config
    from flowgger_tpu.control import ControlPlane, ControlSpec
    from flowgger_tpu.fleet.membership import Membership
    from flowgger_tpu.obs import events as obs_events
    from flowgger_tpu.obs.slo import Objective, SloEngine
    from flowgger_tpu.tenancy.admission import AdmissionHandler
    from flowgger_tpu.tenancy.registry import TenantRegistry
    from flowgger_tpu.tpu.breaker import OPEN, DecodeBreaker
    from flowgger_tpu.utils.metrics import registry as metrics

    report = {"metric": "control_chaos", "ok": False, "drills": []}
    t_run = time.monotonic()
    reaction_bound_s = 5.0

    def log(msg):
        if not args.json or args.verbose:
            print(f"chaos-control: {msg}", file=sys.stderr, flush=True)

    def fresh():
        metrics.reset()
        obs_events.journal.reset()
        obs_events.journal.configure()
        tenancy.set_current(None)

    class _Capture:
        quiet_empty = False
        bare_errors = False
        ingest_sep = b"\n"
        ingest_strip_cr = True

        def __init__(self):
            self.chunks = []

        def ingest_chunk(self, chunk):
            self.chunks.append(chunk)

        def flush(self):
            pass

    def calm_chunk(i):
        return b"".join(b"<13>calm steady line %d.%d\n" % (i, j)
                        for j in range(4))

    CALM_CHUNKS = 200

    try:
        # ---------------- drill A: flood tighten, calm untouched -----
        fresh()
        reg = TenantRegistry.from_config(Config.from_string(
            "[tenants.noisy]\nrate = 2000\n[tenants.calm]\n"))
        reference = [calm_chunk(i) for i in range(CALM_CHUNKS)]

        eng = SloEngine()
        eng.configure([
            Objective(name="noisy_sheds", kind="events",
                      metric="events_tenant_shed", max_per_sec=10.0,
                      tenant="noisy", fast_window_s=0.4,
                      slow_window_s=1.2),
            Objective(name="calm_floor", kind="throughput",
                      metric="tenant_calm_lines", floor_per_sec=50.0,
                      objective=0.9, tenant="calm", fast_window_s=0.4,
                      slow_window_s=1.2),
        ], interval_s=0)
        plane = ControlPlane(ControlSpec(admission=True, interval_s=0),
                             tenants=reg, burn_source=eng.burn_states)
        noisy = reg.state("noisy")
        calm_sink = _Capture()
        calm = AdmissionHandler(calm_sink, reg.state("calm"))

        stop = threading.Event()

        def flood():
            # ~10x the admitted rate, sustained for the whole drill
            while not stop.is_set():
                noisy.admit(64, 4096)
                time.sleep(0.002)

        calm_fed = threading.Event()

        def feed_calm():
            for i in range(CALM_CHUNKS):
                if stop.is_set():
                    return
                calm.ingest_chunk(calm_chunk(i))
                time.sleep(0.01)
            calm_fed.set()

        flooder = threading.Thread(target=flood, daemon=True)
        feeder = threading.Thread(target=feed_calm, daemon=True)
        t0 = time.monotonic()
        flooder.start()
        feeder.start()
        reaction_s = None
        calm_burned = False
        deadline = t0 + args.window
        while time.monotonic() < deadline:
            eng.tick()
            plane.tick()
            for b in eng.burn_states():
                # judge the calm SLO only while the feed is live — the
                # instant after the last chunk its throughput is 0 by
                # construction, which is not the flood's fault
                if b["tenant"] == "calm" and b["burning"] \
                        and not calm_fed.is_set():
                    calm_burned = True
            if reaction_s is None and noisy.rate_factor < 1.0:
                reaction_s = time.monotonic() - t0
                log(f"drill A: noisy tightened to "
                    f"{noisy.rate_factor:.0%} after {reaction_s:.2f}s")
            if reaction_s is not None and calm_fed.is_set():
                break
            time.sleep(0.1)
        stop.set()
        flooder.join(timeout=2)
        feeder.join(timeout=5)
        eng.stop()
        # the counter mirror, not the ring: the sustained shed flood
        # evicts older events from the bounded journal, but every emit
        # also bumps events_<reason> in the registry
        tighten_events = int(metrics.get("events_admission_tighten"))
        if reaction_s is None:
            raise ChaosError(
                "drill A: the flooding tenant was never tightened")
        if reaction_s >= reaction_bound_s:
            raise ChaosError(
                f"drill A: tighten took {reaction_s:.2f}s "
                f"(bound {reaction_bound_s}s)")
        if tighten_events < 1:
            raise ChaosError(
                "drill A: no admission_tighten event journaled")
        if not calm_fed.is_set():
            raise ChaosError("drill A: calm feed never completed")
        if calm_sink.chunks != reference:
            raise ChaosError(
                "drill A: the calm tenant's bytes diverged under the "
                "flood — isolation broken")
        if calm_burned:
            raise ChaosError(
                "drill A: the calm tenant's SLO burned under the flood")
        if reg.state("calm").rate_factor != 1.0:
            raise ChaosError(
                "drill A: the controller touched the calm tenant")
        log(f"drill A held: tightened {noisy.rate_factor:.0%} in "
            f"{reaction_s:.2f}s; calm byte-identical "
            f"({len(reference)} chunks), calm SLO green")
        report["drills"].append({
            "drill": "flood_tighten", "reaction_s": round(reaction_s, 2),
            "noisy_factor": round(noisy.rate_factor, 3),
            "tighten_events": tighten_events,
            "calm_chunks": len(reference),
            "calm_byte_identical": True, "calm_slo_green": True,
            "ok": True})

        # ---------------- drill B: share decay beats the breaker -----
        fresh()
        local = Membership(rank=0, addr="127.0.0.1:9001", capacity=2.0)
        local.activate()
        local.note_heartbeat(1, "127.0.0.1:9002", capacity=2.0)
        peer = Membership(rank=1, addr="127.0.0.1:9002", capacity=2.0)
        peer.activate()
        peer.note_heartbeat(0, "127.0.0.1:9001", capacity=2.0)
        base_share = peer.shares()[0]

        eng2 = SloEngine()
        eng2.configure([Objective(
            name="host_device", kind="events",
            metric="events_device_error", max_per_sec=2.0,
            fast_window_s=0.4, slow_window_s=1.2)], interval_s=0)
        fleet = type("F", (), {"capacity": 2.0, "membership": local})()
        plane2 = ControlPlane(ControlSpec(share=True, interval_s=0),
                              fleet=fleet, burn_source=eng2.burn_states)
        # 60 consecutive failures at 20/s = the breaker trips ~3s in;
        # the SLO windows (0.4s/1.2s) see the same feed burning within
        # ~1.3s — the share loop must win that race
        breaker = DecodeBreaker(failures=60, cooldown_ms=60_000)

        stop2 = threading.Event()

        def degrade():
            # a slowly failing device: each failure journals (the burn
            # signal) and feeds the breaker ladder (the trip signal)
            while not stop2.is_set():
                obs_events.emit("chaos", "device_error",
                                detail="injected device failure")
                breaker.record_failure(RuntimeError("injected"))
                time.sleep(0.05)

        degrader = threading.Thread(target=degrade, daemon=True)
        t0 = time.monotonic()
        degrader.start()
        t_decay = t_open = None
        deadline = t0 + args.window
        while time.monotonic() < deadline:
            eng2.tick()
            plane2.tick()
            # the decayed weight rides the ordinary heartbeat fields
            me = local.roster()[0]
            peer.note_heartbeat(0, me["addr"], state=me["state"],
                                capacity=me["capacity"])
            if t_decay is None and \
                    peer.shares().get(0, 0.0) < base_share - 0.01:
                t_decay = time.monotonic() - t0
                if breaker.state == OPEN:
                    raise ChaosError(
                        "drill B: the breaker tripped before the share "
                        "decayed — feedback too slow")
                log(f"drill B: peer sees share "
                    f"{peer.shares()[0]:.1%} (was {base_share:.1%}) "
                    f"after {t_decay:.2f}s; breaker still "
                    f"{breaker.state}")
            if breaker.state == OPEN:
                t_open = time.monotonic() - t0
                break
            time.sleep(0.1)
        stop2.set()
        degrader.join(timeout=2)
        eng2.stop()
        if t_decay is None:
            raise ChaosError(
                "drill B: the peer never saw the share decay")
        if t_open is None:
            raise ChaosError(
                "drill B: the breaker never tripped — the failure feed "
                "was not degrading for real")
        if not (t_decay < t_open):
            raise ChaosError(
                f"drill B: decay at {t_decay:.2f}s did not precede the "
                f"breaker trip at {t_open:.2f}s")
        decay_events = int(metrics.get("events_share_decay"))
        if decay_events < 1:
            raise ChaosError("drill B: no share_decay event journaled")
        log(f"drill B held: share decayed at {t_decay:.2f}s, breaker "
            f"opened at {t_open:.2f}s")
        report["drills"].append({
            "drill": "share_decay_before_breaker",
            "decay_s": round(t_decay, 2), "breaker_open_s": round(t_open, 2),
            "peer_share": round(peer.shares().get(0, 0.0), 4),
            "base_share": round(base_share, 4),
            "share_decay_events": decay_events, "ok": True})
        report["ok"] = True
    except ChaosError as e:
        report["error"] = str(e)
        print(f"chaos-control: FAILED: {e}", file=sys.stderr)
    except Exception as e:  # harness bug: report it, don't hang CI
        import traceback

        traceback.print_exc()
        report["error"] = f"harness error: {e!r}"
    report["wall_s"] = round(time.monotonic() - t_run, 1)
    print(json.dumps(report))
    return 0 if report["ok"] else 1


def harness_main(args) -> int:
    sites = [s.strip() for s in args.sites.split(",") if s.strip()]
    unknown = [s for s in sites if s not in DRILLS]
    if unknown:
        print(f"chaos: unknown sites {unknown} "
              f"(known: {', '.join(DRILLS)})", file=sys.stderr)
        return 2
    workdir = args.dir or tempfile.mkdtemp(prefix="flowgger_chaos_")
    os.makedirs(workdir, exist_ok=True)
    h = Harness(args.hosts, args.window, workdir,
                verbose=not args.json or args.verbose)
    report = {"metric": "chaos", "hosts": args.hosts,
              "events": [], "ok": False}
    t_run = time.monotonic()

    def _terminated(signum, _frame):
        # ci.sh's `timeout` sends SIGTERM: raise through the drill so
        # the finally: below kills the worker fleet instead of
        # orphaning it (SIGKILL can't be caught — the workers' own
        # parent-gone check covers that path)
        raise ChaosError(f"harness terminated by signal {signum}")

    signal.signal(signal.SIGTERM, _terminated)
    signal.signal(signal.SIGINT, _terminated)
    try:
        # boot the initial fleet: rank 0 is the configured coordinator
        first = h.spawn(0, 0, "none")
        coord_addr = f"127.0.0.1:{first.port}"
        for rank in range(1, args.hosts):
            h.spawn(rank, 0, coord_addr)
        h.wait_converged("initial boot")
        h.check_outputs(require_growth=False)
        time.sleep(0.5)  # one ingest beat so growth checks mean something
        for k in range(args.events):
            site = sites[k % len(sites)]
            h.log(f"=== event {k + 1}/{args.events}: {site} ===")
            dt = DRILLS[site](h)
            h.check_outputs()
            report["events"].append(
                {"site": site, "reconverge_s": round(dt, 2), "ok": True})
        # clean teardown: every survivor drains byte-cleanly
        for rank in sorted(h.hosts):
            h.sigterm(h.hosts[rank])
        for host in h.hosts.values():
            data = open(host.out_path, "rb").read()
            want = h._reference_prefix(host.rank, host.gen, len(data))
            if data != want:
                raise ChaosError(
                    f"rank {host.rank}: post-drain output diverged")
        report["ok"] = True
    except ChaosError as e:
        report["error"] = str(e)
        print(f"chaos: FAILED: {e}", file=sys.stderr)
    except Exception as e:  # harness bug: report it, don't hang CI
        import traceback

        traceback.print_exc()
        report["error"] = f"harness error: {e!r}"
    finally:
        for host in h.hosts.values():
            if host.proc.poll() is None:
                host.proc.kill()
    recs = [e["reconverge_s"] for e in report["events"]]
    report["max_reconverge_s"] = max(recs) if recs else None
    report["wall_s"] = round(time.monotonic() - t_run, 1)
    # the heartbeat-ladder bound every reconvergence must respect:
    # eviction + departure grace + one poll slack
    report["ladder_bound_s"] = round((EVICT_MS + DEPART_MS) / 1000 + 1, 1)
    if not args.keep_dir and report["ok"]:
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)
    else:
        report["dir"] = workdir
    print(json.dumps(report))
    return 0 if report["ok"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chaos", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--worker", action="store_true",
                    help="internal: run one fleet host")
    ap.add_argument("--durability", action="store_true",
                    help="run the kill-mid-spill / kill-mid-replay WAL "
                         "drill instead of the fleet drills")
    ap.add_argument("--durability-worker", action="store_true",
                    help="internal: run one durability drill worker")
    ap.add_argument("--control", action="store_true",
                    help="run the closed-loop control drills (flood "
                         "tighten + share decay) instead of the fleet "
                         "drills")
    ap.add_argument("--phase", default="spill",
                    choices=("spill", "replay"))
    ap.add_argument("--spill-dir", default="wal")
    ap.add_argument("--replay-pause-ms", type=int, default=0)
    ap.add_argument("--kill-records", type=int, default=25,
                    help="spilled records on disk before the phase-A "
                         "SIGKILL")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--hosts", type=int, default=3)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--coordinator", default="none")
    ap.add_argument("--roster", default="none")
    ap.add_argument("--out", default="chaos_out.bin")
    ap.add_argument("--gen", type=int, default=0)
    ap.add_argument("--events", type=int, default=4,
                    help="fault drills to run (cycled through --sites)")
    ap.add_argument("--window", type=float, default=60.0,
                    help="per-step reconvergence deadline, seconds")
    ap.add_argument("--sites", default=",".join(DEFAULT_SITES))
    ap.add_argument("--json", action="store_true",
                    help="quiet; one machine-readable report line")
    ap.add_argument("--verbose", action="store_true")
    ap.add_argument("--dir", default=None,
                    help="work dir (default: fresh temp dir)")
    ap.add_argument("--keep-dir", action="store_true")
    args = ap.parse_args(argv)
    if args.worker:
        return worker_main(args)
    if args.durability_worker:
        return durability_worker_main(args)
    if args.durability:
        return durability_main(args)
    if args.control:
        return control_main(args)
    return harness_main(args)


if __name__ == "__main__":
    sys.exit(main())
