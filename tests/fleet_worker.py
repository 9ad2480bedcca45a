"""Worker process for the fleet federation acceptance test
(tests/test_fleet_acceptance.py — NOT a test module itself).

Each worker joins the real 2-process ``jax.distributed`` group via the
production ``init_distributed`` path AND the fleet heartbeat layer via
the production ``Fleet`` path, then streams its own corpus through the
production ``BatchHandler`` in small chunks — slowly enough that the
harness's simulated host kill (the ``host_kill`` fault site, set via
``FLOWGGER_FAULTS`` on the victim) lands mid-stream.

The survivor (rank 0) must keep decoding through the peer's death,
emit byte-identical framed output for every line it owns, watch the
victim walk the missed-heartbeat ladder (suspect → draining →
departed), and report its observed transition history as one JSON line
on stdout.  It exits via ``os._exit(0)`` after its output is flushed:
the JAX coordination service's opinion of the dead peer must not be
able to wedge a clean fleet exit.
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

CHUNK = 8
CHUNK_SLEEP_S = 0.25  # spreads 96 lines over ~3s: the kill lands mid-stream


def corpus(pid: int, n: int):
    return [
        (f'<{(3 * i + pid) % 192}>1 2023-09-20T12:35:45.{i % 1000:03d}Z '
         f'host{pid} app {i} m [sd@1 k="{i}" x="y"] '
         f'worker {pid} line {i}').encode()
        for i in range(n)
    ]


def main():
    pid = int(sys.argv[1])
    jax_port = sys.argv[2]
    fleet_port = sys.argv[3]
    coord_fleet_port = sys.argv[4]
    out_path = sys.argv[5]
    n_lines = int(sys.argv[6])

    import queue

    from flowgger_tpu.block import EncodedBlock
    from flowgger_tpu.config import Config
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.fleet import DEPARTED, Fleet
    from flowgger_tpu.mergers import LineMerger
    from flowgger_tpu.parallel.distributed import init_distributed
    from flowgger_tpu.supervise import Supervisor
    from flowgger_tpu.tpu.batch import BatchHandler
    from flowgger_tpu.utils import faultinject

    coord = ("" if pid == 0 else
             f'tpu_fleet_coordinator = "127.0.0.1:{coord_fleet_port}"\n')
    cfg = Config.from_string(
        f'[input]\ntpu_coordinator = "127.0.0.1:{jax_port}"\n'
        f"tpu_num_processes = 2\ntpu_process_id = {pid}\n"
        f"tpu_fleet = true\n"
        f"tpu_fleet_port = {fleet_port}\n{coord}"
        "tpu_fleet_heartbeat_ms = 200\ntpu_fleet_suspect_ms = 1000\n"
        "tpu_fleet_evict_ms = 2500\ntpu_fleet_depart_ms = 1500\n")
    faultinject.configure_from(cfg)  # FLOWGGER_FAULTS (host_kill) applies
    assert init_distributed(cfg) is True
    assert jax.process_count() == 2, jax.process_count()

    fleet = Fleet.from_config(cfg, supervisor=Supervisor())
    fleet.start()
    assert fleet.wait_active(2, 60), "fleet rendezvous never converged"
    print(f"worker {pid}: fleet converged (2 active)", flush=True)

    lines = corpus(pid, n_lines)
    tx = queue.Queue()
    handler = BatchHandler(tx, RFC5424Decoder(),
                           GelfEncoder(Config.from_string("")), cfg,
                           fmt="rfc5424", start_timer=False,
                           merger=LineMerger())
    # stream the output file incrementally (fsync per chunk): when the
    # host_kill site SIGKILLs the victim mid-stream, whatever this host
    # already emitted must survive on disk as an uncorrupted, in-order
    # prefix of its reference stream
    total = 0
    with open(out_path, "wb") as fd:
        for start in range(0, len(lines), CHUNK):
            for ln in lines[start:start + CHUNK]:
                handler.handle_bytes(ln)
            handler.flush()
            while not tx.empty():
                item = tx.get_nowait()
                data = item.data if isinstance(item, EncodedBlock) else item
                fd.write(data)
                total += len(data)
            fd.flush()
            os.fsync(fd.fileno())
            time.sleep(CHUNK_SLEEP_S)
    print(f"worker {pid}: decoded {len(lines)} lines, "
          f"{total} bytes", flush=True)

    if pid != 0:
        # the victim: FLOWGGER_FAULTS host_kill SIGKILLs us from the
        # fleet ticker; idle here until it lands (the parent asserts we
        # died by signal, not by falling off main)
        time.sleep(120)
        sys.exit(3)

    # the survivor: watch the victim walk the full missed-heartbeat
    # ladder in OUR membership view, then report and leave
    other = 1 - pid
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        view = fleet.membership.view_of(other)
        if view is not None and view["state"] == DEPARTED:
            break
        time.sleep(0.05)
    view = fleet.membership.view_of(other)
    ladder = [(a, b) for _, r, a, b in fleet.membership.transitions
              if r == other]
    counts = fleet.membership.counts()
    print(json.dumps({
        "rank": pid,
        "bytes": total,
        "peer_final_state": view["state"] if view else None,
        "peer_evicted": bool(view and view["evicted"]),
        "peer_ladder": ladder,
        "counts": counts,
    }), flush=True)
    # linger so the parent's health poller can observe the final state
    # through the endpoint before it disappears with us
    time.sleep(2.0)
    sys.stdout.flush()
    os._exit(0)  # see module docstring: never wait on jax's opinion


if __name__ == "__main__":
    main()
