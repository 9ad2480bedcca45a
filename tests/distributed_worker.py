"""Worker process for the real 2-process jax.distributed smoke test
(tests/test_distributed_smoke.py — NOT a test module itself).

Each worker joins the process group via the production
``init_distributed`` config path, asserts the global device view spans
both hosts, then decodes its own corpus shard through the production
BatchHandler with the mesh forced on — which, per the multi-host
contract (ADVICE r3 / parallel/mesh.py), must engage a *local-device*
mesh so every row stays addressable.  The framed output bytes go to a
file the parent compares against the single-process reference.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402


def main():
    pid = int(sys.argv[1])
    port = sys.argv[2]
    out_path = sys.argv[3]

    import queue

    from flowgger_tpu.block import EncodedBlock
    from flowgger_tpu.config import Config
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.mergers import LineMerger
    from flowgger_tpu.parallel.distributed import init_distributed
    from flowgger_tpu.tpu.batch import BatchHandler

    cfg = Config.from_string(
        f'[input]\ntpu_coordinator = "127.0.0.1:{port}"\n'
        f"tpu_num_processes = 2\ntpu_process_id = {pid}\n"
        'tpu_mesh = "on"\n')
    assert init_distributed(cfg) is True
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.local_devices()) == 4
    assert len(jax.devices()) == 8, "global view must span both processes"

    # per-process shard: each host ingests its own stream (dp over DCN
    # is data parallelism over independent shards, SURVEY.md §2.8)
    lines = [
        (f'<{(3 * i + pid) % 192}>1 2023-09-20T12:35:45.{i:03d}Z '
         f'host{pid} app {i} m [sd@1 k="{i}" x="y"] '
         f'worker {pid} line {i}').encode()
        for i in range(64)
    ]

    tx = queue.Queue()
    h = BatchHandler(tx, RFC5424Decoder(), GelfEncoder(Config.from_string("")),
                     cfg, fmt="rfc5424", start_timer=False,
                     merger=LineMerger())
    for ln in lines:
        h.handle_bytes(ln)
    h.flush()

    # multi-process ⇒ the mesh must engage on LOCAL devices only
    assert h._sharded_for("rfc5424") is not None, "mesh did not engage"
    assert h._mesh is not None
    assert set(h._mesh.devices.flat) == set(jax.local_devices()), \
        "multi-process mesh must be host-local"

    data = b""
    while not tx.empty():
        item = tx.get_nowait()
        data += item.data if isinstance(item, EncodedBlock) else item
    with open(out_path, "wb") as f:
        f.write(data)
    print(f"worker {pid}: ok ({len(lines)} lines, {len(data)} bytes)")


if __name__ == "__main__":
    main()
