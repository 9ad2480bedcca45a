"""End-to-end pipeline tests: config → wiring → stdin-style stream →
file sink (SURVEY.md §7 step 3, the minimum end-to-end slice)."""

import io

import pytest

from flowgger_tpu.config import Config, ConfigError
from flowgger_tpu.outputs import SHUTDOWN
from flowgger_tpu.pipeline import Pipeline, infer_output_framing
from flowgger_tpu.splitters import LineSplitter

LINE = '<23>1 2015-08-05T15:53:45.637824Z testhostname appname 69 42 - test message'


def test_e2e_rfc5424_to_gelf_file(tmp_path):
    out = tmp_path / "out.log"
    config = Config.from_string(
        f"""
[input]
type = "stdin"
format = "rfc5424"
[output]
type = "file"
format = "gelf"
file_path = "{out}"
"""
    )
    pipeline = Pipeline(config)
    thread = pipeline.start_output()
    handler = pipeline.handler_factory()
    LineSplitter().run(io.BytesIO(f"{LINE}\n{LINE}\n".encode()), handler)
    pipeline.tx.put(SHUTDOWN)
    thread.join(timeout=10)
    data = out.read_bytes()
    # gelf + file infers nul framing (mod.rs:446-451)
    msgs = data.split(b"\0")
    assert msgs[-1] == b""
    assert len(msgs) == 3
    for msg in msgs[:2]:
        assert b'"host":"testhostname"' in msg
        assert b'"timestamp":1438790025.637824' in msg


def test_e2e_passthrough_line(tmp_path):
    out = tmp_path / "out.log"
    config = Config.from_string(
        f"""
[input]
type = "stdin"
format = "rfc5424"
[output]
type = "file"
format = "passthrough"
framing = "line"
file_path = "{out}"
"""
    )
    pipeline = Pipeline(config)
    thread = pipeline.start_output()
    handler = pipeline.handler_factory()
    LineSplitter().run(io.BytesIO(f"{LINE}\nnot valid\n".encode()), handler)
    pipeline.tx.put(SHUTDOWN)
    thread.join(timeout=10)
    assert out.read_bytes() == f"{LINE}\n".encode()


def test_framing_inference():
    # mod.rs:444-452 table
    assert infer_output_framing("capnp", "file") == "noop"
    assert infer_output_framing("gelf", "kafka") == "noop"
    assert infer_output_framing("gelf", "debug") == "line"
    assert infer_output_framing("ltsv", "file") == "line"
    assert infer_output_framing("gelf", "file") == "nul"
    assert infer_output_framing("rfc5424", "file") == "noop"


def test_unknown_input_format():
    with pytest.raises(ConfigError, match="Unknown input format"):
        Pipeline(Config.from_string(
            '[input]\ntype = "stdin"\nformat = "bogus"\n[output]\ntype = "debug"\n'
        ))


def test_unknown_output_type():
    with pytest.raises(ConfigError, match="Invalid output type"):
        Pipeline(Config.from_string(
            '[input]\ntype = "stdin"\n[output]\ntype = "bogus"\n'
        ))


def _start_tcp_tpu_pipeline(out_path, extra_input=""):
    """Construct, start and return a TCP rfc5424_tpu -> gelf file
    pipeline with its accept loop on a daemon thread; waits (bounded)
    for the listener to bind."""
    import threading
    import time

    from flowgger_tpu.pipeline import Pipeline

    config = Config.from_string(
        '[input]\ntype = "tcp"\nlisten = "127.0.0.1:0"\n'
        'format = "rfc5424_tpu"\ntimeout = 5\n' + extra_input +
        '[output]\ntype = "file"\nformat = "gelf"\n'
        f'file_path = "{out_path}"\n')
    p = Pipeline(config)
    p.start_output()
    t = threading.Thread(target=p.input.accept, args=(p.handler_factory,),
                         daemon=True)
    t.start()
    deadline = time.time() + 10
    while p.input.bound_port is None:
        assert time.time() < deadline, "listener never bound"
        time.sleep(0.01)
    return p


def _await_records(out_path, want, wall_s, cold_s=300):
    """Wait for ``want`` NUL-framed records in the sink.  The wall
    deadline starts once the first batch has come out of the handler:
    before that the wait is bounded only by ``cold_s``, because the
    first batch carries a cold kernel compile whose length is the
    host's (and its neighbours') business, not the served path's."""
    import time

    def count():
        return out_path.read_bytes().count(b"\0") if out_path.exists() else 0

    deadline = time.time() + cold_s
    while count() < 1:
        assert time.time() < deadline, "no batch ever left the handler"
        time.sleep(0.05)
    deadline = time.time() + wall_s
    while count() < want and time.time() < deadline:
        time.sleep(0.05)


def test_tpu_handler_shared_across_connections(tmp_path):
    """Every connection of a *_tpu pipeline shares ONE batch handler so
    batches aggregate across connections; scalar pipelines keep
    per-connection handlers."""
    import socket

    from flowgger_tpu.pipeline import Pipeline

    out_path = tmp_path / "shared.out"
    p = _start_tcp_tpu_pipeline(out_path, "tpu_flush_ms = 30\n")
    line = "<13>1 2015-08-05T15:53:45Z shared app 1 2 - via conn %d"
    conns = [socket.create_connection(("127.0.0.1", p.input.bound_port))
             for _ in range(3)]
    for i, c in enumerate(conns):
        c.sendall((line % i + "\n").encode())
    _await_records(out_path, 3, wall_s=10)
    for c in conns:
        c.close()
    assert len(p._handlers) == 1  # one shared BatchHandler
    data = out_path.read_bytes()
    assert data.count(b"\0") == 3
    for i in range(3):
        assert (f"via conn {i}".encode()) in data

    # scalar pipelines keep one handler per connection
    config2 = Config.from_string(
        '[input]\ntype = "tcp"\nlisten = "127.0.0.1:0"\n'
        'format = "rfc5424"\ntimeout = 5\n'
        '[output]\ntype = "debug"\nformat = "gelf"\n')
    p2 = Pipeline(config2)
    h1, h2 = p2.handler_factory(), p2.handler_factory()
    assert h1 is not h2


def test_shared_handler_concurrent_connections_no_loss(tmp_path):
    """Many threads hammering the shared batch handler concurrently:
    every message must come out exactly once (locks on ingest, decode
    serialization, pipelined flushes)."""
    import socket
    import threading

    out_path = tmp_path / "stress.out"
    p = _start_tcp_tpu_pipeline(
        out_path, "tpu_batch_size = 64\ntpu_flush_ms = 20\n")

    n_conns, per_conn = 8, 200

    def sender(cid):
        with socket.create_connection(("127.0.0.1", p.input.bound_port)) as s:
            for i in range(per_conn):
                s.sendall(
                    (f"<13>1 2015-08-05T15:53:45.{i % 1000:03d}Z h app "
                     f"{cid} m - c{cid}-m{i}\n").encode())

    threads = [threading.Thread(target=sender, args=(c,))
               for c in range(n_conns)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    want = n_conns * per_conn
    _await_records(out_path, want, wall_s=20)
    data = out_path.read_bytes()
    assert data.count(b"\0") == want
    assert len(p._handlers) == 1
    for c in range(n_conns):
        for i in range(0, per_conn, 37):
            assert f"c{c}-m{i}".encode() in data


def test_cli_run_caches_where_the_environment_says(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` places the persistent compile cache
    from outside: a ``python -m flowgger_tpu`` run writes its entries
    into that directory as it is — no sub-directory of the program's
    making, nothing in the checkout's own default."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache = tmp_path / "placed-cache"
    out = tmp_path / "out.gelf"
    cfg = tmp_path / "cfg.toml"
    cfg.write_text(
        '[input]\ntype = "stdin"\nformat = "rfc5424_tpu"\n'
        '[output]\ntype = "file"\nformat = "gelf"\n'
        f'file_path = "{out}"\n')
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache), "PYTHONPATH": repo}
    r = subprocess.run(
        [sys.executable, "-m", "flowgger_tpu", str(cfg)],
        input=f"{LINE}\n{LINE}\n{LINE}\n".encode(), env=env, cwd=repo,
        capture_output=True, timeout=300)
    assert r.returncode == 0, r.stderr.decode()[-2000:]
    assert out.read_bytes().count(b"\0") == 3
    entries = os.listdir(cache)
    assert entries, "the run cached nothing where it was told to"
    assert not any(e.startswith("kabi-") for e in entries)
