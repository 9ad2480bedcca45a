"""Transport tests: loopback sockets driving the real input loops
(reference pattern: in-memory channel harness, udp_input.rs:182-233)."""

import queue
import socket
import threading
import time

import pytest

from flowgger_tpu.config import Config
from flowgger_tpu.decoders import RFC5424Decoder
from flowgger_tpu.encoders import PassthroughEncoder
from flowgger_tpu.splitters import ScalarHandler

LINE = "<13>1 2015-08-05T15:53:45Z host app 1 2 - hello"


def _factory(tx):
    return lambda: ScalarHandler(tx, RFC5424Decoder(),
                                 PassthroughEncoder(Config.from_string("")))


def _drain(tx, n, timeout=5.0):
    out = []
    deadline = time.time() + timeout
    while len(out) < n and time.time() < deadline:
        try:
            out.append(tx.get(timeout=0.2))
        except queue.Empty:
            pass
    return out


def test_tcp_input_end_to_end():
    from flowgger_tpu.inputs.tcp_input import TcpInput

    config = Config.from_string('[input]\nlisten = "127.0.0.1:0"\ntimeout = 5\n')
    inp = TcpInput(config)
    tx = queue.Queue()
    t = threading.Thread(target=inp.accept, args=(_factory(tx),), daemon=True)
    t.start()
    while inp.bound_port is None:
        time.sleep(0.01)
    with socket.create_connection(("127.0.0.1", inp.bound_port)) as s:
        s.sendall(f"{LINE}\n{LINE}\n".encode())
    assert _drain(tx, 2) == [LINE.encode()] * 2


def test_tcp_input_syslen_framing():
    from flowgger_tpu.inputs.tcp_input import TcpInput

    config = Config.from_string(
        '[input]\nlisten = "127.0.0.1:0"\nframed = true\ntimeout = 5\n')
    inp = TcpInput(config)
    assert inp.framing == "syslen"
    tx = queue.Queue()
    t = threading.Thread(target=inp.accept, args=(_factory(tx),), daemon=True)
    t.start()
    while inp.bound_port is None:
        time.sleep(0.01)
    with socket.create_connection(("127.0.0.1", inp.bound_port)) as s:
        s.sendall(f"{len(LINE)} {LINE}".encode())
    assert _drain(tx, 1) == [LINE.encode()]


def test_tcpco_input_end_to_end():
    from flowgger_tpu.inputs.tcp_input import TcpCoInput

    config = Config.from_string('[input]\nlisten = "127.0.0.1:0"\ntimeout = 5\n')
    inp = TcpCoInput(config)
    tx = queue.Queue()
    t = threading.Thread(target=inp.accept, args=(_factory(tx),), daemon=True)
    t.start()
    while inp.bound_port is None:
        time.sleep(0.01)
    with socket.create_connection(("127.0.0.1", inp.bound_port)) as s:
        s.sendall(f"{LINE}\n".encode())
    assert _drain(tx, 1) == [LINE.encode()]


def test_udp_input_end_to_end():
    from flowgger_tpu.inputs.udp_input import UdpInput

    config = Config.from_string('[input]\nlisten = "127.0.0.1:0"\n')
    inp = UdpInput(config)
    tx = queue.Queue()
    t = threading.Thread(target=inp.accept, args=(_factory(tx),), daemon=True)
    t.start()
    while inp.bound_port is None:
        time.sleep(0.01)
    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
        s.sendto(LINE.encode(), ("127.0.0.1", inp.bound_port))
    assert _drain(tx, 1) == [LINE.encode()]


def test_udp_compressed_records():
    import gzip
    import zlib

    from flowgger_tpu.inputs.udp_input import handle_record_maybe_compressed

    tx = queue.Queue()
    handler = _factory(tx)()
    handle_record_maybe_compressed(zlib.compress(LINE.encode()), handler)
    # gzip needs >= 24 bytes; LINE compresses well above that
    handle_record_maybe_compressed(gzip.compress(LINE.encode()), handler)
    handle_record_maybe_compressed(LINE.encode(), handler)
    out = [tx.get_nowait() for _ in range(3)]
    assert out == [LINE.encode()] * 3


def test_udp_corrupted_compressed(capsys):
    from flowgger_tpu.inputs.udp_input import handle_record_maybe_compressed

    tx = queue.Queue()
    handler = _factory(tx)()
    handle_record_maybe_compressed(b"\x78\x9c" + b"garbage!", handler)
    assert tx.empty()
    assert "Corrupted compressed" in capsys.readouterr().err


def test_udp_bare_error_format(capsys):
    from flowgger_tpu.inputs.udp_input import handle_record_maybe_compressed

    tx = queue.Queue()
    handler = _factory(tx)()
    handler.bare_errors = True
    handle_record_maybe_compressed(b"not a syslog line", handler)
    err = capsys.readouterr().err
    assert err == "Unsupported BOM\n"  # no [line] suffix on the udp path


def test_tls_input_end_to_end(session_pem):
    import ssl

    pem = session_pem
    from flowgger_tpu.inputs.tls_input import TlsInput

    config = Config.from_string(
        f'[input]\nlisten = "127.0.0.1:0"\ntimeout = 5\n'
        f'tls_cert = "{pem}"\ntls_key = "{pem}"\n')
    inp = TlsInput(config)
    tx = queue.Queue()
    t = threading.Thread(target=inp.accept, args=(_factory(tx),), daemon=True)
    t.start()
    while inp.bound_port is None:
        time.sleep(0.01)
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    ctx.check_hostname = False
    ctx.verify_mode = ssl.CERT_NONE
    with socket.create_connection(("127.0.0.1", inp.bound_port)) as raw:
        with ctx.wrap_socket(raw) as s:
            s.sendall(f"{LINE}\n".encode())
    assert _drain(tx, 1) == [LINE.encode()]


def test_file_input_tail(tmp_path):
    from flowgger_tpu.inputs.file_input import FileInput

    log = tmp_path / "app.log"
    log.write_text("old line ignored\n")
    config = Config.from_string(f'[input]\nsrc = "{tmp_path}/*.log"\n')
    inp = FileInput(config)
    tx = queue.Queue()
    t = threading.Thread(target=inp.accept, args=(_factory(tx),), daemon=True)
    t.start()
    time.sleep(0.3)
    with open(log, "a") as fd:
        fd.write(f"{LINE}\n")
    assert _drain(tx, 1) == [LINE.encode()]
    # a new file appearing later is read from the start
    log2 = tmp_path / "new.log"
    log2.write_text(f"{LINE}\n")
    assert _drain(tx, 1) == [LINE.encode()]


def test_redis_input_reliable_queue():
    """Full reliable-queue flow against an in-process fake redis server
    speaking just enough RESP."""
    from flowgger_tpu.inputs.redis_input import RedisInput

    main: "queue.Queue[bytes]" = queue.Queue()
    tmp = []
    main.put(LINE.encode())
    lrem_called = threading.Event()

    def serve(server):
        conn, _ = server.accept()
        buf = b""
        while True:
            try:
                data = conn.recv(4096)
            except OSError:
                return
            if not data:
                return
            buf += data
            while b"\r\n" in buf:
                # parse one RESP array command
                cmd, buf2 = _parse_resp(buf)
                if cmd is None:
                    break
                buf = buf2
                name = cmd[0].upper()
                if name == b"RPOPLPUSH":
                    if tmp:
                        v = tmp.pop()
                        main.put(v)
                        conn.sendall(b"$%d\r\n%s\r\n" % (len(v), v))
                    else:
                        conn.sendall(b"$-1\r\n")
                elif name == b"BRPOPLPUSH":
                    v = main.get()
                    tmp.append(v)
                    conn.sendall(b"$%d\r\n%s\r\n" % (len(v), v))
                elif name == b"LREM":
                    tmp.clear()
                    lrem_called.set()
                    conn.sendall(b":1\r\n")

    server = socket.create_server(("127.0.0.1", 0))
    port = server.getsockname()[1]
    threading.Thread(target=serve, args=(server,), daemon=True).start()

    config = Config.from_string(f'[input]\nredis_connect = "127.0.0.1:{port}"\n')
    inp = RedisInput(config)
    inp.exit_on_failure = False
    tx = queue.Queue()
    threading.Thread(target=inp.accept, args=(_factory(tx),), daemon=True).start()
    assert _drain(tx, 1) == [LINE.encode()]
    assert lrem_called.wait(timeout=5)


def _parse_resp(buf):
    """Parse one complete RESP array of bulk strings; (None, buf) if short."""
    if not buf.startswith(b"*"):
        return None, buf
    try:
        head, rest = buf.split(b"\r\n", 1)
        n = int(head[1:])
        parts = []
        for _ in range(n):
            if not rest.startswith(b"$"):
                return None, buf
            lhead, rest = rest.split(b"\r\n", 1)
            ln = int(lhead[1:])
            if len(rest) < ln + 2:
                return None, buf
            parts.append(rest[:ln])
            rest = rest[ln + 2:]
        return parts, rest
    except (ValueError, IndexError):
        return None, buf


def test_tcp_to_tpu_batch_pipeline_end_to_end(tmp_path):
    """Full flagship path over a real socket: TCP -> chunked ingest ->
    columnar decode -> span->gelf encode -> file sink."""
    from flowgger_tpu.pipeline import Pipeline

    out = tmp_path / "out.log"
    config = Config.from_string(
        f"""
[input]
type = "tcp"
format = "rfc5424_tpu"
listen = "127.0.0.1:0"
timeout = 5
tpu_flush_ms = 30
[output]
type = "file"
format = "gelf"
file_path = "{out}"
"""
    )
    pipeline = Pipeline(config)
    pipeline.start_output()
    t = threading.Thread(target=pipeline.input.accept,
                         args=(pipeline.handler_factory,), daemon=True)
    t.start()
    while pipeline.input.bound_port is None:
        time.sleep(0.01)
    lines = [f"<13>1 2015-08-05T15:53:45Z host{i} app {i} m - msg {i}"
             for i in range(50)]
    with socket.create_connection(("127.0.0.1", pipeline.input.bound_port)) as s:
        s.sendall("".join(ln + "\n" for ln in lines).encode())
    def records():
        return out.read_bytes().count(b"\x00") if out.exists() else 0

    # the first batch carries a cold kernel compile, whose length is the
    # host's (and, under xdist, its neighbours') business: the 15 s wall
    # deadline starts once a batch has left the handler
    cold = time.time() + 300
    while records() < 1 and time.time() < cold:
        time.sleep(0.05)
    deadline = time.time() + 15
    while records() < 50 and time.time() < deadline:
        time.sleep(0.05)
    msgs = [m for m in out.read_bytes().split(b"\x00") if m]
    assert len(msgs) == 50
    # order preserved end to end
    for i, m in enumerate(msgs):
        assert f'"host":"host{i}"'.encode() in m, (i, m)


def test_file_input_tail_poll_fallback(tmp_path, monkeypatch):
    """The poll fallback (platforms without inotify) must behave the
    same: existing files tail from EOF, new files read from the start."""
    from flowgger_tpu.inputs import file_input as fi

    monkeypatch.setattr(fi._ino, "available", lambda: False)
    log = tmp_path / "app.log"
    log.write_text("old line ignored\n")
    config = Config.from_string(f'[input]\nsrc = "{tmp_path}/*.log"\n')
    inp = fi.FileInput(config)
    assert inp.use_inotify is False
    tx = queue.Queue()
    t = threading.Thread(target=inp.accept, args=(_factory(tx),), daemon=True)
    t.start()
    time.sleep(0.3)
    with open(log, "a") as fd:
        fd.write(f"{LINE}\n")
    assert _drain(tx, 1) == [LINE.encode()]
    log2 = tmp_path / "new.log"
    log2.write_text(f"{LINE}\n")
    assert _drain(tx, 1) == [LINE.encode()]


def test_file_input_inotify_event_driven(tmp_path):
    """With inotify active, a new file in a fresh subdirectory matching
    the glob is discovered via directory events (no rescan interval),
    and appends flow through file Modify events."""
    from flowgger_tpu.inputs.file_input import FileInput
    from flowgger_tpu.utils import inotify as ino

    if not ino.available():
        import pytest

        pytest.skip("inotify unavailable on this platform")
    config = Config.from_string(f'[input]\nsrc = "{tmp_path}/*/app.log"\n')
    inp = FileInput(config)
    assert inp.use_inotify is True
    tx = queue.Queue()
    t = threading.Thread(target=inp.accept, args=(_factory(tx),), daemon=True)
    t.start()
    time.sleep(0.3)
    sub = tmp_path / "svc1"
    sub.mkdir()
    time.sleep(0.7)  # one bounded event-wait cycle to pick up the dir
    log = sub / "app.log"
    log.write_text(f"{LINE}\n")
    assert _drain(tx, 1) == [LINE.encode()]
    with open(log, "a") as fd:
        fd.write(f"{LINE}\n")
    assert _drain(tx, 1) == [LINE.encode()]


def test_file_input_logrotate_rename_create(tmp_path):
    """logrotate's rename+create: the old worker dies, a fresh worker
    must pick up the recreated path and read it from the start."""
    from flowgger_tpu.inputs.file_input import FileInput

    log = tmp_path / "app.log"
    log.write_text("preexisting\n")
    config = Config.from_string(f'[input]\nsrc = "{tmp_path}/app.log"\n')
    inp = FileInput(config)
    tx = queue.Queue()
    t = threading.Thread(target=inp.accept, args=(_factory(tx),), daemon=True)
    t.start()
    time.sleep(0.3)
    with open(log, "a") as fd:
        fd.write(f"{LINE}\n")
    assert _drain(tx, 1) == [LINE.encode()]
    # rotate: rename away, create a new file at the same path
    log.rename(tmp_path / "app.log.1")
    time.sleep(0.2)
    log.write_text(f"{LINE}\n{LINE}\n")
    assert _drain(tx, 2) == [LINE.encode()] * 2


def test_udp_batched_recvmmsg_tpu(tmp_path):
    """UDP with a span-capable handler takes the recvmmsg fast path:
    plain datagrams (incl. empty) batch into spans, compressed ones
    inflate, all arrive exactly once."""
    import zlib as _zlib
    import gzip as _gzip

    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.inputs.udp_input import UdpInput
    from flowgger_tpu.mergers import LineMerger
    from flowgger_tpu.tpu.batch import BatchHandler
    from flowgger_tpu.utils import recvmmsg as rm
    from flowgger_tpu.block import EncodedBlock
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder

    if not rm.available():
        import pytest

        pytest.skip("recvmmsg unavailable")
    cfg = Config.from_string(
        '[input]\nlisten = "127.0.0.1:0"\ntpu_flush_ms = 20\n')
    inp = UdpInput(cfg)
    tx = queue.Queue()
    dec = RFC5424Decoder(cfg)
    enc = GelfEncoder(cfg)

    def factory():
        return BatchHandler(tx, dec, enc, cfg, fmt="rfc5424",
                            start_timer=True, merger=LineMerger())

    t = threading.Thread(target=inp.accept, args=(factory,), daemon=True)
    t.start()
    while inp.bound_port is None:
        time.sleep(0.01)
    line = "<13>1 2015-08-05T15:53:45Z h app 1 2 - udp msg %d"
    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
        for i in range(40):
            s.sendto((line % i).encode(), ("127.0.0.1", inp.bound_port))
        s.sendto(_zlib.compress((line % 100).encode()),
                 ("127.0.0.1", inp.bound_port))
        s.sendto(_gzip.compress((line % 101).encode() + b" padpadpadpad"),
                 ("127.0.0.1", inp.bound_port))
        s.sendto(b"", ("127.0.0.1", inp.bound_port))  # zero-length span
    got = []
    # generous deadline: a cold box pays the decode-kernel compile plus
    # a device-encode watchdog decline before the first batch lands
    deadline = time.time() + 45
    while len(got) < 42 and time.time() < deadline:
        try:
            item = tx.get(timeout=0.2)
        except queue.Empty:
            continue
        got.extend(item.iter_unframed() if isinstance(item, EncodedBlock)
                   else [item])
    assert len(got) == 42
    blob = b"".join(got)
    for i in list(range(40)) + [100, 101]:
        assert (f"udp msg {i}".encode()) in blob


def test_tls_input_to_tpu_block_pipeline(session_pem):
    """TLS transport feeding the block-mode batch handler: framed TLS
    bytes flow through ingest_chunk to an EncodedBlock, byte-identical
    to the scalar expectation."""
    import ssl

    from flowgger_tpu.block import EncodedBlock
    from flowgger_tpu.decoders.rfc5424 import RFC5424Decoder
    from flowgger_tpu.encoders.gelf import GelfEncoder
    from flowgger_tpu.inputs.tls_input import TlsInput
    from flowgger_tpu.mergers import NulMerger
    from flowgger_tpu.tpu.batch import BatchHandler

    pem = session_pem
    config = Config.from_string(
        f'[input]\nlisten = "127.0.0.1:0"\ntimeout = 5\n'
        f'tls_cert = "{pem}"\ntls_key = "{pem}"\ntpu_flush_ms = 20\n')
    inp = TlsInput(config)
    tx = queue.Queue()
    dec = RFC5424Decoder(config)
    enc = GelfEncoder(config)

    def factory():
        return BatchHandler(tx, dec, enc, config, fmt="rfc5424",
                            start_timer=True, merger=NulMerger())

    t = threading.Thread(target=inp.accept, args=(factory,), daemon=True)
    t.start()
    while inp.bound_port is None:
        time.sleep(0.01)
    lines = [f"<13>1 2015-08-05T15:53:45Z tlshost app {i} m - over tls {i}"
             for i in range(5)]
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    ctx.check_hostname = False
    ctx.verify_mode = ssl.CERT_NONE
    with socket.create_connection(("127.0.0.1", inp.bound_port)) as raw:
        with ctx.wrap_socket(raw) as s:
            s.sendall(("".join(ln + "\n" for ln in lines)).encode())
    want = [enc.encode(dec.decode(ln)) + b"\0" for ln in lines]
    got = []
    deadline = time.time() + 10
    while len(got) < 5 and time.time() < deadline:
        try:
            item = tx.get(timeout=0.2)
        except queue.Empty:
            continue
        got.extend(item.iter_framed() if isinstance(item, EncodedBlock)
                   else [item])
    assert got == want
