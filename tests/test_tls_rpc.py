"""Mutual TLS on the RPC substrate: handshake, client-auth enforcement,
wrong-CA rejection, pinned-cert allowlists, peer-cert exposure."""

from __future__ import annotations

import pytest

from fabric_tpu.comm.rpc import RPCClient, RPCError, RPCServer
from fabric_tpu.comm.tls import TLSCredentials, credentials_from_ca
from fabric_tpu.common.crypto import CA


@pytest.fixture(scope="module")
def cas():
    return CA("tlsca.org1.example.com", "org1"), CA(
        "tlsca.org2.example.com", "org2"
    )


def _server(creds):
    srv = RPCServer(tls=creds)
    srv.register("echo", lambda body, stream: b"ok:" + body)
    srv.start()
    return srv


def test_mutual_tls_roundtrip(cas):
    ca, _ = cas
    srv = _server(credentials_from_ca(ca, "server.org1"))
    try:
        cli = RPCClient(*srv.addr, tls=credentials_from_ca(ca, "client.org1"))
        assert cli.call("echo", b"hi") == b"ok:hi"
    finally:
        srv.stop()


def test_two_threads_building_contexts_of_one_credentials_object(cas, monkeypatch):
    """A restarted peer's deliver clients build their client contexts
    on their own threads while the main thread builds the server's: the
    second to ask must not be shown the key directory before the files
    are in it (the peer died of `load_cert_chain`'s FileNotFoundError;
    `tests/test_nwo.py`'s restart under load, until PR 33)."""
    import os
    import threading
    import time

    from fabric_tpu.comm import tls as tls_mod

    named = threading.Event()
    real_chmod = os.chmod

    def slow_chmod(path, mode):
        real_chmod(path, mode)
        if mode == 0o700:       # the directory exists, the files do not
            named.set()
            time.sleep(0.3)

    monkeypatch.setattr(tls_mod.os, "chmod", slow_chmod)
    creds = credentials_from_ca(cas[0], "peer0.org1")
    failed = []

    def first():
        try:
            creds.client_context()
        except Exception as exc:
            failed.append(exc)

    t = threading.Thread(target=first)
    t.start()
    assert named.wait(5)
    creds.server_context()
    t.join()
    assert failed == []


def test_client_without_cert_rejected(cas):
    ca, _ = cas
    srv = _server(credentials_from_ca(ca, "server.org1"))
    try:
        # TLS context with trust but *no* client certificate
        import socket
        import ssl

        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        ctx.check_hostname = False
        ctx.verify_mode = ssl.CERT_REQUIRED
        ctx.load_verify_locations(cadata=ca.cert_pem.decode())
        sock = socket.create_connection(srv.addr, timeout=5)
        with pytest.raises((ssl.SSLError, ConnectionError, OSError)):
            tls_sock = ctx.wrap_socket(sock)
            # server requires a client cert: handshake or first read fails
            tls_sock.sendall(b"x" * 8)
            tls_sock.recv(1)
            tls_sock.recv(1)
            raise ConnectionError("server accepted an unauthenticated client")
    finally:
        srv.stop()


def test_wrong_ca_client_rejected(cas):
    ca1, ca2 = cas
    srv = _server(credentials_from_ca(ca1, "server.org1"))
    try:
        # client cert from a CA the server does not trust
        pair = ca2.issue("evil.org2", client=True, server=True)
        wrong = TLSCredentials(
            cert_pem=pair.cert_pem, key_pem=pair.key_pem,
            ca_pems=[ca1.cert_pem],
        )
        cli = RPCClient(*srv.addr, tls=wrong, timeout=5)
        with pytest.raises((RPCError, ConnectionError, OSError)):
            cli.call("echo", b"hi")
    finally:
        srv.stop()


def test_plaintext_client_to_tls_server_fails(cas):
    ca, _ = cas
    srv = _server(credentials_from_ca(ca, "server.org1"))
    try:
        cli = RPCClient(*srv.addr, timeout=5)
        with pytest.raises((RPCError, ConnectionError, OSError)):
            cli.call("echo", b"hi")
    finally:
        srv.stop()


def test_pinned_cert_allowlist(cas):
    ca, _ = cas
    good = credentials_from_ca(ca, "client.good")
    other = credentials_from_ca(ca, "client.other")
    server_creds = credentials_from_ca(ca, "server.org1")
    server_creds.pinned_certs = [good.cert_der]  # only `good` may connect
    srv = _server(server_creds)
    try:
        cli = RPCClient(*srv.addr, tls=good)
        assert cli.call("echo", b"hi") == b"ok:hi"
        bad = RPCClient(*srv.addr, tls=other, timeout=5)
        with pytest.raises((RPCError, ConnectionError, OSError)):
            bad.call("echo", b"hi")
    finally:
        srv.stop()


def test_peer_cert_exposed_to_handler(cas):
    ca, _ = cas
    seen: list = []
    srv = RPCServer(tls=credentials_from_ca(ca, "server.org1"))

    def capture(body, stream):
        seen.append(stream.peer_cert)
        return b"ok"

    srv.register("cap", capture)
    srv.start()
    try:
        client_creds = credentials_from_ca(ca, "client.org1")
        RPCClient(*srv.addr, tls=client_creds).call("cap")
        assert seen and seen[0] == client_creds.cert_der
    finally:
        srv.stop()


def test_streaming_over_tls(cas):
    ca, _ = cas
    srv = RPCServer(tls=credentials_from_ca(ca, "server.org1"))
    srv.register("count", lambda body, stream: (b"%d" % i for i in range(5)))
    srv.start()
    try:
        cli = RPCClient(*srv.addr, tls=credentials_from_ca(ca, "client.org1"))
        assert list(cli.stream("count")) == [b"0", b"1", b"2", b"3", b"4"]
    finally:
        srv.stop()


def test_server_name_verified_by_default(cas):
    """A cert from the right CA but without the dialed address in its
    SANs must NOT pass as a server endpoint (advisor round-2 medium:
    otherwise any org-issued client cert can impersonate any peer or
    orderer).  Mirrors gRPC transport-credential SAN verification."""
    ca, _ = cas
    rogue_pair = ca.issue(
        "user1@org1", sans=["user1.example.com"], client=True, server=True
    )
    rogue = TLSCredentials(
        cert_pem=rogue_pair.cert_pem,
        key_pem=rogue_pair.key_pem,
        ca_pems=[ca.cert_pem],
    )
    srv = _server(rogue)  # "server" presenting a user cert
    try:
        cli = RPCClient(*srv.addr, tls=credentials_from_ca(ca, "client.org1"))
        with pytest.raises(RPCError, match="tls"):
            cli.call("echo", b"hi")
    finally:
        srv.stop()


def test_server_name_verification_opt_out(cas):
    ca, _ = cas
    pair = ca.issue(
        "node.org1", sans=["node.example.com"], client=True, server=True
    )
    srv_creds = TLSCredentials(
        cert_pem=pair.cert_pem, key_pem=pair.key_pem, ca_pems=[ca.cert_pem]
    )
    srv = _server(srv_creds)
    try:
        cli_creds = credentials_from_ca(ca, "client.org1")
        cli_creds.verify_server_name = False  # pin-protected transports
        cli = RPCClient(*srv.addr, tls=cli_creds)
        assert cli.call("echo", b"hi") == b"ok:hi"
    finally:
        srv.stop()


# -- keepalive / connection lifecycle --------------------------------------


def test_hung_peer_reaped_by_idle_timeout():
    """A client that connects and never sends a request is reaped after
    the idle window (reference keepalive semantics: silent connections
    must not hold server resources forever)."""
    import socket
    import time

    from fabric_tpu.comm.rpc import KeepaliveOptions, RPCServer

    srv = RPCServer(
        keepalive=KeepaliveOptions(idle_timeout=0.3, ping_interval=0.2)
    )
    srv.register("echo", lambda body, stream: b"ok")
    srv.start()
    try:
        sock = socket.create_connection(srv.addr, timeout=5)
        deadline = time.time() + 5
        while srv.connection_count == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert srv.connection_count >= 1
        # the server closes it without us ever sending a byte
        sock.settimeout(5)
        assert sock.recv(1) == b""
        deadline = time.time() + 5
        while srv.connection_count and time.time() < deadline:
            time.sleep(0.02)
        assert srv.connection_count == 0
        sock.close()
    finally:
        srv.stop()


def test_live_idle_stream_survives_keepalive():
    """A streaming handler with gaps longer than the ping interval is
    NOT torn down: PING frames keep the read deadline fresh and the
    client still sees every item."""
    import time

    from fabric_tpu.comm.rpc import KeepaliveOptions, RPCClient, RPCServer

    ka = KeepaliveOptions(
        idle_timeout=5.0, ping_interval=0.15, ping_timeout=0.2
    )

    def slow(body, stream):
        yield b"a"
        time.sleep(0.6)  # several ping intervals of silence
        yield b"b"

    srv = RPCServer(keepalive=ka)
    srv.register("slow", slow)
    srv.start()
    try:
        cli = RPCClient(*srv.addr, timeout=5, keepalive=ka)
        assert list(cli.stream("slow")) == [b"a", b"b"]
    finally:
        srv.stop()


def test_dead_server_detected_on_stream():
    """Silence past ping_interval + ping_timeout on a stream raises
    instead of hanging forever (dead-peer detection)."""
    import threading
    import time

    from fabric_tpu.comm.rpc import (
        KeepaliveOptions,
        RPCClient,
        RPCError,
        RPCServer,
    )

    # a server whose keepalive never fires (huge interval) simulates a
    # peer that froze mid-stream
    srv = RPCServer(keepalive=KeepaliveOptions(ping_interval=60.0))
    hang = threading.Event()

    def frozen(body, stream):
        yield b"first"
        hang.wait(10)  # never yields again, never ends

    srv.register("frozen", frozen)
    srv.start()
    try:
        ka = KeepaliveOptions(ping_interval=0.2, ping_timeout=0.2)
        cli = RPCClient(*srv.addr, timeout=5, keepalive=ka)
        it = cli.stream("frozen")
        assert next(it) == b"first"
        t0 = time.time()
        try:
            next(it)
            raise AssertionError("frozen stream must raise")
        except RPCError:
            pass
        assert time.time() - t0 < 5
    finally:
        hang.set()
        srv.stop()
