"""Network-world-order integration harness (reference integration/nwo +
integration/raft/cft_test.go): real peer/orderer OS processes on
localhost ports driven through the CLIs, with POSIX-signal fault
injection and restart-recovery assertions."""

import os
import signal
import socket
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# TLS material layout (cryptogen output, relative to the network root)
_ORD_TLS = ("crypto-config/ordererOrganizations/example.com/orderers/"
            "orderer.example.com/tls")
_ORD_TLSCA = ("crypto-config/ordererOrganizations/example.com/tlsca/"
              "tlsca.example.com-cert.pem")
_ORG1_TLSCA = ("crypto-config/peerOrganizations/org1.example.com/tlsca/"
               "tlsca.org1.example.com-cert.pem")
_PEER_TLS = ("crypto-config/peerOrganizations/org1.example.com/peers/"
             "peer0.org1.example.com/tls")
_ADMIN_TLS = ("crypto-config/peerOrganizations/org1.example.com/users/"
              "Admin@org1.example.com/tls")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_listening(port: int, timeout: float = 15.0) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
            return
        except OSError:
            time.sleep(0.1)
    raise TimeoutError(f"nothing listening on {port}")


class Network:
    """Launches cryptogen/configtxgen tooling in-process and the
    peer/orderer daemons as real OS processes (gexec+ifrit role)."""

    def __init__(self, root: str):
        self.root = root
        self.procs: dict[str, subprocess.Popen] = {}
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = REPO + os.pathsep + root
        # never the chip: one process owns it, and no test child may claim it
        self.env["JAX_PLATFORMS"] = "cpu"
        self.orderer_port = _free_port()
        self.peer_port = _free_port()
        self._generate()

    def _generate(self) -> None:
        from fabric_tpu.cmd import configtxgen, cryptogen

        with open(os.path.join(self.root, "crypto-config.yaml"), "w") as f:
            f.write(
                "OrdererOrgs:\n"
                "  - Name: Orderer\n    Domain: example.com\n"
                "    Specs: [{Hostname: orderer}]\n"
                "PeerOrgs:\n"
                "  - Name: Org1\n    Domain: org1.example.com\n"
                "    Template: {Count: 1}\n    Users: {Count: 1}\n"
            )
        with open(os.path.join(self.root, "configtx.yaml"), "w") as f:
            f.write(
                "Organizations:\n"
                "  - Name: OrdererOrg\n    ID: OrdererMSP\n"
                "    MSPDir: crypto-config/ordererOrganizations/example.com/msp\n"
                "  - Name: Org1\n    ID: Org1MSP\n"
                "    MSPDir: crypto-config/peerOrganizations/org1.example.com/msp\n"
                "Profiles:\n"
                "  OneOrg:\n"
                "    Orderer:\n"
                "      OrdererType: solo\n      BatchTimeout: 250ms\n"
                "      BatchSize: {MaxMessageCount: 10}\n"
                "      Organizations: [OrdererOrg]\n"
                "    Application:\n      Organizations: [Org1]\n"
            )
        with open(os.path.join(self.root, "kvcc.py"), "w") as f:
            f.write(
                "from fabric_tpu.chaincode.shim import Chaincode, success, error\n"
                "class KV(Chaincode):\n"
                "    def invoke(self, stub):\n"
                "        op, params = stub.get_function_and_parameters()\n"
                "        if op == 'put':\n"
                "            stub.put_state(params[0].decode(), params[1])\n"
                "            return success()\n"
                "        if op == 'get':\n"
                "            return success(stub.get_state(params[0].decode()) or b'')\n"
                "        return error('bad op')\n"
            )
        cwd = os.getcwd()
        os.chdir(self.root)
        try:
            cryptogen.main(
                ["generate", "--config", "crypto-config.yaml",
                 "--output", "crypto-config"]
            )
            configtxgen.main(
                ["-profile", "OneOrg", "-channelID", "nwoch",
                 "-outputBlock", "nwoch.block"]
            )
        finally:
            os.chdir(cwd)

    # -- daemon management -------------------------------------------------

    def _spawn(self, name: str, args: list[str]) -> None:
        self.procs[name] = subprocess.Popen(
            [sys.executable, "-m"] + args,
            cwd=self.root,
            env=self.env,
            stdout=open(os.path.join(self.root, f"{name}.log"), "ab"),
            stderr=subprocess.STDOUT,
        )

    def start_orderer(self) -> None:
        self._spawn("orderer", [
            "fabric_tpu.cmd.orderer",
            "--listen", f"127.0.0.1:{self.orderer_port}",
            "--root", "orderer-root",
            "--genesis", "nwoch.block",
            "--mspid", "OrdererMSP",
            "--msp-dir",
            "crypto-config/ordererOrganizations/example.com/orderers/"
            "orderer.example.com/msp",
            "--tls-dir", _ORD_TLS,
            "--tls-root", _ORG1_TLSCA,
        ])
        _wait_listening(self.orderer_port)

    def start_peer(self) -> None:
        self._spawn("peer", [
            "fabric_tpu.cmd.peer", "node", "start",
            "--listen", f"127.0.0.1:{self.peer_port}",
            "--root", "peer-root",
            "--mspid", "Org1MSP",
            "--msp-dir",
            "crypto-config/peerOrganizations/org1.example.com/peers/"
            "peer0.org1.example.com/msp",
            "--orderer", f"127.0.0.1:{self.orderer_port}",
            "--chaincode", "kvcc=kvcc:KV",
            "--tls-dir", _PEER_TLS,
            "--tls-root", _ORD_TLSCA,
        ])
        _wait_listening(self.peer_port)

    def kill(self, name: str, sig=signal.SIGKILL) -> None:
        self.procs[name].send_signal(sig)
        self.procs[name].wait(timeout=10)

    def stop_all(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                p.terminate()
        for p in self.procs.values():
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()

    # -- CLI drivers -------------------------------------------------------

    def cli(self, args: list[str]) -> subprocess.CompletedProcess:
        return subprocess.run(
            [sys.executable, "-m"] + args,
            cwd=self.root,
            env=self.env,
            capture_output=True,
            timeout=60,
        )

    @property
    def admin_msp(self) -> str:
        return ("crypto-config/peerOrganizations/org1.example.com/users/"
                "Admin@org1.example.com/msp")

    @property
    def client_tls(self) -> list[str]:
        return ["--tls-dir", _ADMIN_TLS, "--tls-root", _ORD_TLSCA]

    def peer_cli(self, *args: str) -> subprocess.CompletedProcess:
        return self.cli(["fabric_tpu.cmd.peer", *args, *self.client_tls])

    def invoke(self, *cc_args: str) -> subprocess.CompletedProcess:
        argv = ["chaincode", "invoke", "-C", "nwoch", "-n", "kvcc"]
        for a in cc_args:
            argv += ["-a", a]
        argv += [
            "--peer", f"127.0.0.1:{self.peer_port}",
            "--orderer", f"127.0.0.1:{self.orderer_port}",
            "--mspid", "Org1MSP", "--msp-dir", self.admin_msp,
        ]
        return self.peer_cli(*argv)

    def query(self, *cc_args: str) -> bytes:
        argv = ["chaincode", "query", "-C", "nwoch", "-n", "kvcc"]
        for a in cc_args:
            argv += ["-a", a]
        argv += [
            "--peer", f"127.0.0.1:{self.peer_port}",
            "--mspid", "Org1MSP", "--msp-dir", self.admin_msp,
        ]
        out = self.peer_cli(*argv)
        assert out.returncode == 0, out.stderr
        return out.stdout.rstrip(b"\n")

    def height(self) -> int:
        out = self.peer_cli(
            "channel", "getinfo", "-c", "nwoch",
            "--peer", f"127.0.0.1:{self.peer_port}",
        )
        return int(out.stdout.split(b":")[1])

    def wait_height(self, want: int, timeout: float = 20.0) -> None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.height() >= want:
                return
            time.sleep(0.3)
        raise TimeoutError(f"height never reached {want}")


@pytest.fixture(scope="module")
def net(tmp_path_factory):
    n = Network(str(tmp_path_factory.mktemp("nwo")))
    n.start_orderer()
    n.start_peer()
    join = n.peer_cli(
        "channel", "join", "--block", "nwoch.block",
        "--peer", f"127.0.0.1:{n.peer_port}",
    )
    assert join.returncode == 0, join.stderr
    yield n
    n.stop_all()


def test_invoke_commit_query(net):
    out = net.invoke("put", "k1", "v1")
    assert out.returncode == 0, out.stderr
    net.wait_height(2)
    assert net.query("get", "k1") == b"v1"


def test_discover_peers_and_endorsers(net):
    import json

    out = net.cli([
        "fabric_tpu.cmd.discover", "peers", "--channel", "nwoch",
        "--peer", f"127.0.0.1:{net.peer_port}",
        "--mspid", "Org1MSP", "--msp-dir", net.admin_msp,
        *net.client_tls,
    ])
    assert out.returncode == 0, out.stderr
    peers = json.loads(out.stdout)
    assert len(peers) == 1 and "kvcc" in peers[0]["chaincodes"]

    out = net.cli([
        "fabric_tpu.cmd.discover", "endorsers", "--channel", "nwoch",
        "--chaincode", "kvcc",
        "--peer", f"127.0.0.1:{net.peer_port}",
        "--mspid", "Org1MSP", "--msp-dir", net.admin_msp,
        *net.client_tls,
    ])
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout), "endorser selection empty"


def test_orderer_sigkill_and_recovery(net):
    """CFT: SIGKILL the orderer (integration/raft/cft_test.go:118 style),
    restart it, and verify the peer's deliver client reconnects and new
    transactions commit on top of the recovered chain."""
    base = net.height()
    net.kill("orderer", signal.SIGKILL)
    # endorsement still works while ordering is down; broadcast fails
    out = net.invoke("put", "k2", "v2")
    assert out.returncode != 0
    net.start_orderer()  # recovers chain from its block store
    out = net.invoke("put", "k2", "v2-after-restart")
    assert out.returncode == 0, out.stderr
    net.wait_height(base + 1)
    assert net.query("get", "k2") == b"v2-after-restart"


def test_peer_sigterm_restart_recovers_state(net):
    net.invoke("put", "k3", "v3")
    net.wait_height(net.height())
    deadline = time.time() + 15
    while net.query("get", "k3") != b"v3":
        assert time.time() < deadline
        time.sleep(0.3)
    net.kill("peer", signal.SIGTERM)
    net.start_peer()
    # NO re-join: the peer reopens its joined channels at startup
    # (ledgermgmt recovery), and committed state survives the restart
    deadline = time.time() + 15
    while time.time() < deadline:
        if net.query("get", "k3") == b"v3":
            return
        time.sleep(0.3)
    raise AssertionError("state not recovered after peer restart")


def test_wrong_ca_client_rejected_by_peer(net):
    """The network runs mutual TLS: a client presenting a cert from an
    unrelated CA must be refused by the peer's transport (the
    reference's ClientAuthRequired threat model)."""
    import sys as _sys

    _sys.path.insert(0, REPO)
    from fabric_tpu.comm.rpc import RPCClient, RPCError
    from fabric_tpu.comm.tls import credentials_from_ca
    from fabric_tpu.common.crypto import CA

    rogue_ca = CA("tlsca.rogue.example.com", "rogue")
    creds = credentials_from_ca(rogue_ca, "intruder")
    # trust the peer's real TLS CA so only CLIENT auth can fail
    with open(os.path.join(net.root, _ORG1_TLSCA), "rb") as f:
        creds.ca_pems.append(f.read())
    cli = RPCClient("127.0.0.1", net.peer_port, timeout=5, tls=creds)
    with pytest.raises((RPCError, OSError)):
        cli.call("admin.Channels")


def test_plaintext_client_rejected_by_peer(net):
    import sys as _sys

    _sys.path.insert(0, REPO)
    from fabric_tpu.comm.rpc import RPCClient, RPCError

    cli = RPCClient("127.0.0.1", net.peer_port, timeout=5)
    with pytest.raises((RPCError, OSError)):
        cli.call("admin.Channels")
