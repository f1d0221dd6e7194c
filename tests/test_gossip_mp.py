"""Multi-process gossip convergence: three OS processes over the real
TCP transport.  Node A holds blocks it never pushes; node C bootstraps
off B only (never contacts A directly) and starts late.  Everything —
blocks AND identities — must converge purely via the pull machinery
(block pull + state anti-entropy + certstore identity pull), including
transitively through B."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "gossip_worker.py")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_partitioned_peer_converges_via_pull(tmp_path):
    pa, pb, pc = _free_port(), _free_port(), _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    # never the chip: one process owns it, and no test child may claim it
    env["JAX_PLATFORMS"] = "cpu"
    outs = {n: str(tmp_path / f"{n}.json") for n in "ABC"}

    def spawn(name, port, bootstrap, lo, hi):
        return subprocess.Popen(
            [sys.executable, WORKER, f"node{name}", str(port), bootstrap,
             str(lo), str(hi), "3", "3", outs[name]],
            env=env,
            stdout=open(str(tmp_path / f"{name}.log"), "ab"),
            stderr=subprocess.STDOUT,
        )

    # A holds blocks 1..3 (push disabled); B knows A; C knows only B
    procs = [
        spawn("A", pa, "-", 1, 3),
        spawn("B", pb, f"127.0.0.1:{pa}", 1, 0),
    ]
    time.sleep(3)  # C joins late: it must catch up purely by pulling
    procs.append(spawn("C", pc, f"127.0.0.1:{pb}", 1, 0))

    try:
        for p in procs:
            assert p.wait(timeout=90) == 0, "worker did not converge"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    for name in "ABC":
        with open(outs[name]) as f:
            got = json.load(f)
        assert got["blocks"] == [1, 2, 3], (name, got)
        assert got["identities"] == ["nodeA", "nodeB", "nodeC"], (name, got)
