"""chip_smoke.py: the quickest proof that tpu-fabric still starts on the chip.

    python chip_smoke.py [--seed N]

Drives the peer's validate-and-commit path once, on the accelerator,
through the entry points users have, and checks what comes out against
the tree's own plain reference.  Two legs, one after the other, because
one process owns the chip:

* Leg A, a child process: the 3-of-5 stream at full width (5 orgs,
  1000-tx blocks, ~4,000 signature lanes per block) through
  `TxValidator` -> `Committer.store_stream` with `TPUCSP`, against the
  `faithful=True` serial validator on `SWCSP` committing the same
  blocks to a second ledger.  The blocks are the benchmark's own
  (`benchlib.generator`, `X509_CONFIG`) with their corrupted creator
  and endorsement signatures, so a kernel that answered all-true would
  fail.  Host race off: every lane must be sealed by the device.  Then
  a pass at the provider's defaults (the split is printed, not judged),
  the second kernel (an Idemix batch of 128 with one tampered, then a
  block of 128 anonymous Idemix creators through `TxValidator` +
  `Committer.store_block`: flags as planted, no fallback counted), and
  the parked-waiter observation.
* Leg B, real daemons: `python -m fabric_tpu.cmd.orderer` (host only)
  and `python -m fabric_tpu.cmd.peer node start` with
  `CORE_BCCSP_DEFAULT=TPU`, mutual TLS, blocks cut at the orderer
  defaults (500 messages / 2 s).  This process endorses and submits
  ~1,500 invokes over RPC, waits until each is committed VALID, reads a
  sample back through `peer chaincode query`, scrapes /metrics,
  /healthz and /traces, then SIGTERMs the peer, which must exit 0.

This parent is stdlib plus host-only `fabric_tpu` modules and never
imports JAX.  Every chip user is a child with `JAX_PLATFORMS=tpu`, so a
chip that cannot be initialized raises instead of yielding the CPU; no
flag or environment variable lets the smoke pass off-chip.  Every time
printed is a smoke reading (one run, cold or warm as stated), not a
benchmark.

The last line of standard output is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`,
after a `summary:` line with the readings; on any failure the exit
status is non-zero and neither line is printed.
Data comes from `--seed` (leg A's keys, transactions and corrupted
signatures, the Idemix credential); leg B's X.509 and TLS key
material is drawn from the OS generator by the `cryptography` package.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

# The platform every chip user must report.  A constant, not an option.
REQUIRED_PLATFORM = "tpu"

# Leg A: BASELINE.json configuration 4 at full width, as the benchmark's
# configuration deploys and plants it (benchmarks/configs/).
X509_CONFIG, N_BLOCKS = "majority5-1000tx", 4
IDEMIX_SIGS = 128  # above IdemixCSP.DEVICE_CROSSOVER: the 256 bucket
# the benchmark's Idemix deployment: its world builds the smoke's block
IDEMIX_CONFIG = "idemix-nym128"
# Leg B: three full blocks at the orderer's default MaxMessageCount.
DAEMON_TXS = 1500
QUERY_SAMPLE = 8
CHANNEL = "smokech"

LEG_A_TIMEOUT_S = 780.0
LEG_B_TIMEOUT_S = 420.0
ABORT_MARK = "FATAL: exception not rethrown"
RESULT_MARK = "LEG_A_RESULT "


class SmokeFailure(Exception):
    """A failed check: the whole smoke fails."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(text: str) -> None:
    print(text, flush=True)


class Phases:
    """Elapsed seconds per named phase, printed as they end."""

    def __init__(self, prefix: str):
        self.prefix = prefix
        self.seconds: dict[str, float] = {}

    def run(self, name: str, fn, *args, **kwargs):
        t0 = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            dt = time.perf_counter() - t0
            self.seconds[name] = round(self.seconds.get(name, 0.0) + dt, 3)
            say(f"{self.prefix} phase {name}: {dt:.2f} s (smoke reading)")


def chip_env(**extra: str) -> dict:
    """Environment of a child that is to own the chip."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = REQUIRED_PLATFORM
    env.update(extra)
    return env


def host_env(**extra: str) -> dict:
    """Environment of a child that must never claim the chip."""
    env = chip_env(**extra)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("CORE_BCCSP_DEFAULT", None)
    return env


def metric_value(text: str, series: str) -> float:
    """One series of a Prometheus text exposition; 0 where absent."""
    for line in text.splitlines():
        if line.startswith(series + " "):
            return float(line.split()[-1])
    return 0.0


def cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0


# ---------------------------------------------------------------------------
# Leg A, the child: everything below this line up to the parent half
# runs in a process of its own and is the only code here that imports JAX.
# ---------------------------------------------------------------------------


def leg_a_world(seed: int, n_blocks: int, block_txs: int | None = None):
    """Leg A's blocks and the flags planted in them: the benchmark's
    generator over the deployment of `X509_CONFIG` (`block_txs` narrows
    it for the CPU test of this function)."""
    bench = os.path.join(ROOT, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from benchlib import generator

    with open(os.path.join(bench, "configs", f"{X509_CONFIG}.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    deployment = dict(cfg["deployment"])
    if block_txs is not None:
        deployment["block_txs"] = block_txs
    return generator.build_world(seed, deployment, cfg["planted"], n_blocks)


def leg_a_child(seed: int) -> int:
    import hashlib
    import random
    import statistics

    sys.path.insert(0, ROOT)
    phases = Phases("[A]")
    t_start = time.perf_counter()

    # -- the device, before anything expensive --------------------------
    from fabric_tpu.csp import tpu as csp_tpu
    from fabric_tpu.csp.tpu.provider import TPUCSP

    import jax

    device = phases.run("device_init", TPUCSP.device_info)
    say(f"[A] platform: {device['platform']}  device_kind: "
        f"{device['kind']}  devices: {device['count']}  "
        f"local devices: {jax.local_device_count()}")
    check(device["platform"] == REQUIRED_PLATFORM,
          f"leg A is on {device['platform']!r}, not the chip")

    from fabric_tpu import native

    ok = phases.run("native_build", native.available)
    check(ok, f"native library unavailable: {native.load_error()}")
    cache_dir = csp_tpu.compile_cache_dir()
    entries_before = cache_entries(cache_dir)
    say(f"[A] compile cache: {cache_dir}  entries before: {entries_before}")

    # -- the world: blocks with a few corrupted signatures --------------
    from fabric_tpu.common import workpool
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.common.metrics import CSPMetrics, PrometheusProvider
    from fabric_tpu.csp import SWCSP
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.committer import Committer
    from fabric_tpu.peer.txvalidator import TxValidator
    from fabric_tpu.protos.common import common_pb2

    rng = random.Random(seed)
    world = phases.run("build_blocks", leg_a_world, seed, N_BLOCKS)
    sw, genesis, want = SWCSP(), world.genesis, world.planted
    bundle = bundle_from_genesis(genesis, sw)
    n_invalid = sum(f != 0 for row in want for f in row)

    def copies():
        return [common_pb2.Block.FromString(raw) for raw in world.blocks]

    tmp = tempfile.TemporaryDirectory(prefix="chip-smoke-a-")
    providers = []

    def fresh_ledger(name: str):
        provider = LedgerProvider(os.path.join(tmp.name, name))
        providers.append(provider)
        return provider.create(genesis)

    def state_of(ledger):
        rows = list(ledger.get_state_range(world.namespaces[0], "", ""))
        digest = hashlib.sha256(repr(rows).encode()).hexdigest()
        return rows, digest

    # -- the plain reference: faithful serial validator on SWCSP --------
    def reference():
        led = fresh_ledger("reference")
        committer = Committer(
            TxValidator(world.channel, led, bundle, sw, faithful=True), led
        )
        return led, [list(committer.store_block(b)) for b in copies()]

    ref_ledger, ref_flags = phases.run("reference_host", reference)
    check(ref_flags == want,
          "the host reference did not flag exactly the transactions "
          "the generator planted: the smoke's own data is wrong")
    ref_rows, ref_digest = state_of(ref_ledger)
    check(dict(ref_rows) == {
        key: value for (_, key), (value, _) in world.expected_state().items()
    }, "reference state is not the generator's expected state")

    # -- the device, host race OFF --------------------------------------
    class Counting(TPUCSP):
        """Counts what the validator submits and what each flush used,
        independently of the provider's own tally."""

        submitted = 0

        def __init__(self, **kw):
            super().__init__(**kw)
            self.flushes: list[tuple[int, tuple]] = []
            self.last_items: list = []

        def verify_batch_async(self, items, flush=False):
            self.submitted += len(items)
            return super().verify_batch_async(items, flush=flush)

        def _dispatch(self, items):
            res = super()._dispatch(items)
            self.flushes.append((len(items), self.last_dispatch_devices))
            self.last_items = list(items)
            return res

    prov = PrometheusProvider()
    # stall_factor=None: a mask can then come
    # only from the device or from a failure path the tally exposes
    csp = Counting(
        sw=sw, min_device_batch=1, coalesce_lanes=4096,
        stall_factor=None, metrics=CSPMetrics(prov),
    )

    def stream(provider_csp, name: str):
        led = fresh_ledger(name)
        committer = Committer(
            TxValidator(world.channel, led, bundle, provider_csp), led
        )
        flags = [
            list(f) for f in committer.store_stream(iter(copies()), depth=6)
        ]
        provider_csp.drain()
        return led, flags

    from fabric_tpu.csp.tpu import pallas_ec

    def kernels_built() -> int:
        return (pallas_ec._build_call.cache_info().misses
                + pallas_ec._build_call_dedup.cache_info().misses)

    # the same stream twice: the first pass pays every kernel shape's
    # compile (or its cache load), the second is the steady reading
    _, warm_flags = phases.run(
        "device_compile_first_stream", stream, csp, "warmup"
    )
    check(warm_flags == want, "first (compiling) stream: flags differ")
    built = kernels_built()
    dev_ledger, dev_flags = phases.run("device_stream", stream, csp, "device")
    say(f"[A] kernel shapes built: {built} in the first stream, "
        f"{kernels_built() - built} more in the steady one")
    for bno in range(N_BLOCKS):
        diff = [
            (i, ref_flags[bno][i], dev_flags[bno][i])
            for i in range(len(want[bno]))
            if ref_flags[bno][i] != dev_flags[bno][i]
        ]
        check(not diff, f"block {bno + 1}: (tx, reference, device) flags "
                        f"differ: {diff[:10]}")
    dev_rows, dev_digest = state_of(dev_ledger)
    check(dev_ledger.height == ref_ledger.height == 1 + N_BLOCKS,
          "ledger heights differ")
    check(dev_rows == ref_rows, "final state differs from the reference")
    say(f"[A] flags agree on {N_BLOCKS} x {len(want[0])} tx of "
        f"benchlib.generator's {X509_CONFIG} ({n_invalid} planted invalid: "
        f"corrupted creator and endorsement signatures, conflicts); state "
        f"digest {dev_digest[:16]} == reference, {len(dev_rows)} keys")

    tally = csp.lane_tally()
    text = prov.registry.expose()
    failures = metric_value(text, "csp_tpu_device_failures_total")
    say(f"[A] race off: submitted {csp.submitted} lanes, sealed by {tally}; "
        f"device failures {failures:.0f}, breaker trips "
        f"{csp.breaker.trips}")
    check(failures == 0, f"{failures:.0f} device failures with the race off")
    check(csp.breaker.trips == 0 and not csp.breaker.open,
          "the breaker tripped")
    check(csp.submitted >= 2 * N_BLOCKS * world.lanes_per_block * 0.95,
          f"only {csp.submitted} lanes submitted: not the full-width path")
    check(tally["device"] == csp.submitted,
          f"device sealed {tally['device']} of {csp.submitted} lanes")
    check(sum(tally.values()) == tally["device"],
          f"lanes sealed off the device: {tally}")
    check(
        metric_value(text, 'csp_tpu_lanes_total{sealed_by="device"}')
        == tally["device"],
        "the exported lane counter disagrees with the provider's tally",
    )
    by_size: dict[int, set] = {}
    for lanes, devs in csp.flushes:
        by_size.setdefault(lanes, set()).add(
            tuple(str(d) for d in devs) or ("default device",)
        )
    for lanes in sorted(by_size):
        say(f"[A] flush of {lanes} lanes -> devices "
            f"{sorted(by_size[lanes])}")

    # -- does a flush finish with no thread parked in its wait? ---------
    items = csp.last_items
    walls = []
    for _ in range(5):
        t0 = time.perf_counter()
        csp.verify_batch(items)
        walls.append(time.perf_counter() - t0)
    typical = statistics.median(walls)
    idle = max(1.0, 10 * typical)
    res = TPUCSP._dispatch(csp, items)  # no waiter started
    res._on_sealed = None
    time.sleep(idle)
    t0 = time.perf_counter()
    res.collect()
    late = time.perf_counter() - t0
    unparked = late < typical / 2
    say(f"[A] waiter check on {len(items)} lanes: steady flush wall "
        f"{typical * 1e3:.1f} ms (median of 5); dispatched with no waiter, "
        f"left alone {idle:.1f} s, collect then took {late * 1e3:.1f} ms "
        f"-> the execution {'completes' if unparked else 'does NOT complete'}"
        f" without a parked waiter (smoke readings)")

    # -- a short pass at the provider's defaults: the split, not judged -
    prov2 = PrometheusProvider()
    csp2 = Counting(sw=sw, metrics=CSPMetrics(prov2))
    _, flags2 = phases.run("default_settings_stream", stream, csp2, "defaults")
    check(flags2 == ref_flags, "default settings: flags differ")
    tally2 = csp2.lane_tally()
    failures2 = metric_value(
        prov2.registry.expose(), "csp_tpu_device_failures_total"
    )
    say(f"[A] provider defaults (host race on): submitted "
        f"{csp2.submitted} lanes, sealed by {tally2}; device failures "
        f"{failures2:.0f}, flush sizes "
        f"{sorted({n for n, _ in csp2.flushes})}")
    check(sum(tally2.values()) == csp2.submitted,
          "default settings: the tally does not add up to what was "
          "submitted")

    # -- the second kernel: Idemix, one batch above the crossover -------
    from fabric_tpu.csp import IdemixCSP, IdemixVerifyItem
    from fabric_tpu.csp.tpu import bn254_batch, pallas_bn254
    from fabric_tpu.idemix import bn254 as bn
    from fabric_tpu.idemix import signature
    from fabric_tpu.idemix.credential import (
        attribute_to_scalar,
        new_cred_request,
        new_credential,
    )
    from fabric_tpu.idemix.issuer import IssuerKey

    def idemix_world():
        ik = IssuerKey.generate(["OU", "Role"], rng=rng)
        sk = bn.rand_zr(rng)
        req = new_cred_request(sk, b"nonce", ik.ipk, rng=rng)
        cred = new_credential(
            ik, req,
            [attribute_to_scalar("org1"), attribute_to_scalar(2)], rng=rng,
        )
        bad = rng.randrange(IDEMIX_SIGS)
        out = []
        for i in range(IDEMIX_SIGS):
            msg = b"smoke-%d" % i
            sig = signature.new_signature(cred, sk, ik.ipk, msg, rng=rng)
            out.append(IdemixVerifyItem(sig, b"tampered" if i == bad else msg))
        return ik.ipk, out, bad

    ipk, id_items, bad = phases.run("idemix_build", idemix_world)
    host_mask = signature.verify_batch(
        [i.sig for i in id_items], ipk, [i.msg for i in id_items], rng=rng
    )
    check(host_mask == [i != bad for i in range(IDEMIX_SIGS)],
          "idemix host verifier did not reject exactly the tampered one")
    idemix = IdemixCSP(rng=rng)
    dev_mask = phases.run(
        "idemix_compile_first_batch", idemix.verify_batch, id_items, ipk
    )
    check(dev_mask == host_mask, "idemix: device mask differs from host")
    dev_mask = phases.run(
        "idemix_second_batch", idemix.verify_batch, id_items, ipk
    )
    check(dev_mask == host_mask, "idemix: second device mask differs")
    check(not bn254_batch._PALLAS_FAILURES,
          f"pallas_bn254 failed: {bn254_batch._PALLAS_FAILURES}")
    check(pallas_bn254._build_call.cache_info().currsize >= 1,
          "the Idemix batch never reached the Pallas kernel")
    say(f"[A] idemix: {IDEMIX_SIGS} signatures, 1 tampered, device mask == "
        "host mask, _PALLAS_FAILURES empty")

    # -- the path a peer takes: a block of anonymous creators through
    # TxValidator + Committer.store_block, its credential proofs and
    # pseudonym signatures as one launch of the BN254 kernel --------
    def idemix_block():
        sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
        from benchlib.manifest import Manifest

        man = Manifest(ROOT)
        held = man.config({"name": "smoke", "config": IDEMIX_CONFIG})
        return held, man.world(held)(
            seed, held["deployment"], held["planted"], 1
        )

    held, iworld = phases.run("idemix_block_build", idemix_block)
    from fabric_tpu.common.channelconfig import bundle_from_genesis

    ibundle = bundle_from_genesis(iworld.genesis, csp)
    iprovider = LedgerProvider(os.path.join(tmp.name, "idemix"))
    providers.append(iprovider)
    iledger = iprovider.create(iworld.genesis)
    before = csp.idemix.tally()
    committer = Committer(
        TxValidator(iworld.channel, iledger, ibundle, csp), iledger
    )
    blk = common_pb2.Block.FromString(iworld.blocks[0])
    iflags = phases.run("idemix_block_store", committer.store_block, blk)
    check([int(f) for f in iflags] == [int(f) for f in iworld.planted[0]],
          "idemix block: flags differ from the planted ones")
    after = csp.idemix.tally()
    reached = len(iflags) - iworld.refused_at_deserialise[0]
    moved = {
        k: after["items"].get(k, 0) - before["items"].get(k, 0)
        for k in after["items"]
    }
    check(after["fallbacks"] == before["fallbacks"] == {},
          f"idemix block: a fallback was counted: {after['fallbacks']}")
    check(moved == {"proof.pallas": reached, "nym.pallas": reached},
          f"idemix block: items did not all run on the kernel: {moved}")
    say(f"[A] idemix block: {len(iflags)} anonymous creators through "
        f"TxValidator + store_block, {reached} proofs + {reached} "
        f"pseudonym signatures on the kernel (buckets "
        f"{sorted(after['batches'])}), flags as planted, no fallback")

    # -- leave through normal interpreter shutdown ----------------------
    csp.close()
    csp2.close()
    workpool.shutdown()
    for provider in providers:
        provider.close()
    tmp.cleanup()
    entries_after = cache_entries(cache_dir)
    say(f"[A] compile cache entries after: {entries_after} "
        f"(+{entries_after - entries_before})")
    result = {
        "device": device,
        "local_devices": jax.local_device_count(),
        "cache_dir": cache_dir,
        "cache_entries": [entries_before, entries_after],
        "phase_s": phases.seconds,
        "setup_compile_s": round(sum(
            phases.seconds[k] for k in (
                "device_init", "native_build",
                "device_compile_first_stream", "idemix_compile_first_batch",
            )
        ), 3),
        "total_s": round(time.perf_counter() - t_start, 3),
        "lanes_race_off": tally,
        "lanes_defaults": tally2,
        "flush_wall_ms": round(typical * 1e3, 3),
        "unparked_collect_ms": round(late * 1e3, 3),
        "unparked_flush_completes": unparked,
        "flush_devices": {
            str(n): sorted(map(list, d)) for n, d in sorted(by_size.items())
        },
    }
    say(RESULT_MARK + json.dumps(result, sort_keys=True))
    return 0


# ---------------------------------------------------------------------------
# The parent half: stdlib and host-only fabric_tpu modules.
# ---------------------------------------------------------------------------


def run_child(argv: list[str], env: dict, timeout: float, tag: str):
    """Run a child to its end, echoing its output; (status, lines)."""
    proc = subprocess.Popen(
        argv, env=env, cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, errors="replace",
    )
    killer = threading.Timer(timeout, proc.kill)
    killer.start()
    lines = []
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            lines.append(line)
            if not line.startswith(RESULT_MARK):
                say(line if line.startswith(tag) else f"{tag} | {line}")
        status = proc.wait()
    finally:
        killer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    return status, lines


def leg_a(seed: int) -> dict:
    status, lines = run_child(
        [sys.executable, os.path.abspath(__file__), "--leg-a-child",
         "--seed", str(seed)],
        chip_env(), LEG_A_TIMEOUT_S, "[A]",
    )
    check(not any(ABORT_MARK in ln for ln in lines),
          f"leg A printed {ABORT_MARK!r}")
    check(status == 0, f"leg A's child exited with status {status}")
    results = [ln for ln in lines if ln.startswith(RESULT_MARK)]
    check(len(results) == 1, "leg A printed no result")
    return json.loads(results[0][len(RESULT_MARK):])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_listening(port: int, proc: subprocess.Popen, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        check(proc.poll() is None,
              f"a daemon exited with status {proc.returncode} before "
              f"listening on {port}")
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
            return
        except OSError:
            time.sleep(0.2)
    raise SmokeFailure(f"nothing listening on {port} after {timeout:.0f} s")


def http_get(port: int, path: str) -> tuple[int, str]:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10
        ) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


_CRYPTO = "crypto-config"
_ORD = f"{_CRYPTO}/ordererOrganizations/example.com"
_ORG1 = f"{_CRYPTO}/peerOrganizations/org1.example.com"
_ORD_TLS = f"{_ORD}/orderers/orderer.example.com/tls"
_ORD_MSP = f"{_ORD}/orderers/orderer.example.com/msp"
_ORD_TLSCA = f"{_ORD}/tlsca/tlsca.example.com-cert.pem"
_ORG1_TLSCA = f"{_ORG1}/tlsca/tlsca.org1.example.com-cert.pem"
_PEER_TLS = f"{_ORG1}/peers/peer0.org1.example.com/tls"
_PEER_MSP = f"{_ORG1}/peers/peer0.org1.example.com/msp"
_ADMIN_TLS = f"{_ORG1}/users/Admin@org1.example.com/tls"
_ADMIN_MSP = f"{_ORG1}/users/Admin@org1.example.com/msp"

_KVCC = '''\
from fabric_tpu.chaincode.shim import Chaincode, success, error


class KV(Chaincode):
    def invoke(self, stub):
        op, params = stub.get_function_and_parameters()
        if op == "put":
            stub.put_state(params[0].decode(), params[1])
            return success()
        if op == "get":
            return success(stub.get_state(params[0].decode()) or b"")
        return error("bad op")
'''


def generate_network(root: str) -> None:
    """cryptogen + configtxgen, the way tests/test_nwo.py does it; no
    BatchSize/BatchTimeout given, so configtxgen's upstream defaults
    (MaxMessageCount 500, BatchTimeout 2 s) are what the orderer cuts at."""
    from fabric_tpu.cmd import configtxgen, cryptogen

    with open(os.path.join(root, "crypto-config.yaml"), "w") as f:
        f.write(
            "OrdererOrgs:\n"
            "  - Name: Orderer\n    Domain: example.com\n"
            "    Specs: [{Hostname: orderer}]\n"
            "PeerOrgs:\n"
            "  - Name: Org1\n    Domain: org1.example.com\n"
            "    Template: {Count: 1}\n    Users: {Count: 1}\n"
        )
    with open(os.path.join(root, "configtx.yaml"), "w") as f:
        f.write(
            "Organizations:\n"
            "  - Name: OrdererOrg\n    ID: OrdererMSP\n"
            f"    MSPDir: {_ORD}/msp\n"
            "  - Name: Org1\n    ID: Org1MSP\n"
            f"    MSPDir: {_ORG1}/msp\n"
            "Profiles:\n"
            "  OneOrg:\n"
            "    Orderer:\n"
            "      OrdererType: solo\n"
            "      Organizations: [OrdererOrg]\n"
            "    Application:\n      Organizations: [Org1]\n"
        )
    with open(os.path.join(root, "kvcc.py"), "w") as f:
        f.write(_KVCC)
    cwd = os.getcwd()
    os.chdir(root)
    try:
        cryptogen.main(["generate", "--config", "crypto-config.yaml",
                        "--output", _CRYPTO])
        configtxgen.main(["-profile", "OneOrg", "-channelID", CHANNEL,
                          "-outputBlock", f"{CHANNEL}.block"])
    finally:
        os.chdir(cwd)


def leg_b(seed: int, root: str, procs: dict) -> dict:
    """The daemon leg.  `procs` collects every process started, so the
    caller can stop whatever is left on any exit path."""
    import random
    from concurrent.futures import ThreadPoolExecutor

    from fabric_tpu.cmd.common import endorse, load_signer, submit
    from fabric_tpu.comm import RPCClient
    from fabric_tpu.comm.tls import credentials_from_files
    from fabric_tpu.common.deliver import make_seek_info_envelope
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.peer import events_pb2
    from fabric_tpu.protos.peer import transaction_pb2 as V

    phases = Phases("[B]")
    deadline = time.monotonic() + LEG_B_TIMEOUT_S
    phases.run("cryptogen_configtxgen", generate_network, root)
    orderer_port, peer_port, ops_port = free_port(), free_port(), free_port()

    def spawn(name: str, args: list[str], env: dict) -> subprocess.Popen:
        log = open(os.path.join(root, f"{name}.log"), "ab")
        try:
            procs[name] = subprocess.Popen(
                [sys.executable, "-m"] + args, cwd=root, env=env,
                stdout=log, stderr=subprocess.STDOUT,
            )
        finally:
            log.close()
        return procs[name]

    def log_of(name: str) -> str:
        with open(os.path.join(root, f"{name}.log"), errors="replace") as f:
            return f.read()

    pypath = ROOT + os.pathsep + root

    def start_daemons():
        orderer = spawn("orderer", [
            "fabric_tpu.cmd.orderer",
            "--listen", f"127.0.0.1:{orderer_port}",
            "--root", "orderer-root", "--genesis", f"{CHANNEL}.block",
            "--mspid", "OrdererMSP", "--msp-dir", _ORD_MSP,
            "--tls-dir", _ORD_TLS, "--tls-root", _ORG1_TLSCA,
        ], host_env(PYTHONPATH=pypath))
        wait_listening(orderer_port, orderer, 60)
        # the one process of this leg that owns the chip; the trace ring
        # makes its tpu.dispatch spans readable on /traces
        peer = spawn("peer", [
            "fabric_tpu.cmd.peer", "node", "start",
            "--listen", f"127.0.0.1:{peer_port}", "--root", "peer-root",
            "--mspid", "Org1MSP", "--msp-dir", _PEER_MSP,
            "--orderer", f"127.0.0.1:{orderer_port}",
            "--chaincode", "kvcc=kvcc:KV",
            "--operations-port", str(ops_port),
            "--tls-dir", _PEER_TLS, "--tls-root", _ORD_TLSCA,
        ], chip_env(PYTHONPATH=pypath, CORE_BCCSP_DEFAULT="TPU",
                    FABRIC_TPU_TRACE="65536"))
        wait_listening(peer_port, peer, 180)
        return peer

    peer = phases.run("start_orderer_and_tpu_peer", start_daemons)
    marks = [ln for ln in log_of("peer").splitlines()
             if ln.startswith("bccsp TPU device: ")]
    check(len(marks) == 1, "the peer did not name its device at start-up")
    device = json.loads(marks[0].split(": ", 1)[1])
    say(f"[B] peer daemon platform: {device['platform']}  device_kind: "
        f"{device['kind']}  devices: {device['count']}")
    check(device["platform"] == REQUIRED_PLATFORM,
          f"the peer daemon is on {device['platform']!r}, not the chip")

    def in_root(rel: str) -> str:
        return os.path.join(root, rel)

    tls = credentials_from_files(
        in_root(f"{_ADMIN_TLS}/client.crt"), in_root(f"{_ADMIN_TLS}/client.key"),
        [in_root(f"{_ADMIN_TLS}/ca.crt"), in_root(_ORD_TLSCA)],
    )
    signer = load_signer(in_root(_ADMIN_MSP), "Org1MSP")
    peer_ep, orderer_ep = ("127.0.0.1", peer_port), ("127.0.0.1", orderer_port)
    with open(in_root(f"{CHANNEL}.block"), "rb") as f:
        joined = RPCClient(*peer_ep, tls=tls).call("admin.JoinChannel", f.read())
    check(joined.decode() == CHANNEL, "channel join failed")

    # -- ~1,500 invokes over RPC, not one CLI process per transaction ---
    rng = random.Random(seed)
    kv = {f"k{seed}-{i}": b"v%d-%d" % (i, rng.randrange(1 << 30))
          for i in range(DAEMON_TXS)}

    def endorse_one(item):
        key, value = item
        prop, resps = endorse(
            [peer_ep], signer, CHANNEL, "kvcc",
            [b"put", key.encode(), value], tls=tls,
        )
        check(all(200 <= r.response.status < 400 for r in resps),
              f"endorsement of {key} failed: {resps[0].response.message}")
        return prop, resps

    def submit_one(endorsed) -> str:
        prop, resps = endorsed
        status = submit(orderer_ep, signer, prop, resps, tls=tls)
        check(status == common_pb2.SUCCESS, f"broadcast status {status}")
        hdr = common_pb2.Header.FromString(prop.header)
        return common_pb2.ChannelHeader.FromString(hdr.channel_header).tx_id

    # endorse everything first, then broadcast in one burst: the writes
    # are blind puts, and a burst is what fills 500-message blocks
    # before the 2 s batch timeout cuts them short
    with ThreadPoolExecutor(max_workers=8) as pool:
        endorsed = phases.run(
            "endorse", lambda: list(pool.map(endorse_one, kv.items()))
        )
        txids = phases.run(
            "submit", lambda: list(pool.map(submit_one, endorsed))
        )
    check(len(set(txids)) == DAEMON_TXS, "duplicate transaction ids")

    def committed() -> tuple[dict, list[int]]:
        """txid -> validation code over every block the peer holds past
        genesis, through its filtered deliver service."""
        height = int(RPCClient(*peer_ep, tls=tls).call(
            "admin.Height", CHANNEL.encode()).decode())
        codes: dict = {}
        sizes: list[int] = []
        if height > 1:
            env = make_seek_info_envelope(CHANNEL, 1, height - 1, signer=signer)
            for raw in RPCClient(*peer_ep, timeout=30.0, tls=tls).stream(
                "deliver.DeliverFiltered", env.SerializeToString()
            ):
                resp = events_pb2.DeliverResponse.FromString(raw)
                if resp.WhichOneof("Type") != "filtered_block":
                    continue
                ftxs = resp.filtered_block.filtered_transactions
                sizes.append(len(ftxs))
                for ftx in ftxs:
                    codes[ftx.txid] = ftx.tx_validation_code
        return codes, sizes

    def wait_committed():
        while True:
            check(peer.poll() is None,
                  f"the peer exited with status {peer.returncode}")
            codes, sizes = committed()
            if all(t in codes for t in txids):
                return codes, sizes
            check(time.monotonic() < deadline,
                  f"only {sum(t in codes for t in txids)} of {DAEMON_TXS} "
                  "transactions committed in time")
            time.sleep(1.0)

    codes, sizes = phases.run("wait_all_committed", wait_committed)
    invalid = {t: codes[t] for t in txids if codes[t] != V.VALID}
    check(not invalid, f"{len(invalid)} transactions not VALID: "
                       f"{list(invalid.items())[:5]}")
    say(f"[B] {DAEMON_TXS} invokes committed VALID in blocks of {sizes} "
        "(orderer defaults: 500 messages / 2 s)")
    check(max(sizes) <= 500, "a block exceeds the default MaxMessageCount")
    check(sum(sizes) == DAEMON_TXS, "the blocks hold other transactions")

    # -- read a sample back through `peer chaincode query` --------------
    def query_sample():
        for key in rng.sample(sorted(kv), QUERY_SAMPLE):
            out = subprocess.run(
                [sys.executable, "-m", "fabric_tpu.cmd.peer", "chaincode",
                 "query", "-C", CHANNEL, "-n", "kvcc", "-a", "get", "-a", key,
                 "--peer", f"127.0.0.1:{peer_port}", "--mspid", "Org1MSP",
                 "--msp-dir", _ADMIN_MSP, "--tls-dir", _ADMIN_TLS,
                 "--tls-root", _ORD_TLSCA],
                cwd=root, env=host_env(PYTHONPATH=pypath),
                capture_output=True, timeout=120,
            )
            check(out.returncode == 0, f"query {key}: {out.stderr[-300:]!r}")
            check(out.stdout.rstrip(b"\n") == kv[key],
                  f"query {key} read {out.stdout!r}, wrote {kv[key]!r}")

    phases.run("query_sample", query_sample)
    say(f"[B] {QUERY_SAMPLE} sampled keys read back through "
        "`peer chaincode query` equal what was written")

    # -- what the daemon says about its own device path -----------------
    status, metrics = http_get(ops_port, "/metrics")
    check(status == 200, f"/metrics answered {status}")
    lanes = {
        kind: float(value) for kind, value in re.findall(
            r'^csp_tpu_lanes_total\{sealed_by="(\w+)"\} (\S+)$', metrics, re.M
        )
    }
    failures = metric_value(metrics, "csp_tpu_device_failures_total")
    trips = metric_value(metrics, "csp_tpu_breaker_trips_total")
    breaker = metric_value(metrics, "csp_tpu_breaker_state")
    say(f"[B] /metrics: lanes sealed by {lanes}; device failures "
        f"{failures:.0f}, breaker trips {trips:.0f}, breaker state "
        f"{breaker:.0f}")
    check(failures == 0 and not lanes.get("failover"),
          "the daemon's device path failed")
    check(trips == 0 and breaker == 0 and not lanes.get("breaker"),
          "the daemon's breaker is or was open")
    check(lanes.get("device", 0) > 0,
          "the device sealed no lane in the daemon")
    status, health = http_get(ops_port, "/healthz?detail=1")
    check(status == 200, f"/healthz answered {status}: {health}")
    status, traces = http_get(ops_port, "/traces")
    check(status == 200, f"/traces answered {status}")
    flushes = sorted(
        ev["args"]["lanes"] for ev in json.loads(traces)["traceEvents"]
        if ev.get("name") == "tpu.dispatch" and "lanes" in ev.get("args", {})
    )
    say(f"[B] /traces: tpu.dispatch flushes of {flushes} lanes; /healthz OK")
    check(flushes and flushes[-1] >= 256,
          "no device flush of 256 lanes or more in the daemon")

    # -- SIGTERM: the peer must leave through normal shutdown, status 0 -
    def stop_peer():
        peer.send_signal(signal.SIGTERM)
        return peer.wait(timeout=90)

    status = phases.run("peer_sigterm_exit", stop_peer)
    text = log_of("peer")
    check(ABORT_MARK not in text, f"the peer printed {ABORT_MARK!r}")
    check(status == 0, f"the peer exited with status {status} on SIGTERM:\n"
                       + text[-1500:])
    procs["orderer"].send_signal(signal.SIGTERM)
    status = procs["orderer"].wait(timeout=30)
    check(status == 0, f"the orderer exited with status {status}")
    say("[B] peer and orderer exited 0 on SIGTERM")
    return {
        "device": device,
        "phase_s": phases.seconds,
        "block_sizes": sizes,
        "lanes": lanes,
        "dispatch_lanes": flushes,
    }


def stop_all(procs: dict) -> None:
    for p in procs.values():
        if p.poll() is None:
            p.terminate()
    for p in procs.values():
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--leg-a-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.leg_a_child:
        return leg_a_child(args.seed)

    t0 = time.perf_counter()
    ambient = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    procs: dict = {}
    work = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        check(not ambient or REQUIRED_PLATFORM in ambient.split(","),
              f"JAX_PLATFORMS={ambient!r} excludes the chip; this smoke "
              "runs on the accelerator only")
        a = leg_a(args.seed)
        # only now, with the chip free again and the native library
        # built by leg A's child, the daemons
        b = leg_b(args.seed, work, procs)
        check(a["device"] == b["device"],
              f"the legs saw different devices: {a['device']} {b['device']}")
        check("jax" not in sys.modules, "the smoke's parent imported jax")
    except Exception as exc:
        # the one boundary: whatever went wrong, say so, show the
        # daemons' last words, stop them, and leave non-zero
        if not isinstance(exc, SmokeFailure):
            traceback.print_exc()
        say(f"chip_smoke: FAILED: {type(exc).__name__}: {exc}")
        for name in procs:
            path = os.path.join(work, f"{name}.log")
            if os.path.exists(path):
                with open(path, errors="replace") as f:
                    say(f"--- tail of {name}.log ---\n{f.read()[-3000:]}")
        return 1
    finally:
        stop_all(procs)
        shutil.rmtree(work, ignore_errors=True)

    entries = cache_entries(a["cache_dir"])
    total = time.perf_counter() - t0
    say(f"compile cache {a['cache_dir']}: {a['cache_entries'][0]} entries "
        f"before, {entries} after both legs")
    say(f"leg A {a['total_s']:.1f} s, of which set-up and compile "
        f"{a['setup_compile_s']:.1f} s; leg B "
        f"{sum(b['phase_s'].values()):.1f} s; whole smoke {total:.1f} s "
        "(smoke readings, not a benchmark)")
    say("summary: " + json.dumps({
        "seed": args.seed,
        "elapsed_s": round(total, 1),
        "cache": {"dir": a["cache_dir"],
                  "entries_before": a["cache_entries"][0],
                  "entries_after": entries},
        "leg_a": {k: a[k] for k in (
            "phase_s", "setup_compile_s", "lanes_race_off",
            "lanes_defaults", "flush_wall_ms", "unparked_collect_ms",
            "unparked_flush_completes", "flush_devices", "local_devices",
        )},
        "leg_b": {k: b[k] for k in (
            "phase_s", "block_sizes", "lanes", "dispatch_lanes",
        )},
    }, sort_keys=True))
    say(json.dumps({"ok": True, "device": a["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
