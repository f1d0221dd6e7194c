"""Host-only pins for the on-chip bring-up (ISSUE 21).  chip_smoke.py is
the proof on hardware; these keep its preconditions true on CPU, and
all of them are cheap: nothing here compiles a kernel."""

from __future__ import annotations

import ast
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what only leg A's child runs: it alone may import jax, and its world
# comes from `benchmarks/`, which the parent never puts on its path
CHILD_ONLY = {"leg_a_child", "leg_a_world"}


def _parent_side_imports() -> list[str]:
    """Every import statement chip_smoke.py's parent half can execute:
    module level plus the bodies of all functions but the leg A child."""
    with open(os.path.join(ROOT, "chip_smoke.py"), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    found: list[str] = []

    def walk(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.FunctionDef) and child.name in CHILD_ONLY:
                continue
            if isinstance(child, (ast.Import, ast.ImportFrom)):
                found.append(ast.unparse(child))
            walk(child)

    walk(tree)
    return found


def test_smoke_parent_never_imports_jax():
    imports = _parent_side_imports()
    assert any("fabric_tpu.cmd" in line for line in imports), imports
    code = "\n".join(
        ["import sys", "import chip_smoke"]
        + [line for line in imports if "__future__" not in line]
        + ["assert 'jax' not in sys.modules, 'the parent half pulls in jax'",
           "assert chip_smoke.REQUIRED_PLATFORM == 'tpu'",
           "print('PARENT-JAX-FREE')"]
    )
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PARENT-JAX-FREE" in proc.stdout


def test_smoke_refuses_an_environment_without_the_chip():
    """Held to the CPU the smoke fails before it starts anything, and
    prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_compile_cache_rule(monkeypatch, tmp_path):
    import jax

    from fabric_tpu.csp import tpu as csp_tpu

    fixed = os.path.join(ROOT, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    assert before is None  # held to the CPU, the suite keeps no cache

    # the environment's directory wins and JAX's config is left alone
    monkeypatch.setenv(csp_tpu.COMPILE_CACHE_ENV, str(tmp_path))
    assert csp_tpu.compile_cache_dir() == str(tmp_path)
    csp_tpu._place_compile_cache()
    assert jax.config.jax_compilation_cache_dir is None

    # unset: the fixed directory inside the checkout ...
    monkeypatch.delenv(csp_tpu.COMPILE_CACHE_ENV)
    assert csp_tpu.compile_cache_dir() == fixed
    csp_tpu._place_compile_cache()  # ... but never for a CPU-held process
    assert jax.config.jax_compilation_cache_dir is None
    platforms = jax.config.jax_platforms
    try:
        jax.config.update("jax_platforms", "tpu")
        csp_tpu._place_compile_cache()
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_platforms", platforms)
        jax.config.update("jax_compilation_cache_dir", before)

    # and a second process computes the very same path
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    env.pop(csp_tpu.COMPILE_CACHE_ENV, None)
    out = subprocess.run(
        [sys.executable, "-c",
         "from fabric_tpu.csp import tpu; print(tpu.compile_cache_dir())"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == fixed


def test_no_cache_path_from_tempfile_pid_or_clock():
    with open(os.path.join(ROOT, "fabric_tpu", "csp", "tpu", "__init__.py"),
              encoding="utf-8") as f:
        names = {
            node.id if isinstance(node, ast.Name) else node.attr
            for node in ast.walk(ast.parse(f.read()))
            if isinstance(node, (ast.Name, ast.Attribute))
        }
    assert not names & {"tempfile", "getpid", "time", "mkdtemp", "uuid"}


def test_peer_node_stop_closes_csp_and_work_pool():
    from fabric_tpu.common import workpool
    from fabric_tpu.csp import SWCSP
    from fabric_tpu.node.peer_node import PeerNode

    class ClosableCSP(SWCSP):
        closed = 0

        def close(self):
            self.closed += 1

    csp = ClosableCSP()
    node = PeerNode(None, csp, None, port=0)
    node.start()
    workpool.default_pool()  # as a commit's parallel collect would
    assert workpool._pool is not None
    node.stop()
    assert csp.closed == 1
    assert workpool._pool is None
    node.stop()  # idempotent: the second call closes nothing again
    assert csp.closed == 1


def test_native_load_error_is_recorded(monkeypatch):
    """A failed build keeps the Python fallback but says why."""
    from fabric_tpu import native

    def no_compiler(*args, **kwargs):
        raise subprocess.CalledProcessError(
            1, "g++", stderr=b"marshal.cc:1: error: no such compiler"
        )

    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_error", None)
    monkeypatch.setattr(native, "_LIB", native._LIB + ".absent")
    monkeypatch.setattr(native.subprocess, "run", no_compiler)
    assert not native.available()
    assert "no such compiler" in native.load_error()
    assert native.marshal_batch(b"", b"", b"", b"", [0]) is None


def test_the_smokes_x509_blocks_are_the_benchmarks_world_through_the_validator(tmp_path):
    """Leg A's first step on the chip, here on the host at a small
    size: the function the smoke builds its blocks with
    (`benchlib.generator` over the deployment of `X509_CONFIG`) through
    TxValidator over SWCSP into a ledger gives exactly the planted
    flags (corrupted creator and endorsement signatures, conflicting
    pairs) and the generator's expected state."""
    import chip_smoke
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.csp import SWCSP
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.committer import Committer
    from fabric_tpu.peer.txvalidator import TxValidator
    from fabric_tpu.protos.common import common_pb2

    world = chip_smoke.leg_a_world(30, 2, block_txs=12)
    assert [len(row) for row in world.planted] == [12, 12]
    assert all(len(set(row)) == 4 for row in world.planted)  # every kind planted
    sw = SWCSP()
    ledger = LedgerProvider(str(tmp_path)).create(world.genesis)
    committer = Committer(
        TxValidator(world.channel, ledger,
                    bundle_from_genesis(world.genesis, sw), sw), ledger)
    flags = [
        list(committer.store_block(common_pb2.Block.FromString(raw)))
        for raw in world.blocks
    ]
    assert flags == world.planted
    assert dict(ledger.get_state_range(world.namespaces[0], "", "")) == {
        key: value for (_, key), (value, _) in world.expected_state().items()
    }


def test_the_smokes_idemix_block_is_the_benchmarks_world_through_the_validator(tmp_path):
    """Leg A's last step on the chip, here on the host path at a small
    size: the block the smoke builds (the benchmark's Idemix world, by
    the name `IDEMIX_CONFIG`) through TxValidator + store_block gives
    the planted flags, and the provider's tally is what the smoke
    reads (on the chip: only `*.pallas` items and no fallback)."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        from benchlib.manifest import Manifest
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmarks"))
    import chip_smoke
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.csp.tpu.provider import TPUCSP
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.committer import Committer
    from fabric_tpu.peer.txvalidator import TxValidator
    from fabric_tpu.protos.common import common_pb2

    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        man = Manifest(ROOT)
        held = man.config({"name": "smoke", "config": chip_smoke.IDEMIX_CONFIG})
        world = man.world(held)(
            21, dict(held["deployment"], block_txs=16), held["planted"], 1)
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmarks"))
    csp = TPUCSP()
    try:
        ledger = LedgerProvider(str(tmp_path)).create(world.genesis)
        committer = Committer(
            TxValidator(world.channel, ledger,
                        bundle_from_genesis(world.genesis, csp), csp), ledger)
        flags = committer.store_block(common_pb2.Block.FromString(world.blocks[0]))
        assert [int(f) for f in flags] == [int(f) for f in world.planted[0]]
        reached = 16 - world.refused_at_deserialise[0]
        tally = csp.idemix.tally()
        assert tally["items"] == {"proof.host": reached, "nym.host": reached}
        assert tally["fallbacks"] == {"below_crossover": 1}
    finally:
        csp.close()
