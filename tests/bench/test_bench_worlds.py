"""The kind of deployment is data: a configuration's file names its
world, its plain reference and its conditions, `benchmarks/control.py`
finds a control by its name, and `benchlib/manifest.py` says what each
file has to give.  Here: the accepted files keep to those contracts, a
name without a file is refused before anything is measured, the
`x509-majority` world is the generator's world of before (a digest
taken from the parent commit's `generator.build_world`), every
reference is independent of the code under test, and building a world
leaves JAX unimported.  The test that a new kind comes in by files
alone sits beside the other rehearsals (`test_bench_rehearsal.py`),
where the kernel is already built."""

import ast
import glob
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchlib import engine
from benchlib.manifest import Manifest, ManifestError

from conftest import BENCH, ROOT

SEED = 2**31 + 99
SIZE = engine.Rehearsal()          # 12 transactions a block, 3 blocks

# taken at the parent commit (69a9a73) from `benchlib.generator.build_world(
# SEED, dict(deployment, block_txs=12), planted, 3)` by the two functions
# below (the state there `generator.planted_state(world)`, key -> (value,
# version)).  Transaction ids are not in it: they hash the creator's
# certificate, whose serial number is random, so they never repeated.
GOLDEN = {
    "majority5-1000tx": ("5ce22da80797776d6e2b8cf4abc02d40512ae68b81a3647409939f70d64b78dd",
                         "3a9a5ee1369aa308645ec38b57309013c2778868e51498057983d8748c7782b2"),
    "solo1-500tx": ("29d1fcddc438d87fd5b0af823dec5862af8730b833352ed26e7c4bfab83d2a92",
                    "49623360f0a694ffe902609c33d27aa8a837007f0749732528c788533712f72f"),
}

# the configurations whose every chaincode is under the channel's default
# endorsement policy: their worlds hand the engine no definitions
DEFAULT_POLICY_ALONE = ("majority5-1000tx", "solo1-500tx", "idemix-nym128",
                        "manyclients-10k", "timeoutcut-2s")

# the configurations accepted before a world could start its ledgers
# populated (PR 43): their worlds have no `setup_blocks`, so every pass's
# ledger is made from the genesis block alone, as it was
STARTS_AT_GENESIS = DEFAULT_POLICY_ALONE + ("keylevel-5org-1000tx",)


def blocks_digest(world) -> str:
    """Planted flags and, of every transaction, channel, nonce, number
    of endorsements, reads and writes."""
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.ledger.rwset import rwset_pb2
    from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2
    from fabric_tpu.protos.peer import proposal_pb2, proposal_response_pb2, transaction_pb2

    h = hashlib.sha256()
    for block_bytes, planted in zip(world.blocks, world.planted):
        block = common_pb2.Block.FromString(block_bytes)
        h.update(repr((block.header.number, [int(f) for f in planted])).encode())
        for env_bytes in block.data.data:
            payload = common_pb2.Payload.FromString(
                common_pb2.Envelope.FromString(env_bytes).payload)
            chdr = common_pb2.ChannelHeader.FromString(payload.header.channel_header)
            shdr = common_pb2.SignatureHeader.FromString(payload.header.signature_header)
            tx = transaction_pb2.Transaction.FromString(payload.data)
            cap = transaction_pb2.ChaincodeActionPayload.FromString(tx.actions[0].payload)
            prp = proposal_response_pb2.ProposalResponsePayload.FromString(
                cap.action.proposal_response_payload)
            results = proposal_pb2.ChaincodeAction.FromString(prp.extension).results
            rw = []
            for ns in rwset_pb2.TxReadWriteSet.FromString(results).ns_rwset:
                kv = kv_rwset_pb2.KVRWSet.FromString(ns.rwset)
                rw.append((ns.namespace, [r.key for r in kv.reads],
                           [(w.key, w.value) for w in kv.writes]))
            h.update(repr((chdr.channel_id, shdr.nonce, len(cap.action.endorsements), rw)).encode())
    return h.hexdigest()


def state_digest(state: dict) -> str:
    """key -> (value, version), the namespace left out."""
    return hashlib.sha256(repr(sorted(state.items())).encode()).hexdigest()


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def _config_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [(c["name"], c["file"]) for c in json.load(f)["configs"]]


@pytest.mark.parametrize("config", sorted(GOLDEN))
def test_the_x509_majority_world_is_the_parents_world(man, config):
    held = man.config({"name": config + ".any", "config": config})
    assert held["world"] == held["reference"] == "x509-majority"
    world = man.world(held)(
        SEED, dict(held["deployment"], block_txs=SIZE.block_txs), held["planted"],
        SIZE.blocks_per_pass,
    )
    assert world.namespaces == ("benchcc",) and world.channel == "benchch"
    state = world.expected_state()
    assert {ns for ns, _key in state} == {"benchcc"}
    assert (blocks_digest(world), state_digest({k: v for (_ns, k), v in state.items()})) \
        == GOLDEN[config]


@pytest.mark.parametrize("name,file", _config_files())
def test_every_configuration_names_a_world_and_a_reference_that_keep_the_contract(man, name, file):
    with open(os.path.join(ROOT, file)) as f:
        held = json.load(f)
    for key, kind in (("world", "worlds"), ("reference", "reference")):
        assert os.path.isfile(os.path.join(BENCH, kind, held[key] + ".py")), (name, key)
    for cond in held.get("conditions", ()):
        assert os.path.isfile(os.path.join(BENCH, "conditions", cond + ".py")), (name, cond)
    man.check_kind(held)
    n = 2
    world = man.world(held)(SEED, dict(held["deployment"], block_txs=SIZE.block_txs),
                            held["planted"], n)
    from fabric_tpu.protos.common import common_pb2

    assert isinstance(world.channel, str) and world.channel
    assert isinstance(world.genesis, common_pb2.Block) and world.genesis.header.number == 0
    # the second optional part: blocks 1..m that populate every ledger
    # before the first measured block, which is then number m + 1
    setup_blocks = getattr(world, "setup_blocks", None)
    if name in STARTS_AT_GENESIS:
        assert setup_blocks is None
    m = len(setup_blocks or ())
    assert [common_pb2.Block.FromString(b).header.number for b in setup_blocks or ()] \
        == list(range(1, m + 1))
    assert [common_pb2.Block.FromString(b).header.number for b in world.blocks] == [m + 1, m + 2]
    assert [len(p) for p in world.planted] == \
        [len(common_pb2.Block.FromString(b).data.data) for b in world.blocks]
    assert world.namespaces and all(isinstance(ns, str) for ns in world.namespaces)
    assert world.lanes_per_block > 0 and isinstance(world.public, dict)
    # the optional part of the contract: a world that carries
    # definitions answers as a peer's lifecycle does.  The five
    # configurations accepted before the door opened (PR 39) carry none,
    # so the engine builds their validators as it built them
    definitions = getattr(world, "definition_provider", None)
    assert definitions is None or callable(definitions.validation_info)
    if name in DEFAULT_POLICY_ALONE:
        assert definitions is None
    state = world.expected_state()
    assert state and all(ns in world.namespaces for ns, _key in state)
    assert all(1 <= blk <= m + n and isinstance(value, bytes)
               for value, (blk, _tx) in state.values())
    # the reference gives flags and a state after every block, from
    # `public`, the deployment's numbers and the blocks alone; of a
    # populated world the state the set-up blocks left and, a block, the
    # rows that changed
    if setup_blocks:
        flags, base, changes = man.reference(held)(
            world.public, held["deployment"], world.blocks, setup_blocks)
        assert base and all(1 <= blk <= m for _value, (blk, _tx) in base.values())
        states = []
        for changed in changes:
            base = {row: v for row, v in {**base, **changed}.items() if v is not None}
            states.append(base)
    else:
        flags, states = man.reference(held)(world.public, held["deployment"], world.blocks)
    assert [list(f) for f in flags] == [list(p) for p in world.planted]
    assert len(states) == n and states[-1] == state and states[0] != states[1]


def test_a_name_without_its_file_is_refused_before_anything_is_measured(tmp_path, man):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    cfg_path = os.path.join(root, "benchmarks", "configs", "solo1-500tx.json")
    with open(cfg_path) as f:
        sound = json.load(f)
    cell = "solo1-500tx.catchup"
    for broken in (dict(sound, world="no-such-world"), dict(sound, reference="no-such-reference"),
                   dict(sound, conditions=["no-such-condition"]),
                   {k: v for k, v in sound.items() if k != "world"},
                   {k: v for k, v in sound.items() if k != "reference"}):
        with open(cfg_path, "w") as f:
            json.dump(broken, f)
        with pytest.raises(ManifestError):
            engine.Cell(root, cell, SEED, 1.0, False)
    with pytest.raises(ManifestError):
        Manifest(root).control("no-such-control")
    # the run itself: exit 2, no result line (the refusal comes before
    # the look for a TPU, so this CPU sees it too)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed", "7",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 2 and "nothing measured" in p.stderr
    assert not [line for line in p.stdout.splitlines() if line.startswith("{")]
    # a file that is there and gives nothing by the name is refused too
    with open(os.path.join(root, "benchmarks", "worlds", "empty.py"), "w") as f:
        f.write('"""no build_world here"""\n')
    with pytest.raises(ManifestError):
        Manifest(root).world({"name": "x", "world": "empty"})


def _reference_is_independent(source: str) -> None:
    """What a reference's file may say: of `fabric_tpu` the protobufs
    and nothing else, nothing of the harness's generator, and nothing
    that loads code by a name or a path the eye cannot follow."""
    tree = ast.parse(source)
    mods, names = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module or "")
        elif isinstance(node, ast.Name):
            names.add(node.id)
        elif isinstance(node, ast.Attribute):
            names.add(node.attr)
    ours = {m for m in mods if m.split(".")[0] == "fabric_tpu"}
    # it decodes the program's wire format, and with the protobufs alone
    assert ours and all(m.startswith("fabric_tpu.protos") for m in ours), ours
    # nor the harness's own generator, which drives the program's code
    assert not {m for m in mods if m.split(".")[0] in ("benchlib", "worlds")}, mods
    assert not {m for m in mods if m.split(".")[0] in ("importlib", "imp", "runpy", "pkgutil")}, mods
    assert not names & {"__import__", "import_module", "spec_from_file_location",
                        "exec", "eval"}, names


REFERENCE_FILES = sorted(glob.glob(os.path.join(BENCH, "reference", "*.py")))


@pytest.mark.parametrize("path", REFERENCE_FILES, ids=os.path.basename)
def test_a_reference_imports_nothing_of_the_code_under_test(path):
    with open(path) as f:
        _reference_is_independent(f.read())


@pytest.mark.parametrize("smuggled", [
    "from fabric_tpu.peer import txvalidator",
    "import fabric_tpu.csp",
    "from benchlib import generator",
    "import importlib.util",
    "m = __import__('fabric_tpu.peer.txvalidator')",
    "import os",                       # and no protobufs at all: it decodes with something else
])
def test_the_independence_check_sees_a_reference_that_is_not(smuggled):
    sound = "from fabric_tpu.protos.common import common_pb2\n"
    _reference_is_independent(sound)
    with pytest.raises(AssertionError):
        _reference_is_independent(smuggled + "\n" + ("" if smuggled == "import os" else sound))


@pytest.mark.parametrize("name,file", _config_files())
def test_a_reference_runs_with_none_of_the_program_loaded(man, name, file, tmp_path):
    """The static look cannot see everything, so: the reference of
    every configuration, run in a process of its own on a world built
    here, and what of `fabric_tpu` that process has loaded."""
    import pickle

    with open(os.path.join(ROOT, file)) as f:
        held = json.load(f)
    world = man.world(held)(SEED, dict(held["deployment"], block_txs=SIZE.block_txs),
                            held["planted"], 2)
    fed = os.path.join(str(tmp_path), "fed.pickle")
    with open(fed, "wb") as f:
        pickle.dump((held, world.public, world.blocks,
                     getattr(world, "setup_blocks", None)), f)
    code = (
        "import json, os, pickle, sys\n"
        f"root = {ROOT!r}\n"
        "sys.path[:0] = [os.path.join(root, 'benchmarks'), root]\n"
        "from benchlib.manifest import Manifest\n"
        f"held, public, blocks, setup_blocks = pickle.load(open({fed!r}, 'rb'))\n"
        "more = (setup_blocks,) if setup_blocks else ()\n"
        "flags = Manifest(root).reference(held)(public, held['deployment'], blocks, *more)[0]\n"
        "ours = sorted(m for m in sys.modules if m.split('.')[0] in ('fabric_tpu', 'jax', 'jaxlib')\n"
        "              and m != 'fabric_tpu' and not m.startswith('fabric_tpu.protos'))\n"
        "print(json.dumps([[list(f) for f in flags], ours]))\n"
    )
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr
    flags, ours = json.loads(p.stdout.splitlines()[-1])
    assert flags == [list(f) for f in world.planted] and ours == []


def test_building_a_world_leaves_jax_unimported():
    code = (
        "import json, os, sys\n"
        f"root = {ROOT!r}\n"
        "sys.path[:0] = [os.path.join(root, 'benchmarks'), root]\n"
        "from benchlib.manifest import Manifest\n"
        "man = Manifest(root)\n"
        "for c in man.doc['configs']:\n"
        "    held = man.config({'name': 'x', 'config': c['name']})\n"
        "    w = man.world(held)(7, dict(held['deployment'], block_txs=10), held['planted'], 1)\n"
        "    w.expected_state()\n"
        "print(json.dumps(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib'))))\n"
    )
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr
    assert json.loads(p.stdout.splitlines()[-1]) == []
