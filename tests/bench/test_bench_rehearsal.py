"""One rehearsal of a whole run on the CPU at a tiny size, behind the
test-only entry (`python benchmarks/run.py` itself refuses a CPU): the
open-loop cell with tracing on, then the same run with the program
broken underneath (a control of `benchmarks/controls/` patches the
program's own classes), which must come out as not correct.  Then a
new KIND of deployment, added to a copy of the benchmark by new files
and entries alone: a toy world, reference, condition and control; a
per-layer metric appended to that copy with its reader, and the copy
held to the manifest's own tests; and a toy world of two chaincodes
under a policy each, which come to the validator through the world's
`definition_provider`.

Every flush is 20 lanes, so one kernel shape is built in this process.
No number of a CPU run is a device number: the test reads counts,
keys and the verdict, never a time.
"""

import inspect
import json
import os
import shutil

import pytest

import test_bench_manifest as contract
from benchlib import engine
from benchlib.manifest import Manifest

from conftest import ROOT

TINY = engine.Rehearsal(block_txs=10, blocks_per_pass=1)
CELL = "solo1-500tx.steady"


@pytest.fixture(scope="module")
def sound():
    return engine.run_cell(ROOT, CELL, 2**31 + 99, 1.0, True, rehearsal=TINY)


def test_a_sound_run_is_correct_and_reports_the_cells_per_layer_metrics(sound):
    assert sound["correct"] is True
    assert sound["attempted"] >= 4 and sound["failed"] == 0
    assert set(sound) >= {"correct", "attempted", "failed", "metrics", "device", "breakdown"}
    assert set(sound["device"]) >= {"platform", "kind", "count", "memory_peak_bytes",
                                    "busy_s", "window_s"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    names = {m["name"] for m in doc["per_layer"]}
    assert set(sound["metrics"]) <= names
    # the host-side readers find something to read; the device's find
    # nothing on a CPU and are left out of the line
    assert {"collect_ms_per_block.steady", "commit_ms_per_block.steady",
            "lanes_per_flush.steady", "device_lane_share.steady",
            "late_arrival_p95_ms.steady", "first_block_s"} <= set(sound["metrics"])
    assert "device_idle_share.steady" not in sound["metrics"]
    assert sound["metrics"]["lanes_per_flush.steady"]["value"] == 20.0
    assert sound["metrics"]["device_lane_share.steady"]["value"] == 100.0
    for m in sound["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]


@pytest.fixture
def unpatched():
    """A control patches the program's classes; put them back."""
    from fabric_tpu.csp.tpu.provider import TPUCSP
    from fabric_tpu.ledger.txmgmt import MVCCValidator

    saved = (TPUCSP.verify_batch_async, MVCCValidator._committed_version)
    yield
    TPUCSP.verify_batch_async, MVCCValidator._committed_version = saved


@pytest.mark.parametrize("name", ["accept_all_signatures", "skip_mvcc"])
def test_a_broken_program_comes_out_as_not_correct(sound, unpatched, name, capsys):
    Manifest(ROOT).control(name)()
    line = engine.run_cell(ROOT, CELL, 2**31 + 99, 1.0, False, rehearsal=TINY)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0     # wrong in every block
    assert set(line["metrics"]) == {"block_commit_p50_ms", "block_commit_p95_ms", "setup_s"}
    compared = {}
    for out_line in capsys.readouterr().out.splitlines():
        if out_line.startswith("# compared: "):
            rec = json.loads(out_line[len("# compared: "):])
            compared[rec["number"]] = rec["value"]
    # the broken guarantee shows in the ledger's state too, not only
    # in the flags the harness collected
    assert compared["state_entries_differing_from_reference"] > 0


# -- a new kind of deployment, by files alone -----------------------------
#
# The toy kind: the x509 world with the creator signature of every
# block's transaction `toy_unsigned_tx` spoiled on top of what is
# planted, a `public` of its own shape that only its own reference
# reads, a condition on the blocks a run yielded, and a control that
# lets that one spoiled signature pass.

TOY_WORLD = '''
"""Toy: benchlib.generator's world, one more creator signature spoiled."""
import dataclasses

from benchlib import generator


@dataclasses.dataclass
class ToyWorld:
    channel: str
    genesis: object
    blocks: list
    planted: list
    namespaces: tuple
    public: dict
    lanes_per_block: int
    state: dict

    def expected_state(self):
        return self.state


def build_world(seed, deployment, planted, n_blocks):
    from fabric_tpu.protos.common import common_pb2

    w = generator.build_world(seed, deployment, dict(planted, conflict_pairs_per_block=0), n_blocks)
    victim = int(deployment["toy_unsigned_tx"])
    blocks, flags = [], [list(p) for p in w.planted]
    for bno, raw in enumerate(w.blocks):
        blk = common_pb2.Block.FromString(raw)
        env = common_pb2.Envelope.FromString(blk.data.data[victim])
        env.signature = env.signature[:-1] + bytes([env.signature[-1] ^ 2])
        blk.data.data[victim] = env.SerializeToString()
        flags[bno][victim] = generator.BAD_CREATOR_SIGNATURE
        blocks.append(blk.SerializeToString())
    state = {}
    for bno, (row, wrote) in enumerate(zip(flags, w.writes)):
        for i, (flag, (key, value)) in enumerate(zip(row, wrote)):
            if flag == generator.VALID:
                state["benchcc", key] = (value, (1 + bno, i))
    return ToyWorld(w.channel, w.genesis, blocks, flags, w.namespaces,
                    {"toy_trust": w.public["ca_certs_pem"]}, w.lanes_per_block, state)
'''

TOY_REFERENCE = '''
"""Toy: the x509 reference over the toy world's own `public`."""
import importlib.util
import os

FLIP = %(flip)r          # the test's switch: one flag of one block reported wrong

_spec = importlib.util.spec_from_file_location(
    "toy_x509_reference", os.path.join(os.path.dirname(__file__), "x509-majority.py"))
_x509 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_x509)


def run(public, deployment, blocks):
    flags, states = _x509.run({"ca_certs_pem": public["toy_trust"]}, deployment, blocks)
    if FLIP:
        flags[0][-1] = _x509.VALID if flags[0][-1] else _x509.BAD_CREATOR_SIGNATURE
    return flags, states
'''

TOY_CONDITION = '''
"""Toy: a run may yield at most LIMIT blocks.  It reads everything of
the engine's `Cell` that `manifest.py` says a condition may read."""
from benchlib.manifest import A_CONDITION_MAY_READ

LIMIT = %(limit)d


def numbers(cell):
    held = {name: getattr(cell, name) for name in A_CONDITION_MAY_READ}
    assert held["world"].blocks and held["deployment"]["block_txs"] > 0
    assert set(held["lanes_window"]) == set(held["csp"].lane_tally())
    assert held["new_buckets"] == []
    return {"toy_blocks_yielded": (len(held["yielded"]), LIMIT)}
'''

TOY_CONTROL = '''
"""Toy: a provider that lets one spoiled signature in every batch pass."""


def apply():
    from fabric_tpu.csp.tpu.provider import TPUCSP

    inner = TPUCSP.verify_batch_async

    def lenient(self, items, flush=False):
        wait = inner(self, items, flush)

        def mask():
            out = list(wait())
            if False in out:
                out[out.index(False)] = True
            return out

        return mask

    TPUCSP.verify_batch_async = lenient
'''


def _held(root, config):
    with open(os.path.join(root, "benchmarks", "configs", config + ".json")) as f:
        return json.load(f)


def _add_cell(root, name, like, **changed):
    """One more configuration and its catch-up cell in the copy at
    `root`: `like`'s file with `changed` laid over it, and the entries
    APPENDED, the cell's name at the end of every list that names
    `like`'s catch-up cell."""
    held = dict(_held(root, like), name=name, **changed)
    with open(os.path.join(root, "benchmarks", "configs", name + ".json"), "w") as f:
        json.dump(held, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({"name": name, "source": "a test: " + name, "why": "toy",
                           "reduced": held["reduced"],
                           "file": f"benchmarks/configs/{name}.json"})
    doc["workloads"].append({"name": name + ".catchup", "config": name, "traffic": "catchup",
                             "chips": 1, "why": "toy"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if like + ".catchup" in m.get("workloads", ()):
            m["workloads"].append(name + ".catchup")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)


@pytest.fixture
def toy_root(tmp_path):
    """A copy of the benchmark with the toy kind added as new files and
    new entries; what was there is checked byte for byte on the way out."""
    root = str(tmp_path)
    b = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), b,
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copytree(os.path.join(ROOT, "sampleconfig"), os.path.join(root, "sampleconfig"))
    before = {}
    for d, _dirs, files in os.walk(b):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    solo = _held(root, "solo1-500tx")
    _add_cell(root, "toy", "solo1-500tx", world="toy-world", reference="toy-reference",
              conditions=["toy-condition"],
              deployment=dict(solo["deployment"], toy_unsigned_tx=0),
              planted=dict(solo["planted"], bad_creator_per_block=1,
                           bad_endorsement_per_block=1))

    def write(kind, name, text):
        # a kind's directory comes with its first file: git carries no
        # empty one, so `benchmarks/conditions/` is not in a checkout yet
        os.makedirs(os.path.join(b, kind), exist_ok=True)
        with open(os.path.join(b, kind, name + ".py"), "w") as f:
            f.write(text)

    write("worlds", "toy-world", TOY_WORLD)
    write("reference", "toy-reference", TOY_REFERENCE % {"flip": False})
    write("conditions", "toy-condition", TOY_CONDITION % {"limit": 1000})
    write("controls", "toy-control", TOY_CONTROL)
    yield root, write
    for p, data in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == data, p


def _toy_run(root, capsys, cell="toy.catchup", size=TINY):
    line = engine.run_cell(root, cell, 2**31 + 99, 0.2, False, rehearsal=size)
    compared = {}
    for out_line in capsys.readouterr().out.splitlines():
        if out_line.startswith("# compared: "):
            rec = json.loads(out_line[len("# compared: "):])
            compared[rec["number"]] = (rec["value"], rec["limit"])
    assert compared == {k: (c["value"], c["limit"]) for k, c in line["compared"].items()}
    assert list(line)[-1] == "compared"
    return line, compared


def test_a_new_kind_of_deployment_is_added_by_files_only(sound, unpatched, toy_root, capsys):
    root, write = toy_root
    line, compared = _toy_run(root, capsys)
    assert line["correct"] is True and line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"committed_tx_per_s", "setup_s"}
    # the engine's own nine, and the toy's one beside them
    assert len(compared) == 10 and compared["toy_blocks_yielded"][1] == 1000
    assert compared["toy_blocks_yielded"][0] == line["attempted"]
    assert all(value == 0 for name, (value, _l) in compared.items() if name != "toy_blocks_yielded")

    # one flag of the toy reference flipped: the program no longer agrees
    write("reference", "toy-reference", TOY_REFERENCE % {"flip": True})
    line, compared = _toy_run(root, capsys)
    assert line["correct"] is False and line["failed"] == line["attempted"] > 0
    assert compared["generator_disagrees_with_reference"][0] == 1
    assert compared["state_entries_differing_from_reference"][0] == 0
    write("reference", "toy-reference", TOY_REFERENCE % {"flip": False})

    # the toy condition over its limit, everything else sound
    write("conditions", "toy-condition", TOY_CONDITION % {"limit": 0})
    line, compared = _toy_run(root, capsys)
    assert line["correct"] is False and line["failed"] == 0
    assert compared.pop("toy_blocks_yielded") == (line["attempted"], 0)
    assert all(value == 0 for value, _l in compared.values())
    write("conditions", "toy-condition", TOY_CONDITION % {"limit": 1000})

    # the toy control: the spoiled signature passes, flags and state differ
    Manifest(root).control("toy-control")()
    line, compared = _toy_run(root, capsys)
    assert line["correct"] is False and line["failed"] == line["attempted"] > 0
    assert compared["state_entries_differing_from_reference"][0] > 0


# -- a per-layer metric, appended -------------------------------------------
#
# PR 37 built seven metrics that no PR could declare: three accepted
# tests pinned the END of `per_layer` and the count of metrics due in
# two cells.  What holds "appended, nothing moved" now is data
# (`data/accepted_*.json`, `test_bench_manifest.py`), and this case is
# the one that would have caught the pins: one more entry with its
# reader, and every test of the manifest run against the copy.

TOY_READER = '''
"""Toy: the transactions of a block, a number the engine already gives."""


def read(obs):
    return float(obs["block_txs"]) if obs["blocks"] else None
'''


def _every_test_of(module, **fixtures):
    """Call each `test_*` of `module` with `fixtures` standing in for
    pytest's, one call a parametrised case; the names that ran."""
    ran = []
    for name, test in sorted(vars(module).items()):
        if not (name.startswith("test_") and inspect.isfunction(test)):
            continue
        cases = [{}]
        for mark in getattr(test, "pytestmark", ()):
            if mark.name == "parametrize":
                names = [n.strip() for n in mark.args[0].split(",")]
                cases = [dict(case, **dict(zip(names, v if len(names) > 1 else (v,))))
                         for case in cases for v in mark.args[1]]
        for case in cases:
            test(**{p: case[p] if p in case else fixtures[p]()
                    for p in inspect.signature(test).parameters})
            ran.append(name)
    return ran


def test_a_per_layer_metric_is_appended_and_the_manifests_tests_pass_unedited(
        sound, toy_root, tmp_path_factory):
    root, write = toy_root
    write("layer_metrics", "toy_txs_per_block", TOY_READER)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["per_layer"].append({"name": "toy_txs_per_block.catchup", "unit": "tx", "better": "higher",
                             "source": "program_counter", "layer": "harness (benchmarks/)",
                             "moves": "committed_tx_per_s",
                             "workloads": ["toy.catchup", "timeoutcut-2s.catchup",
                                           "manyclients-10k.catchup"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)

    ran = _every_test_of(contract, root=lambda: root, doc=lambda: doc,
                         tmp_path=lambda: tmp_path_factory.mktemp("a_copy_of_the_copy"))
    assert len(ran) >= 19 and {
        "test_top_level_keys_and_sizes", "test_configs", "test_workloads", "test_metrics",
        "test_what_was_accepted_stands_first_in_its_order_and_as_it_was",
        "test_a_config_a_mix_a_cell_and_a_metric_are_added_as_new_files_only"} <= set(ran)
    # the same tests do refuse an entry that is put BEFORE an accepted one
    moved = dict(doc, per_layer=doc["per_layer"][-1:] + doc["per_layer"][:-1])
    with pytest.raises(AssertionError):
        contract.test_what_was_accepted_stands_first_in_its_order_and_as_it_was(moved, "per_layer")

    # the entry is due where it says, its reader answers, and a traced
    # run of the cell it was added to reports it beside the others
    man = Manifest(root)
    for cell in ("toy.catchup", "timeoutcut-2s.catchup", "manyclients-10k.catchup"):
        assert "toy_txs_per_block.catchup" in {m["name"] for m in man.metrics("per_layer", cell)}
    assert "toy_txs_per_block.catchup" not in {
        m["name"] for m in man.metrics("per_layer", "solo1-500tx.catchup")}
    line = engine.run_cell(root, "toy.catchup", 2**31 + 99, 0.2, True, rehearsal=TINY)
    assert line["correct"] is True
    assert line["metrics"]["toy_txs_per_block.catchup"] == {"value": 10.0, "unit": "tx"}
    assert {"collect_ms_per_block.catchup", "policy_ms_per_block.catchup",
            "collect_cpu_ms_per_block.catchup", "host_cores_busy.catchup"} <= set(line["metrics"])


# -- two chaincodes, a policy each ------------------------------------------
#
# The door: a world may carry a `definition_provider`, which the engine
# hands every validator it builds, as a peer's lifecycle would.  The toy
# world writes into two chaincodes by turns, three organisations of five
# endorsing: `toy-any` wants one of them, `toy-most` four.  The channel's
# default MAJORITY would pass every transaction, so the flags agree with
# the reference, which counts organisations by chaincode, only where the
# validator was handed the definitions.

TOY_TWO_WORLD = '''
"""Toy: two chaincodes under a policy each, on benchlib.generator's channel."""
import dataclasses
import random

from benchlib import generator

HAND_OVER = %(hand_over)r       # the test's switch: a world that keeps its definitions to itself


class Definitions:
    """What a peer's lifecycle answers its validator."""

    def __init__(self, mspids, need):
        from fabric_tpu.policies import policydsl
        from fabric_tpu.protos.peer import collection_pb2

        peers = ", ".join(f"'{m}.peer'" for m in mspids)
        self._params = {}
        for namespace, n in need.items():
            ap = collection_pb2.ApplicationPolicy()
            ap.signature_policy.CopyFrom(policydsl.from_string(f"OutOf({n}, {peers})"))
            self._params[namespace] = ap.SerializeToString()

    def validation_info(self, namespace):
        param = self._params.get(namespace)
        return None if param is None else ("vscc", param)


@dataclasses.dataclass
class TwoWorld:
    channel: str
    genesis: object
    blocks: list
    planted: list
    namespaces: tuple
    public: dict
    lanes_per_block: int
    state: dict

    def expected_state(self):
        return self.state


def build_world(seed, deployment, planted, n_blocks):
    from fabric_tpu import protoutil
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.peer import chaincode_pb2, proposal_pb2

    need = deployment["toy_need"]
    n_txs, endorsers = int(deployment["block_txs"]), int(deployment["endorsers_per_tx"])
    net = generator.build_world(seed, deployment, planted, 0)     # the channel, no block
    rng = random.Random(f"toy-two:{int(seed)}")
    client = net.orgs[0].signer(rng, "client", "client")
    peers = [o.signer(rng, f"peer{i}", "peer") for i, o in enumerate(net.orgs[:endorsers])]
    sim_ledger = LedgerProvider(None).create(net.genesis)
    ok = proposal_pb2.Response(status=200)
    blocks, flags, state = [], [], {}
    for bno in range(n_blocks):
        blk = common_pb2.Block()
        blk.header.number = 1 + bno
        row = []
        for i in range(n_txs):
            namespace = sorted(need)[i %% len(need)]
            key, value = f"k{bno}-{i}", rng.randbytes(16)
            prop, _txid = protoutil.create_chaincode_proposal(
                client.serialize(), net.channel, namespace, [key.encode(), value],
                nonce=rng.randbytes(24))
            sim = sim_ledger.new_tx_simulator()
            sim.set_state(namespace, key, value)
            resps = [protoutil.create_proposal_response(
                prop, results=sim.get_tx_simulation_results(), events=b"", response=ok,
                chaincode_id=chaincode_pb2.ChaincodeID(name=namespace), endorser_signer=p)
                for p in peers]
            if i < len(need):        # one spoiled endorsement a chaincode a block
                e = resps[0].endorsement
                e.signature = generator._flip_last_byte(e.signature)
            sound_orgs = endorsers - (1 if i < len(need) else 0)
            blk.data.data.append(
                protoutil.create_signed_tx(prop, client, resps).SerializeToString())
            if sound_orgs >= need[namespace]:
                row.append(generator.VALID)
                state[namespace, key] = (value, (1 + bno, i))
            else:
                row.append(generator.ENDORSEMENT_POLICY_FAILURE)
        while len(blk.metadata.metadata) < 3:
            blk.metadata.metadata.append(b"")
        blocks.append(blk.SerializeToString())
        flags.append(row)
    world = TwoWorld(net.channel, net.genesis, blocks, flags, tuple(sorted(need)), net.public,
                     n_txs * (1 + endorsers), state)
    if HAND_OVER:
        world.definition_provider = Definitions([o.mspid for o in net.orgs], need)
    return world
'''

TOY_TWO_REFERENCE = '''
"""Toy: the x509 reference, the organisations counted against the
number the deployment states for the chaincode that was called."""
import importlib.util
import os

from fabric_tpu.protos.common import common_pb2
from fabric_tpu.protos.peer import proposal_pb2

_spec = importlib.util.spec_from_file_location(
    "toy_two_x509_reference", os.path.join(os.path.dirname(__file__), "x509-majority.py"))
_x509 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_x509)


class ByChaincode(_x509.Reference):
    def __init__(self, ca_certs_pem, need):
        super().__init__(ca_certs_pem, 1)
        self._by_chaincode = need

    def _validate_tx(self, env_bytes):
        payload = common_pb2.Payload.FromString(common_pb2.Envelope.FromString(env_bytes).payload)
        header = common_pb2.ChannelHeader.FromString(payload.header.channel_header)
        called = proposal_pb2.ChaincodeHeaderExtension.FromString(header.extension).chaincode_id.name
        self._need = self._by_chaincode[called]
        return super()._validate_tx(env_bytes)


def run(public, deployment, blocks):
    ref = ByChaincode(public["ca_certs_pem"], deployment["toy_need"])
    flags, states = [], []
    for b in blocks:
        flags.append(ref.apply_block(b))
        states.append(dict(ref.state))
    return flags, states
'''


def test_two_chaincodes_under_two_policies_come_in_through_the_worlds_definitions(
        sound, toy_root, capsys):
    root, write = toy_root
    five = _held(root, "majority5-1000tx")
    need = {"toy-any": 1, "toy-most": 4}
    _add_cell(root, "toy-two", "majority5-1000tx", world="toy-two-world",
              reference="toy-two-reference",
              deployment=dict(five["deployment"], toy_need=need))
    write("worlds", "toy-two-world", TOY_TWO_WORLD % {"hand_over": True})
    write("reference", "toy-two-reference", TOY_TWO_REFERENCE)

    # five transactions of four lanes: the 20-lane flush this process has compiled
    size = engine.Rehearsal(block_txs=5, blocks_per_pass=1)
    line, compared = _toy_run(root, capsys, "toy-two.catchup", size)
    assert line["correct"] is True and line["attempted"] >= 1 and line["failed"] == 0
    assert all(value == 0 for value, _limit in compared.values())
    # by turns: `toy-any` passes with two sound endorsements or three,
    # `toy-most` fails with three
    man = Manifest(root)
    held = man.config(man.cell("toy-two.catchup"))
    world = man.world(held)(2**31 + 99, dict(held["deployment"], block_txs=5), held["planted"], 1)
    assert [bytes(row) for row in world.planted] == [bytes([0, 10, 0, 10, 0])]
    assert {ns for ns, _key in world.expected_state()} == {"toy-any"}

    # the same blocks with the definitions kept back: the validator
    # falls to the channel's MAJORITY (three of five), which refuses
    # `toy-any`'s spoiled transaction and passes `toy-most`'s sound ones
    write("worlds", "toy-two-world", TOY_TWO_WORLD % {"hand_over": False})
    line, compared = _toy_run(root, capsys, "toy-two.catchup", size)
    assert line["correct"] is False and line["failed"] == line["attempted"] > 0
    assert compared["generator_disagrees_with_reference"][0] == 0
    assert compared["state_entries_differing_from_reference"][0] > 0


# -- a control takes what the provider takes --------------------------------


@pytest.mark.parametrize("name", ["accept_all_signatures", "accept_small_batches"])
def test_a_control_of_the_provider_takes_a_lone_blocks_early_chunk(unpatched, name):
    """Since PR 38 a lone block of more than 2,048 lanes hands its first
    chunk over with `flush=True`: a control that replaced
    `verify_batch_async` with a function of `(self, items)` raised
    `TypeError` there, where it should read `correct` false."""
    from fabric_tpu.csp.tpu.provider import TPUCSP

    class Provider:
        _min_device_batch = 16
        sealed = []

        def _note_sealed(self, by, lanes):
            self.sealed.append((by, lanes))

    asked = []

    def inner(self, items, flush=False):
        asked.append((len(items), flush))
        return lambda: [False] * len(items)

    TPUCSP.verify_batch_async = inner
    Manifest(ROOT).control(name)()
    lenient = name == "accept_all_signatures"
    assert TPUCSP.verify_batch_async(Provider(), [object()] * 2048, flush=True)() == [lenient] * 2048
    assert TPUCSP.verify_batch_async(Provider(), [object()] * 20)() == [lenient] * 20
    assert TPUCSP.verify_batch_async(Provider(), [object()] * 3, flush=True)() == [True] * 3
    assert asked == ([] if lenient else [(2048, True), (20, False)])
