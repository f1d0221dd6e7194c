"""One rehearsal of a whole run on the CPU at a tiny size, behind the
test-only entry (`python benchmarks/run.py` itself refuses a CPU): the
open-loop cell with tracing on, then the same run with the program
broken underneath (a control of `benchmarks/controls/` patches the
program's own classes), which must come out as not correct.  Then a
new KIND of deployment, added to a copy of the benchmark by new files
and entries alone: a toy world, reference, condition and control; a
per-layer metric appended to that copy with its reader; a copy with an
entry appended to each of the manifest's four lists, held to every
test file of `tests/bench` that does not run the engine; a toy world of
two chaincodes under a policy each, which come to the validator through
the world's `definition_provider`; and a toy world whose ledgers start
populated from the `setup_blocks` it carries.

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
def copy_root(tmp_path):
    """A copy of the benchmark to add new files and new entries to;
    what was there is checked byte for byte on the way out."""
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

    def write(kind, name, text):
        # a kind's directory comes with its first file: git carries no
        # empty one, so `benchmarks/conditions/` is not in a checkout yet
        os.makedirs(os.path.join(b, kind), exist_ok=True)
        with open(os.path.join(b, kind, name + ".py"), "w") as f:
            f.write(text)

    yield root, write
    for p, data in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == data, p


@pytest.fixture
def toy_root(copy_root):
    """The copy with the toy kind added."""
    root, write = copy_root
    solo = _held(root, "solo1-500tx")
    _add_cell(root, "toy", "solo1-500tx", world="toy-world", reference="toy-reference",
              conditions=["toy-condition"],
              deployment=dict(solo["deployment"], toy_unsigned_tx=0),
              planted=dict(solo["planted"], bad_creator_per_block=1,
                           bad_endorsement_per_block=1))
    write("worlds", "toy-world", TOY_WORLD)
    write("reference", "toy-reference", TOY_REFERENCE % {"flip": False})
    write("conditions", "toy-condition", TOY_CONDITION % {"limit": 1000})
    write("controls", "toy-control", TOY_CONTROL)
    return root, write


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
# (`data/accepted_*.json`, `test_bench_manifest.py`).  Here: one more
# entry with its reader is due where it says and a traced run reports
# it; the hold further down runs every test file against such a copy.

TOY_READER = '''
"""Toy: the transactions of a block, a number the engine already gives."""


def read(obs):
    return float(obs["block_txs"]) if obs["blocks"] else None
'''


def test_a_per_layer_metric_is_appended_and_a_traced_run_reports_it(sound, toy_root):
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

    # the manifest's test refuses an entry that is put BEFORE an accepted one (a
    # copy that appends passes every test of every file: the hold further down)
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


# -- every list takes an appended entry, under every test of the benchmark ----
#
# PR 39's guard held a copy with one more `per_layer` entry to
# `test_bench_manifest.py` alone, and one PR later a cell's own test
# pinned the end of `per_layer` again (`test_bench_keylevel.py`, PR 40;
# PR 41 could not declare its metric).  So: a copy with one more entry at
# the end of EACH of the four lists, and every test under `tests/bench`
# held to it that does not run the engine, start a process or call the
# chip's compiler: the configuration-and-entries tests of each cell's
# file, the readers' and the worlds'.  A test that counts or indexes a
# list by position now fails in the PR that writes it.

# what a test (or a fixture it uses) says that is not held to the copy
NOT_HELD = ("run_cell", "engine.Cell(", "subprocess", "get_topology_desc")


def _is_fixture(obj) -> bool:
    return hasattr(obj, "_fixture_function_marker") or hasattr(obj, "_pytestfixturefunction")


def _hold(path, root, builtin, monkeypatch):
    """Load the test file at `path` afresh with the copy at `root` as
    its checkout, and run each of its tests that `NOT_HELD` does not
    exclude, the module's own fixtures resolved by name and `builtin`
    standing in for pytest's.  The names that ran."""
    import importlib.util

    import conftest

    monkeypatch.setattr(conftest, "ROOT", root)
    monkeypatch.setattr(conftest, "BENCH", os.path.join(root, "benchmarks"))
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location("held_" + stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    fixtures = {name: getattr(obj, "__wrapped__", obj)
                for name, obj in vars(module).items() if _is_fixture(obj)}

    def says(fn, seen):
        """The source of `fn`, of the module's fixtures under it and of
        the module's own functions it names."""
        text = inspect.getsource(fn)
        for p in list(inspect.signature(fn).parameters) + list(fn.__code__.co_names):
            under = fixtures.get(p) or vars(module).get(p)
            if inspect.isfunction(under) and under.__module__ == module.__name__ \
                    and p not in seen:
                seen.add(p)
                text += says(under, seen)
        return text

    made, open_generators = {}, []

    def value(name, case):
        if name in case:
            return case[name]
        if name not in fixtures:
            return builtin[name]()
        if name not in made:
            fn = fixtures[name]
            got = fn(**{p: value(p, case) for p in inspect.signature(fn).parameters})
            if inspect.isgenerator(got):
                open_generators.append(got)
                got = next(got)
            made[name] = got
        return made[name]

    ran = []
    for name, test in sorted(vars(module).items()):
        if not (name.startswith("test_") and inspect.isfunction(test)):
            continue
        if any(word in says(test, set()) for word in NOT_HELD):
            continue
        cases = [{}]
        for mark in getattr(test, "pytestmark", ()):
            if mark.name == "parametrize":
                names = [n.strip() for n in mark.args[0].split(",")]
                values = [getattr(v, "values", v) for v in mark.args[1]]     # a pytest.param
                cases = [dict(case, **dict(zip(names, v if len(names) > 1 else (v,))))
                         for case in cases for v in values]
        for case in cases:
            with pytest.MonkeyPatch.context() as own:      # undone after every test, as pytest's is
                case = dict(case, monkeypatch=own)
                try:
                    test(**{p: value(p, case) for p in inspect.signature(test).parameters})
                except pytest.skip.Exception:
                    continue
            ran.append(f"{stem}::{name}")
    for gen in open_generators:
        next(gen, None)
    return ran


OLD_PIN = '''
import json
import os

from conftest import ROOT


def test_the_cells_metric_stands_at_the_end_of_per_layer():
    """As `test_bench_keylevel.py:84-88` had it from PR 40 to PR 43."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    assert doc["per_layer"][-1]["name"] == %(last)r
'''


def test_an_entry_appended_to_each_list_passes_every_test_that_reads_the_manifest(
        copy_root, tmp_path_factory, capsys, monkeypatch):
    root, write = copy_root
    # a configuration of an accepted kind (a toy reference need not be
    # independent of the program; an accepted one is held to it), its
    # cell, the cell's name at the end of the `workloads` list of every
    # metric a catch-up cell reports, and a per-layer entry of its own
    _add_cell(root, "toy", "solo1-500tx")
    write("layer_metrics", "toy_txs_per_block", TOY_READER)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["per_layer"].append({"name": "toy_txs_per_block.catchup", "unit": "tx", "better": "higher",
                             "source": "program_counter", "layer": "harness (benchmarks/)",
                             "moves": "committed_tx_per_s", "workloads": ["toy.catchup"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        accepted = json.load(f)
    for name in ("configs", "workloads", "per_layer"):
        assert doc[name][:-1] == accepted[name] or name == "per_layer"
        assert len(doc[name]) == len(accepted[name]) + 1
    grown = [m["name"] for was, m in zip(accepted["end_to_end"], doc["end_to_end"])
             if m.get("workloads", [])[len(was.get("workloads", [])):] == ["toy.catchup"]]
    assert grown == ["committed_tx_per_s"]
    # what the tests read beside the benchmark: the program and their own data
    for beside in ("fabric_tpu", "tests"):
        os.symlink(os.path.join(ROOT, beside), os.path.join(root, beside))

    builtin = {"tmp_path": lambda: tmp_path_factory.mktemp("held"),
               "tmp_path_factory": lambda: tmp_path_factory,
               "capsys": lambda: capsys}
    here = os.path.dirname(os.path.abspath(__file__))
    ran = []
    for file in sorted(os.listdir(here)):
        if file.startswith("test_bench_") and file.endswith(".py") \
                and file != os.path.basename(__file__):
            ran += _hold(os.path.join(here, file), root, builtin, monkeypatch)
    assert {name.split("::")[0] for name in ran} >= {
        "test_bench_manifest", "test_bench_keylevel", "test_bench_worlds",
        "test_bench_commit_assist", "test_bench_cpu_spans", "test_bench_timeoutcut",
        "test_bench_manyclients", "test_bench_policy_reader", "test_bench_span_readers",
        "test_bench_keylevel_bulk_reader"}
    assert len(ran) >= 150 and {
        "test_bench_keylevel::test_the_configuration_states_its_source_its_shapes_and_its_guarantees",
        "test_bench_manifest::test_what_was_accepted_stands_first_in_its_order_and_as_it_was",
        "test_bench_worlds::test_every_configuration_names_a_world_and_a_reference_that_keep_the_contract",
        "test_bench_keylevel_bulk_reader::test_the_entry_stands_beside_the_cells_other_key_level_metrics",
    } <= set(ran)
    # no rehearsal came along: the engine is not run here
    assert not {"test_bench_keylevel::test_a_traced_rehearsal_reports_the_three_metrics",
                "test_bench_idemix::test_skipping_the_pseudonym_signature_is_wrong_in_every_block",
                "test_bench_refusal::test_no_tpu_no_result"} & set(ran)

    # and a pin of the kind PR 40 wrote, which the checkout as it is
    # passes, is refused by the same hold
    pinned = os.path.join(str(tmp_path_factory.mktemp("pinned")), "test_bench_pinned.py")
    with open(pinned, "w") as f:
        f.write(OLD_PIN % {"last": accepted["per_layer"][-1]["name"]})
    assert len(_hold(pinned, ROOT, builtin, monkeypatch)) == 1
    with pytest.raises(AssertionError):
        _hold(pinned, root, builtin, monkeypatch)


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


# -- a ledger that starts populated -------------------------------------------
#
# The door: a world may carry `setup_blocks`, which the engine commits
# once in set-up through `store_stream`, keeps as a template, and copies
# for every pass.  The toy world's two set-up blocks write a hundred rows
# in four fat transactions; its measured blocks read those rows at the
# versions the set-up left and overwrite them, so that every flag and
# every row depends on what the ledger held before the first measured
# block.  Planted: a read of a populated row at a version it never had
# (MVCC_READ_CONFLICT), a read of a row no block wrote (valid: absent is
# what the ledger says too), and a delete of a populated row.

TOY_POPULATED_WORLD = '''
"""Toy: a hundred rows committed before the first measured block."""
import dataclasses
import random

from benchlib import generator

ROWS_PER_SETUP_TX, SETUP_TXS_PER_BLOCK, SETUP_BLOCKS = 25, 2, 2
NS = generator.CHAINCODE


@dataclasses.dataclass
class PopulatedWorld:
    channel: str
    genesis: object
    blocks: list
    planted: list
    namespaces: tuple
    public: dict
    lanes_per_block: int
    state: dict
    setup_blocks: list

    def expected_state(self):
        return self.state


def build_world(seed, deployment, planted, n_blocks):
    from fabric_tpu import protoutil
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.ledger.rwset import rwset_pb2
    from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2
    from fabric_tpu.protos.peer import chaincode_pb2, proposal_pb2

    n_txs, endorsers = int(deployment["block_txs"]), int(deployment["endorsers_per_tx"])
    net = generator.build_world(seed, deployment, planted, 0)     # the channel, no block
    rng = random.Random(f"toy-populated:{int(seed)}")
    client = net.orgs[0].signer(rng, "client", "client")
    peers = [o.signer(rng, f"peer{i}", "peer") for i, o in enumerate(net.orgs[:endorsers])]
    ok = proposal_pb2.Response(status=200)

    def envelope(reads, writes):
        """reads: (key, version or None); writes: (key, value or None for a delete)."""
        kv = kv_rwset_pb2.KVRWSet()
        for key, version in reads:
            read = kv.reads.add(key=key)
            if version is not None:
                read.version.block_num, read.version.tx_num = version
        for key, value in writes:
            if value is None:
                kv.writes.add(key=key, is_delete=True)
            else:
                kv.writes.add(key=key, value=value)
        results = rwset_pb2.TxReadWriteSet(data_model=rwset_pb2.TxReadWriteSet.KV)
        results.ns_rwset.add(namespace=NS, rwset=kv.SerializeToString())
        prop, _txid = protoutil.create_chaincode_proposal(
            client.serialize(), net.channel, NS, [b"toy"], nonce=rng.randbytes(24))
        resps = [protoutil.create_proposal_response(
            prop, results=results.SerializeToString(), events=b"", response=ok,
            chaincode_id=chaincode_pb2.ChaincodeID(name=NS), endorser_signer=p) for p in peers]
        return protoutil.create_signed_tx(prop, client, resps).SerializeToString()

    def block(number, envelopes):
        blk = common_pb2.Block()
        blk.header.number = number
        blk.data.data.extend(envelopes)
        while len(blk.metadata.metadata) < 3:
            blk.metadata.metadata.append(b"")
        return blk.SerializeToString()

    state, setup_blocks, row = {}, [], 0
    for b in range(SETUP_BLOCKS):
        envelopes = []
        for t in range(SETUP_TXS_PER_BLOCK):
            writes = [(f"acct{row + j:03d}", rng.randbytes(10)) for j in range(ROWS_PER_SETUP_TX)]
            row += ROWS_PER_SETUP_TX
            envelopes.append(envelope((), writes))
            for key, value in writes:
                state[NS, key] = (value, (1 + b, t))
        setup_blocks.append(block(1 + b, envelopes))

    blocks, flags = [], []
    for b in range(n_blocks):
        number, envelopes, want = 1 + SETUP_BLOCKS + b, [], []
        for i in range(n_txs):
            key = f"acct{(7 * (b * n_txs + i)) % row:03d}"
            held = state.get((NS, key))
            version = None if held is None else held[1]
            value = rng.randbytes(10)
            if b == 0 and i == 3:
                # a version the populated row never had
                envelopes.append(envelope([(key, (SETUP_BLOCKS, 9))], [(key, value)]))
                want.append(generator.MVCC_READ_CONFLICT)
                continue
            if b == 0 and i == 5:
                # a row no block wrote: read absent, written here
                envelopes.append(envelope([("nobody", None)], [("nobody", value)]))
                state[NS, "nobody"] = (value, (number, i))
            elif b == 0 and i == 7:
                envelopes.append(envelope([(key, version)], [(key, None)]))
                state.pop((NS, key))
            else:
                envelopes.append(envelope([(key, version)], [(key, value)]))
                state[NS, key] = (value, (number, i))
            want.append(generator.VALID)
        blocks.append(block(number, envelopes))
        flags.append(want)
    return PopulatedWorld(net.channel, net.genesis, blocks, flags, (NS,), net.public,
                          n_txs * (1 + endorsers), state, setup_blocks)
'''

TOY_POPULATED_REFERENCE = '''
"""Toy: the x509 reference over both lists of blocks, answering as the
reference of a populated world answers: flags, base, changes."""
import importlib.util
import os

WITHHELD = %(withheld)r     # the test's switch: the set-up blocks kept from the reference

_spec = importlib.util.spec_from_file_location(
    "toy_populated_x509_reference", os.path.join(os.path.dirname(__file__), "x509-majority.py"))
_x509 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_x509)


def run(public, deployment, blocks, setup_blocks):
    ref = _x509.Reference(public["ca_certs_pem"], int(deployment["orgs"]))
    for b in () if WITHHELD else setup_blocks:
        assert set(ref.apply_block(b)) == {_x509.VALID}
    base = dict(ref.state)
    flags, changes = [], []
    for b in blocks:
        before = dict(ref.state)
        flags.append(ref.apply_block(b))
        changes.append({row: ref.state.get(row) for row in set(before) | set(ref.state)
                        if before.get(row) != ref.state.get(row)})
    return flags, base, changes
'''


@pytest.fixture
def populated_root(toy_root):
    root, write = toy_root
    _add_cell(root, "toy-populated", "solo1-500tx", world="toy-populated-world",
              reference="toy-populated-reference")
    write("worlds", "toy-populated-world", TOY_POPULATED_WORLD)
    write("reference", "toy-populated-reference", TOY_POPULATED_REFERENCE % {"withheld": False})
    return root, write


# ten transactions of two lanes a block, two blocks a pass: a flush is 20
# or 40 lanes, the one kernel shape this process has compiled
POPULATED = engine.Rehearsal(block_txs=10, blocks_per_pass=2)
POPULATED_CELL = "toy-populated.catchup"


def test_a_world_may_start_every_passes_ledger_populated(sound, populated_root, capsys,
                                                         monkeypatch):
    root, _write = populated_root
    passes = []
    flags_out = engine.Cell._flags_out

    def kept(self, bno, flags):
        if bno == 0:
            passes.append([])
        passes[-1].append(bytes(flags))
        flags_out(self, bno, flags)
        if len(passes) == 2 and bno == 1:
            self.seconds = 0.0          # two passes, however long the CPU's kernel takes

    monkeypatch.setattr(engine.Cell, "_flags_out", kept)
    line = engine.run_cell(root, POPULATED_CELL, 2**31 + 99, 3600.0, False, rehearsal=POPULATED)
    said = {}
    for out_line in capsys.readouterr().out.splitlines():
        tag, _, rest = out_line.partition(": ")
        if tag in ("# populate", "# check", "# records"):
            said[tag[2:]] = json.loads(rest)
    assert line["correct"] is True and line["failed"] == 0
    assert all(c["value"] == 0 for c in line["compared"].values())
    assert set(line["metrics"]) == {"committed_tx_per_s", "setup_s"}
    # committed once, in set-up; copied for the warm-up's pass and for each of the window's
    pop = said["populate"]
    assert (pop["blocks"], pop["rows"]) == (2, 100) and pop["bytes_on_disk"] > 0
    assert set(pop) == {"blocks", "rows", "seconds", "bytes_on_disk", "copy_s_per_pass"}
    assert len(said["records"]["ledger_copy_s"]) == 1 + len(passes) == 3
    # every row compared, the populated ones too: a hundred, one deleted, one new
    assert said["check"]["state_rows"] == 100
    # a pass is the world's two blocks, and the stage clocks count those alone
    assert said["records"]["blocks"] == 2 * len(passes) == line["attempted"]
    # a pass after the first starts from the template again: the same
    # reads at the same versions get the same flags
    assert all(p == passes[0] for p in passes) and len(passes[0]) == 2
    assert list(passes[0][0]) == [0, 0, 0, 11, 0, 0, 0, 0, 0, 0] and set(passes[0][1]) == {0}


def test_a_populated_world_is_checked_against_what_its_setup_blocks_left(
        sound, populated_root, capsys, monkeypatch):
    root, write = populated_root
    # the set-up blocks kept from the reference: to it the chain starts
    # empty, every read of a populated row conflicts and no such row is there
    write("reference", "toy-populated-reference", TOY_POPULATED_REFERENCE % {"withheld": True})
    line, compared = _toy_run(root, capsys, POPULATED_CELL, POPULATED)
    assert line["correct"] is False and line["failed"] == line["attempted"] > 0
    assert compared["state_entries_differing_from_reference"][0] >= 90
    assert compared["generator_disagrees_with_reference"][0] == 1
    write("reference", "toy-populated-reference", TOY_POPULATED_REFERENCE % {"withheld": False})

    # a template that lost a row nobody reads: every flag is as the
    # reference has it, and the state check still misses the row
    populate = engine._Ledgers.populate

    def lossy(self):
        import sqlite3

        cost = populate(self)
        db = sqlite3.connect(os.path.join(self._template, "index.sqlite"))
        with db:
            lost = db.execute("DELETE FROM kv WHERE instr(k, 'statedb') AND instr(k, 'acct099')")
        assert lost.rowcount == 1
        db.close()
        return cost

    monkeypatch.setattr(engine._Ledgers, "populate", lossy)
    line, compared = _toy_run(root, capsys, POPULATED_CELL, POPULATED)
    assert line["correct"] is False and line["failed"] == 0
    assert compared.pop("state_entries_differing_from_reference") == (1, 0)
    assert all(value == 0 for value, _limit in compared.values())


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
