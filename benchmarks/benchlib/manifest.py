"""`BENCHMARK.json` and the data files it names.

Everything that belongs to one configuration, one traffic mix, one
cell or one per-layer metric is a file of its own, found by its name:

    benchmarks/configs/<config>.json        the deployment as it is run
    benchmarks/traffic/<traffic>.json       the mix's parameters
    benchmarks/cells/<cell>.json            the cell's own numbers (its rate)
    benchmarks/layer_metrics/<metric>.py    the metric's reader

so a later PR adds a cell or a metric by adding files and entries and
edits nothing that is there.  A metric `x.catchup` is read by
`layer_metrics/x.py` unless `layer_metrics/x.catchup.py` exists: the
suffix only says which end-to-end metric the number moves.

The KIND of deployment is found the same way.  A configuration's file
names `"world"`, `"reference"` and, if it has any, `"conditions"`:

    benchmarks/worlds/<world>.py            what a run is generated from
    benchmarks/reference/<reference>.py     the plain reference it is checked against
    benchmarks/conditions/<condition>.py    when a timing is a timing of this path
    benchmarks/controls/<control>.py        the program broken for `control.py`

The four contracts (`tests/bench/test_bench_worlds.py` holds the
accepted files to them):

`worlds/<name>.py` gives `build_world(seed, deployment, planted,
n_blocks)`; what comes back has

    channel           the channel's name
    genesis           its genesis block (common_pb2.Block)
    blocks            serialized Block bytes, numbers 1..n_blocks
    planted           per block, per transaction, the flag expected
    namespaces        the chaincode namespaces whose state is compared
    expected_state()  (namespace, key) -> (value, (block, tx)) after all blocks
    public            what a verifier outside the program may know (CA
                      certificates; an issuer's public key), handed to
                      the reference as it is
    lanes_per_block   signatures a block carries (printed only)

and, if its chaincodes are not all under the channel's default
endorsement policy,

    definition_provider   what a peer's lifecycle gives its validator:
                      `validation_info(namespace)` -> (plugin name,
                      ApplicationPolicy bytes) or None for the default;
                      handed to every `TxValidator` the engine builds.
                      A world without the attribute gets none.

and, if its ledgers are to start populated (a deployment whose state is
larger than a pass's blocks can write),

    setup_blocks      serialized Block bytes, numbers 1..m: ordinary
                      blocks, valid under the channel's policies, that
                      write what a ledger holds before the first
                      measured block (a few fat transactions a block
                      is fine; the orderer's `AbsoluteMaxBytes` bounds
                      a block).  `blocks` are then numbers m+1..m+n,
                      and `expected_state()` holds the populated rows
                      too.  The engine commits them once, in set-up,
                      through `Committer.store_stream` into a ledger
                      of `LedgerProvider.create`, closes it and keeps
                      its directory as the template; every pass's
                      ledger is a copy of the template, opened by
                      `LedgerProvider.open`, outside the timed part as
                      a ledger's creation is.  What is in the state
                      got there by commits: the benchmark writes
                      nothing into the program's tables.  The template
                      is paid for by `setup_s`, and the line
                      `# populate` says what it cost (blocks, rows,
                      seconds, bytes on disk, the copy's seconds a
                      pass).  A world without the attribute gets what
                      it got: a ledger of the genesis block alone.

The same seed gives the same world.  A world module touches no JAX: the
engine builds the world while the device initialises.

`reference/<name>.py` gives `run(public, deployment, blocks)` ->
(per block the flags, per block the (namespace, key) -> (value,
(block, tx)) state after it), of a fresh chain.  The reference of a
world with `setup_blocks` is called `run(public, deployment, blocks,
setup_blocks)`, replays both, and answers (per measured block the
flags, the state after the last of `setup_blocks`, per measured block
the rows that changed: (namespace, key) -> (value, (block, tx)), or
None for a row deleted); the engine folds them, and compares every row
of the world's namespaces, the populated ones too.  It is independent of
the code under test: of `fabric_tpu` it imports `fabric_tpu.protos`
and nothing else, and it takes nothing the program has made.

`conditions/<name>.py` gives `numbers(cell)` -> {name: (value, limit)},
read once the window has closed and merged into the numbers that decide
`correct` (0 <= value <= limit), beside the engine's own, which stay
due in every cell.  Here a configuration bounds, say, the batches a
host fallback verified at 0.  Of the engine's `Cell` a condition reads
the attributes `A_CONDITION_MAY_READ` names and no other (a rehearsal
in `tests/bench/test_bench_rehearsal.py` holds the engine to them):

    yielded        (block index in its pass, flags) of every block the window yielded
    lanes_window   the provider's `lane_tally()`, window only: lanes by who sealed them
    new_buckets    kernel buckets first used inside the window
    csp            the provider as the peer built it (`lane_tally()`, `breaker`)
    world          what the cell's world module built (its `setup_blocks`, where
                   a condition wants the populated rows, among it)
    deployment     the configuration's numbers as they were run

What the program counts elsewhere (a tally another provider keeps, a
counter of `fabric_tpu.common.metrics`) a condition imports from the
program and reads there, as a control patches the program's own class.
Such a count runs from the start of the process, warm-up included: the
right span for a thing that may never happen.

`controls/<name>.py` gives `apply()`, which breaks one guarantee inside
the program (it patches the program's own class where the program
decides the thing) before the cell is built.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

# directory of a kind of module -> the one callable it gives
GIVES = {"layer_metrics": "read", "worlds": "build_world", "reference": "run",
         "conditions": "numbers", "controls": "apply"}


# what a condition's `numbers(cell)` may read of the engine's `Cell`
A_CONDITION_MAY_READ = ("yielded", "lanes_window", "new_buckets", "csp", "world", "deployment")


class ManifestError(Exception):
    pass


def _load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except OSError as e:
        raise ManifestError(f"cannot read {path}: {e}") from e
    except ValueError as e:
        raise ManifestError(f"{path} is not JSON: {e}") from e


class Manifest:
    def __init__(self, root: str):
        self.root = root
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, "benchmarks")

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.doc["workloads"])
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json (have: {known})")

    def config(self, cell: dict) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == cell["config"]:
                return _load_json(os.path.join(self.root, c["file"]))
        raise ManifestError(f"workload {cell['name']!r} names no known config")

    def traffic(self, cell: dict) -> dict:
        """The mix's parameters, overlaid with the cell's own."""
        mix = _load_json(
            os.path.join(self.bench_dir, "traffic", cell["traffic"] + ".json")
        )
        own = os.path.join(self.bench_dir, "cells", cell["name"] + ".json")
        if os.path.exists(own):
            mix = {**mix, **_load_json(own)}
        return mix

    def _reports(self, metric: dict, cell_name: str, family: str) -> bool:
        listed = metric.get("workloads")
        if listed is not None:
            return cell_name in listed
        if family == "end_to_end":
            return True
        # a per-layer metric without `workloads` is due in every cell
        # that reports the end-to-end metric it moves
        return any(
            e["name"] == metric["moves"] and self._reports(e, cell_name, "end_to_end")
            for e in self.doc["end_to_end"]
        )

    def metrics(self, family: str, cell_name: str) -> list:
        """The `end_to_end` or `per_layer` entries due in this cell."""
        return [m for m in self.doc[family] if self._reports(m, cell_name, family)]

    def _path(self, kind: str, name: str) -> str:
        path = os.path.join(self.bench_dir, kind, name + ".py")
        if not os.path.exists(path):
            raise ManifestError(f"no file {path}")
        return path

    def _load(self, kind: str, name: str):
        """The callable that `benchmarks/<kind>/<name>.py` gives."""
        path = self._path(kind, name)
        modname = "bench_" + kind + "_" + "".join(c if c.isalnum() else "_" for c in name)
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod      # a dataclass looks its module up there
        spec.loader.exec_module(mod)
        try:
            return getattr(mod, GIVES[kind])
        except AttributeError:
            raise ManifestError(f"{path} gives no {GIVES[kind]}()") from None

    def reader(self, metric_name: str):
        """The `read(obs)` of a per-layer metric's own file."""
        d = os.path.join(self.bench_dir, "layer_metrics")
        for stem in (metric_name, metric_name.split(".", 1)[0]):
            if os.path.exists(os.path.join(d, stem + ".py")):
                return self._load("layer_metrics", stem)
        raise ManifestError(f"no reader file for per-layer metric {metric_name!r} in {d}")

    def _named(self, config: dict, key: str) -> str:
        name = config.get(key)
        if not isinstance(name, str) or not name:
            raise ManifestError(f"configuration {config.get('name')!r} names no {key!r}")
        return name

    def check_kind(self, config: dict) -> None:
        """The world, the reference and every condition the
        configuration names has its file: a run that lacks one is
        refused before anything is measured.  Nothing is imported yet."""
        self._path("worlds", self._named(config, "world"))
        self._path("reference", self._named(config, "reference"))
        for name in config.get("conditions", ()):
            self._path("conditions", name)

    def world(self, config: dict):
        """The `build_world` of the configuration's kind of deployment."""
        return self._load("worlds", self._named(config, "world"))

    def reference(self, config: dict):
        """The `run` of the configuration's plain reference."""
        return self._load("reference", self._named(config, "reference"))

    def conditions(self, config: dict) -> list:
        """The `numbers(cell)` of every condition the configuration names."""
        return [self._load("conditions", name) for name in config.get("conditions", ())]

    def control(self, name: str):
        """The `apply()` of a control, for `control.py`."""
        return self._load("controls", name)
