"""One run of one cell: set-up, warm-up, the measured window, the check
against the plain reference, the result line.

The system under test is entered where a peer enters it.  The provider
comes from `csp_from_config` over `sampleconfig/core.yaml` (as
`cmd/peer.py` builds it), a backlog goes through
`Committer.store_stream` at its default depth and a lone block through
`Committer.store_block` (as `gossip/state.py` `_drain` chooses), and
the harness sets no `FABRIC_TPU_*` variable.  Departures are listed in
each configuration's file under `assumed`.

What kind of deployment it is, the engine does not know: the
configuration's file names its world, its plain reference and its
conditions, and `benchlib/manifest.py` finds each by that name and
says what each has to give.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import threading
import time

from benchlib import observe, openloop, stats, tracereduce
from benchlib.manifest import Manifest, ManifestError

ANCHOR = "bench.anchor"
# the stage clocks kept for every pass, to explain a pass that stands out
PASS_STAGES = ("collect", "verify_wait", "policy", "mvcc", "block_append",
               "state", "history", "fsync", "kv_txn")


class Refused(Exception):
    """The run cannot measure anything: exit non-zero, print no result."""


@dataclasses.dataclass
class Rehearsal:
    """Test-only entry: a tiny deployment on whatever backend JAX has.
    `python benchmarks/run.py` never builds one, so the run itself
    still refuses a CPU."""

    block_txs: int = 12
    blocks_per_pass: int = 3


def say(tag: str, record) -> None:
    """An earlier line: what is worth reading, one JSON value a line."""
    print(f"# {tag}: {json.dumps(record, sort_keys=True, default=str)}", flush=True)


class _Ledgers:
    """Fresh on-disk ledgers (block files + sqlite WAL), one at a time,
    with the validator and committer a peer would hold for each.  Where
    the world has `setup_blocks`, `populate()` commits them once and
    every ledger after that starts as a copy of what they left."""

    def __init__(self, root: str, world, csp):
        self._root, self._world, self._csp = root, world, csp
        self._n = 0
        self._bundle_cached = None
        self._template = None     # a closed ledger's directory holding the world's setup_blocks
        self.base = 1             # the height of a ledger that took none of the world's `blocks`
        self.copy_s: list = []    # what each copy of the template took
        self.validate_s: dict = collections.Counter()
        self.commit_s: dict = collections.Counter()
        self.blocks_counted = 0
        self.counting = False
        self.cur = None       # (provider, ledger, validator, committer, path)
        self.prev = None      # the last full ledger, kept for the state check

    def fresh(self):
        from fabric_tpu.common import tracing
        from fabric_tpu.ledger import LedgerProvider
        from fabric_tpu.peer.committer import Committer
        from fabric_tpu.peer.txvalidator import TxValidator

        with tracing.span("bench.make_ledger", cat="bench"):
            self.retire()
            self._n += 1
            path = os.path.join(self._root, f"ledger{self._n}")
            if self._template is None:
                provider = LedgerProvider(path)
                ledger = provider.create(self._world.genesis)
            else:
                t0 = time.perf_counter()
                shutil.copytree(self._template, path)
                self.copy_s.append(time.perf_counter() - t0)
                provider = LedgerProvider(path)
                ledger = provider.open(self._world.channel)
            bundle = self._bundle()
            validator = TxValidator(
                self._world.channel, ledger, bundle, self._csp,
                definition_provider=getattr(self._world, "definition_provider", None))
            self.cur = (provider, ledger, validator, Committer(validator, ledger), path)
        return self.cur

    def populate(self) -> dict | None:
        """The world's `setup_blocks` (numbers 1..m), committed once
        through `store_stream` as a pass commits its blocks, into a
        ledger that is then closed, so that its WAL is checkpointed,
        and kept as the template: what is in the state got there by
        commits.  What it cost, or None for a world without any."""
        raw = getattr(self._world, "setup_blocks", None)
        if not raw:
            return None
        from fabric_tpu.protos.common import common_pb2

        t0 = time.perf_counter()
        provider, ledger, _v, committer, path = self.fresh()
        for _flags in committer.store_stream(common_pb2.Block.FromString(b) for b in raw):
            pass
        if ledger.height != 1 + len(raw):
            raise RuntimeError(f"ledger height {ledger.height} after {len(raw)} setup blocks")
        rows = sum(1 for ns in self._world.namespaces
                   for _row in ledger._state.get_state_range(ns, "", ""))
        provider.close()
        self.cur = None
        self._template, self.base = path, 1 + len(raw)
        return {"blocks": len(raw), "rows": rows, "seconds": time.perf_counter() - t0,
                "bytes_on_disk": sum(os.path.getsize(os.path.join(d, f))
                                     for d, _dirs, files in os.walk(path) for f in files)}

    def _bundle(self):
        if self._bundle_cached is None:
            from fabric_tpu.common.channelconfig import bundle_from_genesis

            self._bundle_cached = bundle_from_genesis(self._world.genesis, self._csp)
        return self._bundle_cached

    def retire(self) -> None:
        """Count the current ledger's stage clocks, keep it as `prev`
        for the state check, close and delete the one before it.  A
        ledger that took no block is dropped and `prev` stays."""
        if self.cur is None:
            return
        _prov, ledger, validator, _c, _path = self.cur
        if ledger.height <= self.base:
            self._drop(self.cur)
            self.cur = None
            return
        if self.counting:
            self.validate_s.update(validator.validate_stage_seconds)
            self.commit_s.update(ledger.commit_stage_seconds)
            self.blocks_counted += ledger.height - self.base
        self._drop(self.prev)
        self.prev, self.cur = self.cur, None

    @staticmethod
    def _drop(entry) -> None:
        if entry is not None:
            entry[0].close()
            shutil.rmtree(entry[4], ignore_errors=True)

    def last_with_blocks(self):
        for entry in (self.cur, self.prev):
            if entry is not None and entry[1].height > self.base:
                return entry
        return None

    def close(self) -> None:
        self._drop(self.cur)
        self._drop(self.prev)
        self.cur = self.prev = None


class Cell:
    def __init__(self, root: str, cell_name: str, seed: int, seconds: float,
                 trace: bool, rehearsal: Rehearsal | None = None,
                 t_start: float | None = None):
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.root, self.seed, self.seconds, self.trace = root, seed, seconds, trace
        self.rehearsal = rehearsal
        self.manifest = Manifest(root)
        self.cell = self.manifest.cell(cell_name)
        self.config = self.manifest.config(self.cell)
        self.traffic = self.manifest.traffic(self.cell)
        self.manifest.check_kind(self.config)
        self.deployment = dict(self.config["deployment"])
        self.n_blocks = int(self.traffic["blocks_per_pass"])
        self.rate = self.traffic.get("rate_blocks_per_s")
        if rehearsal is not None:
            self.deployment["block_txs"] = rehearsal.block_txs
            self.n_blocks = rehearsal.blocks_per_pass
        self.mode = self.traffic["arrivals"]          # "backlog" | "open_loop"
        if self.mode == "open_loop" and not self.rate:
            raise ManifestError(
                f"cell {cell_name!r} is open-loop and has no rate_blocks_per_s "
                f"(benchmarks/cells/{cell_name}.json)"
            )
        self.yielded: list = []          # (block index in pass, flags bytes)
        self.pass_walls: list = []
        self.pass_txs: list = []
        self.pass_gc2: list = []         # (gen-2 collections, their pause) in each pass
        self.pass_stages: list = []      # the stage clocks of each pass
        self.latencies_s: list = []
        self.lateness_s: list = []
        self.run_sizes: list = []        # blocks handed to one store_* call
        self.anchors: list = []

    # -- set-up ----------------------------------------------------------

    def setup(self) -> None:
        sys.path.insert(0, self.root)
        try:
            import fabric_tpu  # noqa: F401  (the system under test)
            from fabric_tpu import native
            from fabric_tpu.common.config import Config
            from fabric_tpu.csp import csp_from_config
        except ImportError as e:
            raise Refused(f"the program is not in this checkout: {e}") from e

        say("env", {"set": observe.knobs_set(), "machine": observe.machine(),
                    "argv": sys.argv[1:]})
        device: dict = {}

        def init_device():
            try:
                from fabric_tpu.csp.tpu.provider import TPUCSP

                device.update(TPUCSP.device_info())
            except Exception as e:  # reported by the caller
                device["error"] = repr(e)

        t0 = time.perf_counter()
        th = threading.Thread(target=init_device, name="bench-device-init")
        th.start()
        # a world module does not touch JAX: block generation runs
        # while the device initialises
        self.world = self.manifest.world(self.config)(
            self.seed, self.deployment, self.config["planted"], self.n_blocks
        )
        t_gen = time.perf_counter() - t0
        th.join()
        t_dev = time.perf_counter() - t0
        if "error" in device:
            raise Refused(f"JAX found no device: {device['error']}")
        self.device = device
        if self.rehearsal is None:
            peaks = _load(os.path.join(self.root, "benchmarks", "peaks.json"))
            if device["platform"] != "tpu":
                raise Refused(f"the cell runs on a TPU, JAX reports {device}")
            if device["kind"] not in peaks["devices"]:
                raise Refused(f"device kind {device['kind']!r} is not in peaks.json")
            if device["count"] < int(self.cell["chips"]):
                raise Refused(
                    f"the cell asks for {self.cell['chips']} chip(s), JAX has {device['count']}"
                )
            if not native.available():
                raise Refused(
                    "the native marshaller/collector is unavailable, the measured "
                    f"path would be the pure-Python one: {native.load_error()}"
                )
        cfg = Config.load(
            "core", "CORE", path=os.path.join(self.root, "sampleconfig", "core.yaml")
        )
        self.csp = csp_from_config(cfg)
        if not hasattr(self.csp, "lane_tally"):
            raise Refused(f"sampleconfig/core.yaml selects {type(self.csp).__name__}, not the TPU provider")
        self.gc = observe.GCWatch().install()
        self.compiles = observe.CompileWatch().install()
        self.buckets = observe.BucketWatch(self.csp)
        # the ledgers live where a peer's operator would put them on
        # this machine: the default temporary directory (the driver
        # gives each side a TMPDIR of its own), not the checkout
        self.workdir = tempfile.mkdtemp(prefix="tpu-fabric-bench-")
        self.ledger_fs = observe.filesystem_of(self.workdir)
        if self.ledger_fs["type"] in observe.MEMORY_FILESYSTEMS:
            say("warning", f"the ledgers are on {self.ledger_fs['type']}: an fsync is "
                           "free there, durability's cost is not in this run")
        self.ledgers = _Ledgers(self.workdir, self.world, self.csp)
        say("setup", {
            "device": device, "block_generation_s": t_gen, "device_init_and_generation_s": t_dev,
            "native": native.available(), "filesystem": self.ledger_fs,
            "ledger_dir": self.workdir,
            "provider": {
                "class": type(self.csp).__name__,
                "min_device_batch": self.csp._min_device_batch,
                "coalesce_lanes": self.csp._coalesce,
                "stall_factor": self.csp._stall_factor,
                "max_chunk": self.csp._max_chunk,
                "stream_depth": "default of Committer.store_stream",
            },
            "lanes_per_block": self.world.lanes_per_block,
            "blocks_per_pass": self.n_blocks, "rate_blocks_per_s": self.rate,
        })

    def warm_up(self) -> None:
        """The cell's own traffic once through, so that every kernel
        bucket, the key table and both entry points have run.  A steady
        cell also takes one backlog pass first: how many blocks share a
        flush there depends on timing, and a bucket first met inside
        the window would compile there.  A world's `setup_blocks` are
        committed first, once: the template of every ledger from here on."""
        populated = self.ledgers.populate()
        t0 = time.perf_counter()
        self._backlog_pass(timed=False)
        if self.mode == "open_loop":
            self._open_loop(self.n_blocks, record=False)
        self.csp.drain()
        self.warm_mark = self.buckets.mark()
        self.first_block_s = self.buckets.first_wall_s
        if populated:
            copies = self.ledgers.copy_s
            say("populate", dict(populated, copy_s_per_pass=sum(copies) / len(copies)))
        say("warm_up", {
            "seconds": time.perf_counter() - t0,
            "first_dispatch_s": self.first_block_s,
            "buckets": sorted(self.buckets.buckets()),
            "flush_lanes": self.buckets.lanes(),
            "compile_events": self.compiles.snapshot()["events"],
            "lane_tally": self.csp.lane_tally(),
            "provider_clocks": self._provider_clocks(),
        })

    def _provider_clocks(self) -> dict:
        """The two measurements the provider's race deadlines follow."""
        from fabric_tpu.csp.tpu import provider as p

        return {"lane_wall_ewma_us": (self.csp._lane_wall_ewma or 0.0) * 1e6,
                "host_rate_ewma": p._host_rate_ewma[0]}

    # -- traffic ---------------------------------------------------------

    def _copies(self) -> list:
        from fabric_tpu.protos.common import common_pb2

        return [common_pb2.Block.FromString(b) for b in self.world.blocks]

    def _flags_out(self, bno: int, flags) -> None:
        self.yielded.append((bno, bytes(flags)))

    def _backlog_pass(self, timed: bool) -> float:
        """One pass: every block of the world, already waiting, through
        `store_stream` into a fresh ledger.  Ledger, block copies and
        the provider's drain are outside the timed part."""
        from fabric_tpu.common import tracing

        with tracing.span("bench.between_passes", cat="bench"):
            self.csp.drain()
            _p, ledger, _v, committer, _path = self.ledgers.fresh()
            blocks = self._copies()
        self.gc.timed = timed
        gc2 = (self.gc.gen2_timed, self.gc.gen2_pause_timed_s)
        t0 = time.perf_counter()
        with tracing.span("bench.store_stream", cat="bench"):
            for bno, flags in enumerate(committer.store_stream(iter(blocks))):
                if timed:
                    self._flags_out(bno, flags)
        wall = time.perf_counter() - t0
        self.gc.timed = False
        if ledger.height != self.ledgers.base + len(blocks):
            raise RuntimeError(f"ledger height {ledger.height} after {len(blocks)} blocks")
        if timed:
            self.pass_walls.append(wall)
            self.pass_txs.append(sum(len(b.data.data) for b in blocks))
            self.pass_gc2.append((self.gc.gen2_timed - gc2[0],
                                  self.gc.gen2_pause_timed_s - gc2[1]))
            stages = dict(_v.validate_stage_seconds)
            stages.update(ledger.commit_stage_seconds)
            self.pass_stages.append({k: stages.get(k, 0.0) for k in PASS_STAGES})
            self.run_sizes.append(len(blocks))
        return wall

    def _open_loop(self, n_arrivals: int, record: bool) -> None:
        """`n_arrivals` blocks, each handed in at its due time by a
        thread of its own; this thread drains what has arrived as
        `StateProvider._drain` does: a lone block through
        `store_block`, a run through `store_stream`."""
        from fabric_tpu.common import tracing
        from fabric_tpu.protos.common import common_pb2

        n = self.n_blocks
        buf: collections.deque = collections.deque()
        cond = threading.Condition()
        fed = threading.Event()
        sched = openloop.Schedule(self.rate, time.perf_counter() + 0.05)

        def make(k):
            return common_pb2.Block.FromString(self.world.blocks[k % n])

        def hand_in(k, block, due, _handed):
            with cond:
                buf.append((k, block, due))
                cond.notify()

        late: list = []

        def feeder():
            try:
                late.extend(openloop.feed(
                    sched, n_arrivals, make, hand_in, time.perf_counter, time.sleep
                ))
            finally:
                with cond:
                    fed.set()
                    cond.notify()

        self.csp.drain()
        _p, _ledger, _v, committer, _path = self.ledgers.fresh()
        in_ledger = 0
        self.gc.timed = record
        th = threading.Thread(target=feeder, name="bench-arrivals")
        th.start()
        try:
            while True:
                with cond:
                    while not buf and not fed.is_set():
                        cond.wait()
                    if not buf:
                        break
                    take = min(len(buf), n - in_ledger)
                    run = [buf.popleft() for _ in range(take)]
                with tracing.span("bench.drain_run", cat="bench", blocks=len(run)):
                    if len(run) == 1:
                        results = [committer.store_block(run[0][1])]
                    else:
                        results = committer.store_stream(b for _k, b, _d in run)
                    for (k, _b, due), flags in zip(run, results):
                        done = time.perf_counter()
                        if record:
                            self.latencies_s.append(openloop.latency(due, done))
                            self._flags_out(k % n, flags)
                if record:
                    self.run_sizes.append(len(run))
                in_ledger += len(run)
                if in_ledger == n:
                    _p, _ledger, _v, committer, _path = self.ledgers.fresh()
                    in_ledger = 0
        finally:
            th.join()
            self.gc.timed = False
        if record:
            self.lateness_s.extend(late)

    def window(self) -> None:
        from fabric_tpu.common import tracing

        self.tally0 = self.csp.lane_tally()
        self.compiles0 = self.compiles.total()
        self.gc0 = self.gc.snapshot()
        self.ledgers.retire()     # warm-up's ledger is not the window's
        self.ledgers.counting = True
        profile_dir = None
        if self.trace:
            import jax.profiler

            tracing.arm(1 << 17)
            profile_dir = os.path.join(self.workdir, "profile")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
            self._anchor()
        self.window_m0 = time.monotonic()
        t0 = time.perf_counter()
        if self.mode == "backlog":
            while time.perf_counter() - t0 < self.seconds:
                self._backlog_pass(timed=True)
        else:
            n = openloop.Schedule(self.rate, 0.0).count_within(self.seconds)
            self._open_loop(n, record=True)
        self.window_wall_s = time.perf_counter() - t0
        self.window_m1 = time.monotonic()
        self.csp.drain()          # every flush sealed: the tally is final
        self.ledgers.retire()
        self.ledgers.counting = False
        self.spans = None
        self.device_trace = None
        if self.trace:
            import jax.profiler

            self._anchor()
            jax.profiler.stop_trace()
            self.spans = tracing.export()["traceEvents"]
            tracing.disarm()
            self.device_trace = self._reduce_trace(profile_dir)

    def _anchor(self) -> None:
        import jax.profiler

        self.anchors.append(time.monotonic())
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass

    def _reduce_trace(self, profile_dir: str) -> dict:
        import glob

        found = sorted(glob.glob(os.path.join(
            profile_dir, "plugins", "profile", "*", "*.xplane.pb"
        )))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {profile_dir}")
        doc = tracereduce.load_xplane(found[-1])
        offset = tracereduce.anchor_offset_s(doc, ANCHOR, self.anchors)
        if offset is None:
            raise RuntimeError("the trace holds no bench.anchor annotation")
        w0 = (self.window_m0 + offset) * 1e9
        w1 = (self.window_m1 + offset) * 1e9
        patterns = _load(os.path.join(self.root, "benchmarks", "peaks.json"))["kernels"]["pallas_ec"]
        red = tracereduce.reduce_device(doc, w0, w1, patterns)
        host_spans = [
            (e["name"], e["ts"] / 1e6 + offset, (e["ts"] + e["dur"]) / 1e6 + offset, e["tid"])
            for e in self.spans
            if e.get("ph") == "X" and e.get("cat") in ("stage", "bench")
        ]
        red["idle_by_span"] = tracereduce.attribute_gaps(
            [(a / 1e9, b / 1e9) for a, b in red.pop("gaps")], host_spans
        )
        red["lines"] = {
            p["name"]: [l["name"] for l in p["lines"]] for p in doc["planes"]
        }
        return red

    # -- the check against the plain reference ---------------------------

    def _reference(self):
        """The plain reference's flags, a block of the world's `blocks`
        each, and its state after the k-th of them (k >= 1, asked in
        rising order).  Of a populated world it replays the
        `setup_blocks` too and answers with the base they left and, a
        block, the rows that changed: folded here, in place."""
        run = self.manifest.reference(self.config)
        setup_blocks = getattr(self.world, "setup_blocks", None)
        if not setup_blocks:
            flags, states = run(self.world.public, self.deployment, self.world.blocks)
            return flags, lambda k: states[k - 1]
        flags, state, changes = run(self.world.public, self.deployment, self.world.blocks,
                                    setup_blocks)
        folded = 0

        def state_after(k):
            nonlocal folded
            for changed in changes[folded:k]:
                for row, held in changed.items():
                    if held is None:
                        state.pop(row, None)
                    else:
                        state[row] = held
            folded = max(folded, k)
            return state

        return flags, state_after

    def check(self) -> dict:
        """Every flag list the window yielded against the reference's,
        the last ledger's state against the reference's map, and the
        conditions under which a timing is a timing of this path: the
        engine's own, due in every cell, and those the configuration
        names."""
        t0 = time.perf_counter()
        ref_flags, state_after = self._reference()
        t_ref = time.perf_counter()
        ref_flags = [bytes(f) for f in ref_flags]
        bad_blocks = sum(1 for bno, flags in self.yielded if flags != ref_flags[bno])
        entry = self.ledgers.last_with_blocks()
        state_diff = -1
        rows = same = 0
        if entry is not None:
            ledger = entry[1]
            want = state_after(ledger.height - self.ledgers.base)
            for ns in self.world.namespaces:
                for key, vv in ledger._state.get_state_range(ns, "", ""):
                    rows += 1
                    same += want.get((ns, key)) == (
                        vv.value, (vv.version.block_num, vv.version.tx_num))
            # the rows either side has and the other has not, or has otherwise
            state_diff = (rows - same) + (len(want) - same)
        generator_agrees = (
            [bytes(f) for f in self.world.planted] == ref_flags
            and self.world.expected_state() == state_after(len(self.world.blocks))
        )
        say("check", {"reference_s": t_ref - t0, "state_rows": rows,
                      "state_and_generator_s": time.perf_counter() - t_ref})
        tally = self.csp.lane_tally()
        lanes = {k: tally[k] - self.tally0.get(k, 0) for k in tally}
        win = self.buckets
        new_buckets = sorted(
            win.buckets(self.warm_mark) - win.buckets(0, self.warm_mark)
        )
        compiles = self.compiles.total() - self.compiles0
        numbers = {
            # name: (value, limit): correct needs value <= limit
            "blocks_with_flags_differing_from_reference": (bad_blocks, 0),
            "state_entries_differing_from_reference": (state_diff, 0),
            "generator_disagrees_with_reference": (0 if generator_agrees else 1, 0),
            "lanes_sealed_by_failover": (lanes["failover"], 0),
            "lanes_sealed_by_breaker": (lanes["breaker"], 0),
            "breaker_trips": (self.csp.breaker.trips, 0),
            "compile_events_in_window": (compiles, 0),
            "buckets_first_seen_in_window": (len(new_buckets), 0),
            "blocks_not_attempted": (0 if self.yielded else 1, 0),
        }
        self.lanes_window = lanes
        self.new_buckets = new_buckets
        for numbers_of in self.manifest.conditions(self.config):
            for name, pair in numbers_of(self).items():
                if name in numbers:
                    raise ManifestError(f"two of the cell's compared numbers are named {name!r}")
                numbers[name] = pair
        for name, (value, limit) in numbers.items():
            say("compared", {"number": name, "value": value, "limit": limit,
                             "ok": 0 <= value <= limit})
        return {
            "correct": all(0 <= v <= lim for v, lim in numbers.values()),
            "attempted": len(self.yielded),
            "failed": bad_blocks,
            "reference_s": time.perf_counter() - t0,
            "compared": {name: {"value": v, "limit": lim}
                         for name, (v, lim) in numbers.items()},
        }

    # -- results ---------------------------------------------------------

    def observations(self) -> dict:
        """What the per-layer readers read."""
        gc1 = self.gc.snapshot()
        return {
            "cell": self.cell["name"], "mode": self.mode,
            "blocks": self.ledgers.blocks_counted,
            "block_txs": int(self.deployment["block_txs"]),
            "validate_stage_seconds": dict(self.ledgers.validate_s),
            "commit_stage_seconds": dict(self.ledgers.commit_s),
            "lanes_sealed_by": self.lanes_window,
            "min_device_batch": self.csp._min_device_batch,
            "flush_lanes": self.buckets.lanes(self.warm_mark),
            "flush_buckets": [bs for _n, bs in self.buckets.flushes[self.warm_mark:]],
            "pass_walls_s": self.pass_walls, "pass_txs": self.pass_txs,
            "latencies_s": self.latencies_s, "lateness_s": self.lateness_s,
            "gc_gen2_pause_timed_s": gc1["gen2_pause_timed_s"] - self.gc0["gen2_pause_timed_s"],
            "first_block_s": self.first_block_s,
            "spans": self.spans, "device_trace": self.device_trace,
        }

    def end_to_end(self) -> dict:
        """Every end-to-end number this run can give; `BENCHMARK.json`
        says which of them the cell reports.  A rate is all the timed
        work over all the timed wall, a latency statistic is over every
        block due in the window."""
        out = {"setup_s": self.setup_s}
        if self.pass_walls:
            out["committed_tx_per_s"] = stats.total_rate(self.pass_txs, self.pass_walls)
        if self.latencies_s:
            ms = [x * 1e3 for x in self.latencies_s]
            out["block_commit_p50_ms"] = stats.median(ms)
            out["block_commit_p95_ms"] = stats.percentile(ms, 95)
        return out

    def records(self) -> dict:
        gc1 = self.gc.snapshot()
        rec = {
            "cell": self.cell["name"], "seed": self.seed, "seconds": self.seconds,
            "trace": int(self.trace), "window_wall_s": self.window_wall_s,
            "ledger_filesystem": self.ledger_fs,
            "pass_walls_s": self.pass_walls,
            "pass_gen2_collections": [n for n, _s in self.pass_gc2],
            "pass_gen2_pause_s": [s for _n, s in self.pass_gc2],
            "pass_stage_seconds": self.pass_stages,
            "ledger_copy_s": self.ledgers.copy_s,
            "block_latencies_ms": [x * 1e3 for x in self.latencies_s],
            "arrival_lateness_ms": [x * 1e3 for x in self.lateness_s],
            "blocks_per_store_call": dict(collections.Counter(self.run_sizes)),
            "lanes_sealed_by": self.lanes_window,
            "gc": {k: (gc1[k] - self.gc0[k]) if not isinstance(gc1[k], list)
                   else [a - b for a, b in zip(gc1[k], self.gc0[k])] for k in gc1},
            "buckets": {"warm_up": sorted(self.buckets.buckets(0, self.warm_mark)),
                        "window": sorted(self.buckets.buckets(self.warm_mark)),
                        "first_seen_in_window": self.new_buckets},
            "flush_lanes_window": dict(collections.Counter(self.buckets.lanes(self.warm_mark))),
            "compile_events_in_window": self.compiles.total() - self.compiles0,
            "validate_stage_seconds": dict(self.ledgers.validate_s),
            "commit_stage_seconds": dict(self.ledgers.commit_s),
            "blocks": self.ledgers.blocks_counted,
            "provider_clocks": self._provider_clocks(),
            "machine_at_end": observe.machine(),
        }
        if self.pass_walls:
            import statistics

            rec["pass_wall_quartiles_s"] = (
                statistics.quantiles(self.pass_walls, n=4) if len(self.pass_walls) > 1 else []
            )
        if self.latencies_s:
            n = len(self.latencies_s)
            rec["latency_samples"] = n
            rec["p95_samples_beyond"] = stats.samples_beyond(n, 95)
            rec["p95_supported"] = stats.percentile_supported(n, 95)
        return rec

    def close(self) -> None:
        from fabric_tpu.node import quiesce

        self.gc.remove()
        self.ledgers.close()
        quiesce(self.csp)
        shutil.rmtree(self.workdir, ignore_errors=True)


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(root: str, cell_name: str, seed: int, seconds: float, trace: bool,
             rehearsal: Rehearsal | None = None, t_start: float | None = None) -> dict:
    """Run one cell once and return the result line's object.  Raises
    `Refused` (or ManifestError) where nothing can be measured."""
    cell = Cell(root, cell_name, seed, seconds, trace, rehearsal, t_start)
    cell.setup()
    try:
        cell.warm_up()
        cell.setup_s = time.perf_counter() - cell.t_start
        cell.window()
        verdict = cell.check()
        obs = cell.observations()
        records = cell.records()
        records["reference_s"] = verdict.pop("reference_s")
        compared = verdict.pop("compared")
        say("records", records)
        m = cell.manifest
        if trace:
            metrics = {}
            for entry in m.metrics("per_layer", cell_name):
                value = m.reader(entry["name"])(obs)
                if value is not None:
                    metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        else:
            e2e = cell.end_to_end()
            metrics = {
                entry["name"]: {"value": e2e[entry["name"]], "unit": entry["unit"]}
                for entry in m.metrics("end_to_end", cell_name)
            }
            say("end_to_end_all", e2e)
        device = dict(cell.device, memory_peak_bytes=memory_peak_bytes())
        line = {"correct": verdict["correct"], "attempted": verdict["attempted"],
                "failed": verdict["failed"], "metrics": metrics, "device": device}
        if trace:
            dt = cell.device_trace
            device["busy_s"], device["window_s"] = dt["busy_s"], dt["window_s"]
            ops = sorted(dt["ops"].items(), key=lambda kv: -kv[1])[:10]
            line["breakdown"] = {"device_ops": [[k, v] for k, v in ops],
                                 "idle_gaps": dt["idle_by_span"]}
            say("trace", {"planes": dt["planes"], "lines": dt["lines"],
                          "kernel_s": dt["kernel_s"], "kernel_events": dt["kernel_events"]})
        out_dir = os.path.join(root, "benchmarks", ".out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(
            out_dir, f"{cell_name}.seed{seed}.trace{int(trace)}.json"
        ), "w", encoding="utf-8") as f:
            json.dump({"records": records, "line": line}, f, indent=1, default=str)
        line["compared"] = compared       # last in the line, as the contract has it
        return line
    finally:
        cell.close()
