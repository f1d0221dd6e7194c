"""The timeout-cut kind of deployment (`timeoutcut-2s`): its seeded
world (what the seed fixes, what is planted, what it keeps of its own
cut), a whole rehearsal of `timeoutcut-2s.catchup` on the CPU at a toy
size against the plain reference, the same with three guarantees broken
underneath (the control that answers small batches unverified among
them), the condition on passes too even to be the cell's regime, the
five new readers on a recorded span list, and the key-table kernel
compiled for a described v5e at the two buckets this cell dispatches
and no other does.

No number of a CPU run is a device number: the tests read counts,
flags and verdicts, never a time.  At the toy size (MaxMessageCount 8,
ten blocks) a pass is one 52-lane flush of two blocks and eight blocks
verified on the host, so one kernel shape is built in this process.
"""

import json
import os
import types

import pytest

from benchlib import engine
from benchlib.manifest import Manifest

from conftest import ROOT

SEED = 2**31 + 134
SIZE = engine.Rehearsal(block_txs=8, blocks_per_pass=10)
CELL = "timeoutcut-2s.catchup"
CONFIG = "timeoutcut-2s"
NEW = ("blocks_per_flush", "small_batch_lane_share", "small_batch_ms_per_block",
       "blocks_per_commit_group", "verify_exposed_ms_per_flush")


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def held(man):
    return man.config({"name": CELL, "config": CONFIG})


@pytest.fixture(scope="module")
def toy(man, held):
    return man.world(held)(SEED, dict(held["deployment"], block_txs=SIZE.block_txs),
                           held["planted"], SIZE.blocks_per_pass)


# -- the configuration and its world ----------------------------------------


def test_the_configuration_runs_upstreams_default_batch_settings(held, man):
    dep = held["deployment"]
    assert dep["orderer_batch"] == {
        "batch_timeout_s": 2.0, "max_message_count": 500,
        "preferred_max_bytes": 2 * 1024 * 1024, "absolute_max_bytes": 10 * 1024 * 1024}
    assert dep["block_txs"] == dep["orderer_batch"]["max_message_count"]
    assert [(p["tx_per_s"], p["seconds"]) for p in dep["load"]["cycle"]] \
        == [(1, 16), (40, 20), (500, 2)]
    # majority5-1000tx's network but for the cut
    other = man.config({"name": "x", "config": "majority5-1000tx"})
    same = ("orgs", "endorsement_policy", "endorsers_per_tx", "orderer", "client_identities",
            "writes_per_tx", "value_bytes", "signature_lanes_per_tx", "ledger", "chips")
    assert {k: dep[k] for k in same} == {k: other["deployment"][k] for k in same}
    assert held["guarantees"][:5] == other["guarantees"] and len(held["guarantees"]) == 6
    assert held["reference"] == other["reference"] == "x509-majority"
    assert man.traffic(man.cell(CELL))["blocks_per_pass"] == 64


def test_the_toy_world_keeps_the_make_up_of_the_real_pass(toy, held):
    """Rates scaled with MaxMessageCount: a few blocks of one or two
    transactions, blocks a fraction full, full blocks; and what is
    planted is what the configuration says, by each block's size."""
    from fabric_tpu.protos.common import common_pb2

    sizes = toy.txs_per_block
    assert sizes == [len(common_pb2.Block.FromString(b).data.data) for b in toy.blocks]
    assert sizes == [1, 3, 1, 2, 2, 8, 5, 1, 3, 1]
    assert toy.cut_by == ["timeout"] * 5 + ["count"] + ["timeout"] * 4
    assert toy.lanes_by_block == [4 * n for n in sizes] and toy.lanes_per_block == 32
    for number, flags in enumerate(toy.planted, start=1):
        bad = sorted(int(f) for f in flags if f)
        if len(flags) >= 2:          # every toy block is under full_set_from_txs
            assert bad == [(4, 10, 11)[number % 3]]
        else:
            assert bad == ([4] if number % 3 == 0 else [])
    assert held["planted"]["full_set_from_txs"] == 20


def test_the_same_seed_gives_the_same_world_and_every_seed_the_same_cut(man, held, toy):
    def digest(world):
        return ([list(p) for p in world.planted], sorted(world.expected_state().items()))

    def cut(world):
        return (world.txs_per_block, world.arrivals_s, world.cut_at_s, world.cut_by)

    dep = dict(held["deployment"], block_txs=SIZE.block_txs)
    again = man.world(held)(SEED, dep, held["planted"], SIZE.blocks_per_pass)
    other = man.world(held)(SEED + 1, dep, held["planted"], SIZE.blocks_per_pass)
    assert digest(toy) == digest(again) != digest(other)
    # the arrival times are the configuration's one draw: another seed
    # is other keys, values and planted places in the same 10 blocks
    assert cut(toy) == cut(again) == cut(other)
    redrawn = dict(dep, load=dict(dep["load"], arrival_seed=7))
    assert cut(man.world(held)(SEED, redrawn, held["planted"], SIZE.blocks_per_pass)) != cut(toy)


# -- the rehearsal -----------------------------------------------------------


def run(trace=False):
    return engine.run_cell(ROOT, CELL, SEED, 1.0, trace, rehearsal=SIZE)


@pytest.fixture(scope="module")
def sound():
    return run(trace=True)


def test_a_rehearsal_agrees_with_its_reference_to_the_flag_and_the_state_entry(sound):
    compared = {k: v["value"] for k, v in sound["compared"].items()}
    assert sound["attempted"] >= 10 and sound["failed"] == 0
    assert compared["blocks_with_flags_differing_from_reference"] == 0
    assert compared["state_entries_differing_from_reference"] == 0
    assert compared["generator_disagrees_with_reference"] == 0
    assert compared["buckets_first_seen_in_window"] == 0
    # the toy pass stands in the cell's regime too
    assert compared["full_blocks_over_a_quarter_of_the_pass"] == 0
    assert compared["small_blocks_short_of_an_eighth_of_the_pass"] == 0
    assert compared["distinct_block_sizes_short_of_five_sixteenths_of_the_pass"] == 0
    assert all(v["limit"] == 0 for v in sound["compared"].values())
    assert sound["correct"] is True


def test_a_traced_rehearsal_reports_the_metrics_the_host_can_read(sound, toy, man):
    # due by the harness's own rule: an entry without `workloads` is due
    # where the end-to-end metric it moves is reported, not everywhere
    due = {m["name"] for m in man.metrics("per_layer", CELL)}
    assert set(sound["metrics"]) <= due
    assert {n + ".catchup" for n in NEW} <= set(sound["metrics"])
    # all but the two a device trace alone gives
    assert due - set(sound["metrics"]) == {"ec_kernel_ns_per_lane.catchup",
                                           "device_idle_share.catchup"}
    value = {k: v["value"] for k, v in sound["metrics"].items()}
    lanes = toy.lanes_by_block
    small = sum(n for n in lanes if n < 16)
    # one flush a pass took in the two blocks of 16 lanes or more
    assert value["blocks_per_flush.catchup"] == 2.0
    assert value["lanes_per_flush.catchup"] == float(sum(lanes) - small) == 52.0
    assert value["small_batch_lane_share.catchup"] == pytest.approx(100.0 * small / sum(lanes))
    assert value["small_batch_ms_per_block.catchup"] > 0
    assert 1.0 <= value["blocks_per_commit_group.catchup"] <= 3.0
    assert value["verify_exposed_ms_per_flush.catchup"] > 0


@pytest.fixture
def unpatched():
    from fabric_tpu.csp.tpu.provider import TPUCSP
    from fabric_tpu.ledger.txmgmt import MVCCValidator

    saved = TPUCSP.verify_batch_async, MVCCValidator._committed_version
    yield
    TPUCSP.verify_batch_async, MVCCValidator._committed_version = saved


@pytest.mark.parametrize("control", ["accept_small_batches", "accept_all_signatures", "skip_mvcc"])
def test_a_broken_guarantee_comes_out_as_not_correct(sound, unpatched, man, control):
    """At the toy size blocks 3, 4 and 9 of a pass carry a corrupted
    signature in a batch too small for the device: `accept_small_batches`
    is wrong in those and in no other."""
    man.control(control)()
    line = run()
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["correct"] is False
    assert 0 < line["failed"] <= line["attempted"]
    assert compared["state_entries_differing_from_reference"] >= 1
    assert compared["generator_disagrees_with_reference"] == 0
    assert compared["full_blocks_over_a_quarter_of_the_pass"] == 0
    if control == "accept_small_batches":
        # the device's batches were still verified: the corrupted
        # signatures of blocks 6 and 7 are still refused
        assert 10 * line["failed"] == 3 * line["attempted"]


# -- the condition -----------------------------------------------------------


def _pass(sizes, full):
    by = ["count" if i < full else "timeout" for i in range(len(sizes))]
    return types.SimpleNamespace(world=types.SimpleNamespace(txs_per_block=sizes, cut_by=by))


@pytest.mark.parametrize("sizes,full,want", [
    # the counted pass of seed 2147483747: 21 small, 5 full, 31 distinct sizes
    ([1, 1, 3, 1, 3, 2, 81, 72, 78, 81, 84, 68, 92, 80, 73, 254, 483, 339, 1, 3, 4, 2, 2, 28,
      72, 74, 85, 80, 82, 80, 79, 78, 88, 483, 44, 483, 94, 2, 3, 4, 2, 2, 41, 75, 76, 77, 85,
      84, 88, 80, 73, 73, 483, 23, 483, 54, 2, 2, 3, 1, 5, 82, 87, 84], 5, (0, 0, 0)),
    ([1000] * 8, 8, (6, 1, 2)),                        # majority5-1000tx's pass: all full
    ([483] * 17 + [1] * 3 + list(range(1, 45)), 17, (1, 0, 0)),  # one full block too many in 64
    (list(range(8, 72)), 0, (0, 8, 0)),                # no block under 8 transactions
    ([1, 2, 3] * 21 + [4], 0, (0, 0, 16)),             # 64 blocks of four sizes
    ([1, 3, 1, 2, 2, 8, 5, 1, 3, 1], 1, (0, 0, 0)),    # the rehearsal's toy pass
])
def test_the_condition_holds_the_traffic_to_the_cells_regime(man, held, sizes, full, want):
    (numbers,) = man.conditions(held)
    assert numbers(_pass(sizes, full)) == {
        "full_blocks_over_a_quarter_of_the_pass": (want[0], 0),
        "small_blocks_short_of_an_eighth_of_the_pass": (want[1], 0),
        "distinct_block_sizes_short_of_five_sixteenths_of_the_pass": (want[2], 0),
    }


# -- the new readers, on a recorded span list --------------------------------

SPANS = os.path.join(os.path.dirname(__file__), "data", "spans_timeoutcut.json")


@pytest.fixture(scope="module")
def obs():
    with open(SPANS) as f:
        return json.load(f)


def said(capsys, tag):
    for line in capsys.readouterr().out.splitlines():
        if line.startswith(f"# {tag}: "):
            return json.loads(line.split(": ", 1)[1])
    raise AssertionError(f"no '# {tag}:' line")


def test_the_provider_readers_read_the_flushes_make_up_and_the_small_batches(obs, man, capsys):
    # three flushes took in 3, 1 and 1 batches
    assert man.reader("blocks_per_flush.catchup")(obs) == pytest.approx(5 / 3)
    assert said(capsys, "flush_makeup") == {
        "512": {"flushes": 2, "mean_lanes": 330.0, "mean_segments": 1.0},
        "4096": {"flushes": 1, "mean_lanes": 2612.0, "mean_segments": 3.0},
    }
    # 24 of 3,296 lanes came in batches under min_device_batch
    assert man.reader("small_batch_lane_share.catchup")(obs) == pytest.approx(100 * 24 / 3296)
    # three `tpu.small` of 0.6, 1.2 and 0.9 ms over six blocks
    assert man.reader("small_batch_ms_per_block.catchup")(obs) == pytest.approx(0.45)
    assert said(capsys, "block_classes") == {
        "under_8": {"blocks": 3, "txs": 6, "collect_ms": 4.5, "verify_wait_ms": 0.0,
                    "policy_ms": 0.0, "commit_ms": 3.0},
        "8_to_199": {"blocks": 2, "txs": 170, "collect_ms": 21.0, "verify_wait_ms": 6.5,
                     "policy_ms": 2.0, "commit_ms": 10.0},
        "200_to_479": {"blocks": 0, "txs": 0, "collect_ms": 0.0, "verify_wait_ms": 0.0,
                       "policy_ms": 0.0, "commit_ms": 0.0},
        "480_and_over": {"blocks": 1, "txs": 483, "collect_ms": 50.0, "verify_wait_ms": 1.0,
                         "policy_ms": 12.0, "commit_ms": 9.0},
    }


def test_the_committers_and_the_validators_readers(obs, man):
    # commit groups of 2, 3 and 1 blocks (the `fsync` spans' `blocks`)
    assert man.reader("blocks_per_commit_group.catchup")(obs) == pytest.approx(2.0)
    # 7.5 ms of `verify_wait` over three flushes
    assert man.reader("verify_exposed_ms_per_flush.catchup")(obs) == pytest.approx(2.5)


def test_a_traced_window_without_a_small_batch_reads_zero_not_nothing(obs, man):
    none_small = [e for e in obs["spans"] if e["name"] != "tpu.small"]
    assert man.reader("small_batch_ms_per_block.catchup")(dict(obs, spans=none_small)) == 0.0
    assert man.reader("small_batch_lane_share.catchup")(
        dict(obs, lanes_sealed_by=dict(obs["lanes_sealed_by"], small=0))) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_nothing_where_there_is_nothing_to_read(obs, man, name):
    read = man.reader(name + ".catchup")
    if name == "small_batch_lane_share":          # a counter: an untraced run has it too
        assert read(dict(obs, spans=None)) == pytest.approx(100 * 24 / 3296)
        assert read(dict(obs, lanes_sealed_by=dict.fromkeys(obs["lanes_sealed_by"], 0))) is None
        return
    assert read(dict(obs, spans=None)) is None
    # the parent's spans: `block` without `txs`, `tpu.flush` without
    # `segments` and `segment_lanes`, and no `tpu.small` at all
    bare = [dict(e, args={k: v for k, v in e["args"].items()
                          if k not in ("txs", "segments", "segment_lanes")})
            for e in obs["spans"] if e["name"] != "tpu.small"]
    got = read(dict(obs, spans=bare))
    if name in ("blocks_per_flush", "small_batch_ms_per_block"):
        assert got is None
    else:            # these read spans the parent has: the same number
        assert got == pytest.approx(read(obs))


# -- the cell's two buckets no other cell dispatches --------------------------

BUCKETS = (256, 512)     # a lone block of 4 to 64 transactions; of 65 to 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("bucket", BUCKETS)
def test_the_key_table_kernel_compiles_for_v5e_at_the_cells_small_buckets(one_chip, bucket):
    """`test_bench_kernel_compile.py` compiles 2048, 4096 and 8192; a
    flush of this cell's small blocks runs at 256 or 512.  Nothing
    runs, so this says nothing about results or times."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from fabric_tpu.csp.tpu import pallas_ec

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.uint32, sharding=one_chip)

    c = pallas_ec._consts()
    consts = [
        c["solmat"], c["bias"], c["r256"], c["r512"], c["sub_c"],
        c["p_limbs"], c["n_limbs"], c["gx"][:, :, 0], c["gy"][:, :, 0],
    ]
    args = [
        shape(8, pallas_ec.KEYTAB), shape(8, pallas_ec.KEYTAB), shape(1, bucket),
        shape(8, bucket), shape(8, bucket), shape(8, bucket), shape(2, bucket),
    ] + [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in consts
    ]
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        call = pallas_ec._build_call_dedup.__wrapped__(
            bucket // pallas_ec.BLK, pallas_ec.BLK, False
        )
        compiled = call.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
