"""The many-clients kind of deployment (`manyclients-10k`): its seeded
world (what the seed fixes, what is planted, what it keeps for the
condition), a whole rehearsal of `manyclients-10k.catchup` on the CPU
at a tiny size against its own plain reference, the same with identity
validation switched off underneath, the condition on a world too
uniform to be the cell's regime, and the four new readers on a
recorded span list.

No number of a CPU run is a device number: the tests read counts,
flags and verdicts, never a time.  Both blocks of a pass go out as one
56-lane flush (five creators a block are refused before any lane is
made), so one kernel shape is built in this process.
"""

import json
import os
import types
from collections import Counter

import pytest

from benchlib import engine
from benchlib.manifest import Manifest

from conftest import ROOT

SEED = 2**31 + 132
SIZE = engine.Rehearsal(block_txs=12, blocks_per_pass=2)
CELL = "manyclients-10k.catchup"
CONFIG = "manyclients-10k"


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def held(man):
    return man.config({"name": CELL, "config": CONFIG})


def _creators(world) -> list:
    """Per block, per transaction: (serialized creator, its certificate)."""
    from cryptography import x509

    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.msp import identities_pb2

    out = []
    for raw in world.blocks:
        row = []
        for env_bytes in common_pb2.Block.FromString(raw).data.data:
            payload = common_pb2.Payload.FromString(
                common_pb2.Envelope.FromString(env_bytes).payload)
            shdr = common_pb2.SignatureHeader.FromString(payload.header.signature_header)
            sid = identities_pb2.SerializedIdentity.FromString(shdr.creator)
            row.append((shdr.creator, sid.mspid, x509.load_pem_x509_certificate(sid.id_bytes)))
        out.append(row)
    return out


def test_the_world_plants_what_the_configuration_says_and_counts_its_creators(man, held):
    dep = dict(held["deployment"], block_txs=60)
    world = man.world(held)(SEED, dep, held["planted"], 3)
    p = held["planted"]
    refused = (p["rogue_ca_creators_per_block"] + p["expired_creators_per_block"]
               + p["revoked_creators_per_block"] + p["no_role_ou_creators_per_block"])
    for flags in world.planted:
        c = Counter(int(f) for f in flags)
        assert c[4] == p["bad_creator_per_block"] + refused
        assert c[10] == p["bad_endorsement_per_block"]
        assert c[11] == p["conflict_pairs_per_block"]
    assert set(world.public) == {"ca_certs_pem", "crls_pem"}
    assert set(world.public["crls_pem"]) == set(world.public["ca_certs_pem"]) \
        == {f"Org{i}MSP" for i in range(1, 6)}
    assert world.lanes_per_block == 4 * 60
    # what it keeps for the condition is what the blocks hold
    seen = [{c for c, _m, _x in row} for row in _creators(world)]
    assert world.creators_per_block == [len(s) for s in seen]
    assert world.creators_per_two_blocks == [len(a | b) for a, b in zip(seen, seen[1:])]
    assert world.creators_per_pass == len(set().union(*seen)) == world.certificates_issued
    assert all(n > 0.4 * 60 for n in world.creators_per_block)
    # rank r belongs to organisation r mod 5, and carries the OU client
    from cryptography.x509.oid import NameOID

    for row in _creators(world):
        for _c, mspid, cert in row:
            cn = cert.subject.get_attributes_for_oid(NameOID.COMMON_NAME)[0].value
            if cn.startswith("user"):
                assert mspid == f"Org{int(cn[4:]) % 5 + 1}MSP"
                ous = cert.subject.get_attributes_for_oid(NameOID.ORGANIZATIONAL_UNIT_NAME)
                assert [a.value for a in ous] == ["client"]


def test_the_same_seed_gives_the_same_world(man, held):
    """Who signs which transaction, with which key, over which nonce
    and write; serial numbers and ECDSA nonces stay random."""
    from cryptography.hazmat.primitives import serialization

    def digest(world):
        rows = []
        for row, flags in zip(_creators(world), world.planted):
            rows.append([(mspid, cert.subject.rfc4514_string(),
                          cert.public_key().public_bytes(
                              serialization.Encoding.X962,
                              serialization.PublicFormat.CompressedPoint))
                         for _c, mspid, cert in row] + [list(flags)])
        return rows, sorted(world.expected_state().items())

    dep = dict(held["deployment"], block_txs=SIZE.block_txs)
    a = man.world(held)(SEED, dep, held["planted"], 2)
    b = man.world(held)(SEED, dep, held["planted"], 2)
    c = man.world(held)(SEED + 1, dep, held["planted"], 2)
    assert digest(a) == digest(b) != digest(c)


# -- the rehearsal -----------------------------------------------------------


def run(trace=False):
    return engine.run_cell(ROOT, CELL, SEED, 1.0, trace, rehearsal=SIZE)


@pytest.fixture(scope="module")
def sound():
    return run(trace=True)


def test_a_rehearsal_agrees_with_its_reference_to_the_flag_and_the_state_entry(sound):
    compared = {k: v["value"] for k, v in sound["compared"].items()}
    assert sound["attempted"] >= 2 and sound["failed"] == 0
    assert compared["blocks_with_flags_differing_from_reference"] == 0
    assert compared["state_entries_differing_from_reference"] == 0
    assert compared["generator_disagrees_with_reference"] == 0
    # the tiny blocks stand in the cell's regime too: nearly every
    # creator of a block is a stranger
    assert compared["blocks_with_too_few_distinct_creators"] == 0
    assert compared["two_block_runs_with_too_few_distinct_creators"] == 0
    assert all(v["limit"] == 0 for v in sound["compared"].values())
    assert sound["correct"] is True


def test_a_traced_rehearsal_reports_the_metrics_the_host_can_read(sound, man, held):
    # due by the harness's own rule: an entry without `workloads` is due
    # where the end-to-end metric it moves is reported, not everywhere
    due = {m["name"] for m in man.metrics("per_layer", CELL)}
    assert set(sound["metrics"]) <= due
    assert {"collect_ms_per_block.catchup", "verify_wait_ms_per_block.catchup",
            "commit_ms_per_block.catchup", "lanes_per_flush.catchup",
            "creator_validate_ms_per_block.catchup", "creator_miss_share.catchup",
            "keytable_lane_share.catchup"} <= set(sound["metrics"])
    # every block's memo starts empty: a validation a distinct creator
    world = man.world(held)(SEED, dict(held["deployment"], block_txs=SIZE.block_txs),
                            held["planted"], SIZE.blocks_per_pass)
    share = 100.0 * sum(world.creators_per_block) / (SIZE.blocks_per_pass * SIZE.block_txs)
    assert sound["metrics"]["creator_miss_share.catchup"]["value"] == pytest.approx(share)
    assert sound["metrics"]["creator_validate_ms_per_block.catchup"]["value"] > 0
    assert sound["metrics"]["lanes_per_flush.catchup"]["value"] == 56.0
    # this backend enqueues the XLA kernel: no lane on the key-table
    # kernel, and no `tpu.keytable` span to time
    assert sound["metrics"]["keytable_lane_share.catchup"]["value"] == 0.0
    assert "keytable_ms_per_flush.catchup" not in sound["metrics"]


@pytest.fixture
def unpatched():
    from fabric_tpu.msp.msp import MSP

    saved = MSP.validate
    yield
    MSP.validate = saved


def test_skipping_creator_validation_comes_out_as_not_correct(sound, unpatched, man):
    man.control("skip_creator_validation")()
    line = run()
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0           # wrong in every block
    assert compared["state_entries_differing_from_reference"] >= 5
    assert compared["generator_disagrees_with_reference"] == 0
    assert compared["blocks_with_too_few_distinct_creators"] == 0


# -- the condition -----------------------------------------------------------


@pytest.mark.parametrize("per_block,per_two,want", [
    ([520, 505, 530], [905, 915], (0, 0)),
    ([520, 399, 530], [905, 915], (1, 0)),            # a block the seed drew too uniform
    ([401, 402, 400], [699, 700], (0, 1)),            # neighbours sharing their creators
    ([1, 1, 1], [1, 1], (3, 2)),                      # one client: majority5-1000tx's world
])
def test_the_condition_holds_the_traffic_to_the_cells_regime(man, held, per_block, per_two, want):
    (numbers,) = man.conditions(held)
    cell = types.SimpleNamespace(
        world=types.SimpleNamespace(creators_per_block=per_block,
                                    creators_per_two_blocks=per_two),
        deployment={"block_txs": 1000},
    )
    got = numbers(cell)
    assert got == {
        "blocks_with_too_few_distinct_creators": (want[0], 0),
        "two_block_runs_with_too_few_distinct_creators": (want[1], 0),
    }


# -- the new readers, on a recorded span list --------------------------------

SPANS = os.path.join(os.path.dirname(__file__), "data", "spans_manyclients.json")
READERS = ("creator_validate_ms_per_block", "creator_miss_share",
           "keytable_lane_share", "keytable_ms_per_flush")


@pytest.fixture(scope="module")
def obs():
    with open(SPANS) as f:
        return json.load(f)


def said(capsys, tag):
    for line in capsys.readouterr().out.splitlines():
        if line.startswith(f"# {tag}: "):
            return json.loads(line.split(": ", 1)[1])
    raise AssertionError(f"no '# {tag}:' line")


def test_the_validator_readers_read_the_collect_spans_own_counts(obs, man, capsys):
    # two blocks with the attributes (210 and 190 ms for 520 and 500
    # validations of 1,000 transactions each); a third `collect`
    # without them is not a block of this reading
    assert man.reader("creator_validate_ms_per_block.catchup")(obs) == pytest.approx(200.0)
    seen = said(capsys, "creators")
    assert seen == {"distinct_per_block": 510.0, "validations_per_block": 510.0, "blocks": 2}
    assert man.reader("creator_miss_share.catchup")(obs) == pytest.approx(51.0)


def test_the_provider_readers_read_the_key_tables_outcome_and_the_kernel_enqueued(obs, man, capsys):
    # lanes enqueued: 3 x 8,000 on the per-lane-key kernel, 2,000 on the table's
    assert man.reader("keytable_lane_share.catchup")(obs) == pytest.approx(100 * 2000 / 26000)
    assert said(capsys, "enqueued_lanes_by_kernel") == {
        "pallas_ec_p256_verify": 24000, "pallas_ec_p256_verify_ktab": 2000}
    # warm `tpu.keytable`: 12, 10 and 2 ms; the 30 ms one sits under a
    # dispatch whose enqueue was cold
    assert man.reader("keytable_ms_per_flush.catchup")(obs) == pytest.approx(8.0)
    seen = said(capsys, "keytable_outcomes")
    assert seen["flushes"] == {"per_lane": 2, "grown": 1}
    assert seen["mean_distinct_keys"] == pytest.approx((910 + 890 + 7) / 3)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_nothing_from_an_untraced_run_or_a_program_without_its_spans(
        obs, man, name):
    read = man.reader(name + ".catchup")
    assert read(dict(obs, spans=None)) is None
    # the parent's spans: `collect` without the creators' counts,
    # `tpu.enqueue` without `kernel`, and no `tpu.keytable` at all
    bare = [dict(e, args={k: v for k, v in e["args"].items()
                          if k not in ("creators", "creator_validations", "creator_ms",
                                       "kernel", "outcome", "distinct")})
            for e in obs["spans"] if e["name"] != "tpu.keytable"]
    assert read(dict(obs, spans=bare)) is None
