"""The Idemix kind of deployment (`idemix-nym128`): its seeded world
against a golden digest, the plain reference's BN254 against known
pairing identities and against the program's verdicts on every planted
kind, a whole rehearsal of `idemix-nym128.catchup` on the CPU at a tiny
size, the same with the program broken three ways, and the new readers
on a recorded span list.

On a CPU at 12 transactions a block the provider verifies on the host
(under its crossover, and no TPU): the condition `idemix-on-device`
says so with the reason and `correct` is false for that alone, which
is the condition doing its work.  No number of a CPU run is a device
number: the tests read counts, flags and verdicts, never a time.
"""

import hashlib
import json
import os

import pytest

from benchlib import engine
from benchlib.manifest import Manifest

from conftest import BENCH, ROOT

SEED = 2**31 + 99
SIZE = engine.Rehearsal(block_txs=12, blocks_per_pass=3)
CELL = "idemix-nym128.catchup"
CONFIG = "idemix-nym128"

# `world_digest` of build_world(SEED, block_txs 12, 3 blocks) at the
# commit that added the world: planted flags and, of every
# transaction, nonce, creator (pseudonym and association proof), reads
# and writes; the expected state; the issuer's public key.  The
# endorser's certificate and ECDSA signature are not in it (serial
# number and nonce are random), nor the pseudonym signature, which is
# over a payload that holds them.
GOLDEN = "66a175a2d09d884ad982b04f2ef4a326c96e2392fbdd9d8e4d660494b7369dee"


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def held(man):
    return man.config({"name": CELL, "config": CONFIG})


@pytest.fixture(scope="module")
def world(man, held):
    return man.world(held)(
        SEED, dict(held["deployment"], block_txs=SIZE.block_txs), held["planted"],
        SIZE.blocks_per_pass,
    )


@pytest.fixture(scope="module")
def ref(man, held):
    """The reference's module (its `run`'s globals)."""
    import sys

    return sys.modules[man.reference(held).__module__]


def world_digest(world) -> str:
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.ledger.rwset import rwset_pb2
    from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2
    from fabric_tpu.protos.peer import proposal_pb2, proposal_response_pb2, transaction_pb2

    h = hashlib.sha256()
    for block_bytes, planted in zip(world.blocks, world.planted):
        block = common_pb2.Block.FromString(block_bytes)
        h.update(repr((block.header.number, [int(f) for f in planted])).encode())
        for env_bytes in block.data.data:
            env = common_pb2.Envelope.FromString(env_bytes)
            payload = common_pb2.Payload.FromString(env.payload)
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
            h.update(repr((shdr.nonce, shdr.creator, len(cap.action.endorsements), rw)).encode())
    h.update(repr(sorted(world.expected_state().items())).encode())
    h.update(repr((world.refused_at_deserialise, world.rogue_block,
                   sorted(world.public["idemix_issuers"]["IdemixOrgMSP"].items()))).encode())
    return h.hexdigest()


def test_the_seeded_idemix_world_is_the_golden_one(world, held):
    assert world_digest(world) == GOLDEN
    assert world.channel == "benchch" and world.namespaces == ("benchcc",)
    # what the configuration plants is in every block, the rogue
    # issuer's proof in one
    from collections import Counter

    p = held["planted"]
    bad_creator = (p["bad_proof_per_block"] + p["bad_nym_signature_per_block"]
                   + p["ou_mismatch_per_block"])
    for bno, flags in enumerate(world.planted):
        c = Counter(int(f) for f in flags)
        assert c[4] == bad_creator + (bno == world.rogue_block) * p["rogue_issuer_proofs_per_pass"]
        assert c[10] == p["bad_endorsement_per_block"]
        assert c[11] == p["conflict_pairs_per_block"]
    assert world.refused_at_deserialise == [p["ou_mismatch_per_block"]] * SIZE.blocks_per_pass
    assert world.lanes_per_block == 3 * SIZE.block_txs
    assert set(world.public) == {"ca_certs_pem", "idemix_issuers"}


# -- the reference's own BN254 ---------------------------------------------


def test_the_references_pairing_is_bilinear_and_not_degenerate(ref):
    a, b = 0x1234567, 0x89ABCDEF1
    pa = ref.g1_product([(ref.G1, a)])
    base = ref.pairing(ref.G1, ref.G2)
    assert base != ref.F12_ONE
    assert ref.f12_pow(base, ref.R) == ref.F12_ONE
    # e(aP, Q) == e(P, Q)^a, and e(aP, bQ) == e(P, Q)^(ab) through
    # e(aP, bQ) e(-abP, Q) == 1
    assert ref.pairing(pa, ref.G2) == ref.f12_pow(base, a)
    pab = ref.g1_product([(ref.G1, a * b)])
    q_b = _g2_times(ref, b)
    assert ref.g2_on_curve(q_b)
    assert ref.pairing(pa, q_b) == ref.f12_pow(base, a * b % ref.R)
    assert ref.pairings_multiply_to_one([(pa, q_b), (ref.g1_neg(pab), ref.G2)])
    assert not ref.pairings_multiply_to_one([(pa, q_b), (ref.g1_neg(pa), ref.G2)])


def _g2_times(ref, k: int):
    """k * G2 on the twist with the reference's own affine steps."""
    acc = None
    for bit in bin(k)[2:]:
        if acc is not None:
            m = ref.f2_mul(ref.f2_scale(ref.f2_sqr(acc[0]), 3),
                           ref.f2_inv(ref.f2_scale(acc[1], 2)))
            x3 = ref.f2_sub(ref.f2_sqr(m), ref.f2_scale(acc[0], 2))
            acc = (x3, ref.f2_sub(ref.f2_mul(m, ref.f2_sub(acc[0], x3)), acc[1]))
        if bit == "1":
            if acc is None:
                acc = ref.G2
            else:
                m = ref.f2_mul(ref.f2_sub(ref.G2[1], acc[1]),
                               ref.f2_inv(ref.f2_sub(ref.G2[0], acc[0])))
                x3 = ref.f2_sub(ref.f2_sub(ref.f2_sqr(m), acc[0]), ref.G2[0])
                acc = (x3, ref.f2_sub(ref.f2_mul(m, ref.f2_sub(acc[0], x3)), acc[1]))
    return acc


def test_the_references_group_and_hash_are_the_wire_formats(ref):
    """Against the program's own BN254 (the test may look at both; the
    reference's file may not)."""
    from fabric_tpu.idemix import bn254 as bn

    assert (ref.P, ref.R, ref.G2) == (bn.P, bn.R, bn.G2_GEN)
    for k in (1, 2, 0xDEADBEEF, bn.R - 1):
        assert ref.g1_product([(ref.G1, k)]) == bn.g1_mul(bn.G1_GEN, k)
    pts = [bn.g1_mul(bn.G1_GEN, k) for k in (3, 5, 7)]
    assert ref.g1_product(list(zip(pts, (11, -13, 17)))) == \
        bn.g1_msm([(pts[0], 11), (pts[1], (-13) % bn.R), (pts[2], 17)])
    assert ref.hash_to_zr(b"a", b"", b"bc") == bn.hash_to_zr(b"a", b"", b"bc")
    q = bn.g2_mul(bn.G2_GEN, 77)
    p = bn.g1_mul(bn.G1_GEN, 5)
    assert ref.pairings_multiply_to_one([(p, q), (ref.g1_neg(bn.g1_mul(p, 77)), ref.G2)]) \
        == bn.pairing_check([(p, q), (bn.g1_neg(bn.g1_mul(p, 77)), bn.G2_GEN)]) is True


def test_reference_and_program_agree_on_every_planted_kind(world, held, ref):
    """Creator by creator: the reference's verdict, the program's eager
    verdict (`IdemixMSP.deserialize_identity` + `verify`), and what the
    generator planted."""
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.protos.common import common_pb2

    msp = bundle_from_genesis(world.genesis).msp_manager.get_msp("IdemixOrgMSP")
    keys = {k: ref.IssuerPublicKey(d) for k, d in world.public["idemix_issuers"].items()}
    seen = set()
    for block_bytes, planted in zip(world.blocks, world.planted):
        for env_bytes, flag in zip(common_pb2.Block.FromString(block_bytes).data.data, planted):
            env = common_pb2.Envelope.FromString(env_bytes)
            payload = common_pb2.Payload.FromString(env.payload)
            shdr = common_pb2.SignatureHeader.FromString(payload.header.signature_header)
            mspid, creator = ref.Reference._creator_of(env_bytes)
            theirs = ref.creator_verifies(keys[mspid], creator)
            try:
                ident = msp.deserialize_identity(shdr.creator)
                ours = msp.verify(ident, env.payload, env.signature)
            except Exception:
                ours = False
            assert theirs == ours == (int(flag) != 4)
            seen.add(int(flag))
    assert seen == {0, 4, 10, 11}


# -- a whole rehearsal, sound and broken -----------------------------------


def run(trace=False):
    return engine.run_cell(ROOT, CELL, SEED, 1.0, trace, rehearsal=SIZE)


@pytest.fixture(scope="module")
def sound():
    return run(trace=True)


ENGINES_OWN = (
    "blocks_with_flags_differing_from_reference", "state_entries_differing_from_reference",
    "generator_disagrees_with_reference", "lanes_sealed_by_failover", "lanes_sealed_by_breaker",
    "breaker_trips", "compile_events_in_window", "buckets_first_seen_in_window",
    "blocks_not_attempted",
)


def test_a_rehearsal_agrees_with_the_reference_and_the_condition_names_the_host_path(sound):
    compared = {k: v["value"] for k, v in sound["compared"].items()}
    assert sound["attempted"] >= 3 and sound["failed"] == 0
    for name in ENGINES_OWN:
        assert compared[name] == 0, name
    # 12 transactions a block: 11 creators reach the provider (one is
    # refused when deserialised), 22 items, under the crossover, on a CPU
    blocks = sound["attempted"]
    assert compared["idemix_fallbacks"] >= blocks            # warm-up's too
    assert compared["idemix_items_not_on_the_pallas_kernel"] >= 22 * blocks
    assert compared["idemix_proofs_short_on_device"] == 11 * blocks
    assert compared["idemix_nyms_short_on_device"] == 11 * blocks
    assert compared["bn254_buckets_first_seen_in_window"] == 0
    # so on a CPU the verdict is false, and for that alone
    assert sound["correct"] is False
    from fabric_tpu.csp.idemix_provider import IdemixCSP  # the reason, by name

    assert IdemixCSP.DEVICE_CROSSOVER > 22


def test_a_traced_rehearsal_reports_the_metrics_the_host_can_read(sound):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    due = {m["name"] for m in doc["per_layer"]
           if CELL in m.get("workloads", ()) or "workloads" not in m}
    assert set(sound["metrics"]) <= due
    # no device launch on this path: the three metrics that are times
    # or counts read 0.0, the shares of what was never launched nothing
    for name in ("idemix_flush_wall_ms_per_block.catchup", "idemix_host_ms_per_block.catchup",
                 "idemix_isolated_items_per_pass.catchup"):
        assert sound["metrics"][name]["value"] == 0.0
    for name in ("bn254_kernel_ns_per_lane.catchup", "bn254_kernel_hbm_share.catchup",
                 "bn254_bucket_fill_share.catchup", "idemix_device_item_share.catchup"):
        assert name not in sound["metrics"]
    # (`first_block_s` is the ECDSA provider's first device dispatch: at
    # 11 endorsement lanes a block, under `min_device_batch`, there is none)
    assert {"collect_ms_per_block.catchup", "verify_wait_ms_per_block.catchup",
            "commit_ms_per_block.catchup"} <= set(sound["metrics"])


@pytest.fixture
def unpatched():
    from fabric_tpu.csp.idemix_provider import IdemixCSP

    saved = IdemixCSP._seal
    yield
    IdemixCSP._seal = saved


def test_accepting_every_idemix_item_comes_out_as_not_correct(sound, unpatched, man):
    man.control("accept_all_idemix_proofs")()
    line = run()
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["failed"] == line["attempted"] > 0           # wrong in every block
    assert compared["state_entries_differing_from_reference"] > 0
    assert compared["generator_disagrees_with_reference"] == 0


def test_skipping_the_pairings_is_wrong_in_the_rogue_issuers_block_alone(sound, monkeypatch):
    from fabric_tpu.idemix import signature

    monkeypatch.setattr(signature, "_pairing_mask",
                        lambda sigs, ok, ipk, rng=None, stats=None: ok)
    line = run()
    # one block of every pass carries the rogue issuer's proof
    assert line["attempted"] % SIZE.blocks_per_pass == 0
    assert line["failed"] == line["attempted"] // SIZE.blocks_per_pass > 0


def test_skipping_the_pseudonym_signature_is_wrong_in_every_block(sound, monkeypatch):
    from fabric_tpu.idemix import nymsignature

    monkeypatch.setattr(nymsignature, "verify_nym", lambda *a, **k: True)
    monkeypatch.setattr(nymsignature, "challenge_matches", lambda *a, **k: True)
    line = run()
    assert line["failed"] == line["attempted"] > 0


# -- the new readers, on a recorded span list ------------------------------

SPANS = os.path.join(os.path.dirname(__file__), "data", "spans_idemix.json")


@pytest.fixture(scope="module")
def obs():
    with open(SPANS) as f:
        return json.load(f)


def said(capsys, tag):
    for line in capsys.readouterr().out.splitlines():
        if line.startswith(f"# {tag}: "):
            return json.loads(line.split(": ", 1)[1])
    raise AssertionError(f"no '# {tag}:' line")


def test_the_kernel_readers_take_device_time_over_bucket_lanes(obs, man, capsys):
    # two launches at the 256 bucket (254 and 256 lanes), 30 ms and 34 ms
    # of `pallas_bn254_pairing.*`; the ECDSA kernel's 3 ms is not counted
    ns = man.reader("bn254_kernel_ns_per_lane.catchup")(obs)
    assert ns == pytest.approx(1e9 * 0.064 / 512)
    from kernel_counts import pallas_bn254 as counts

    share = man.reader("bn254_kernel_hbm_share.catchup")(obs)
    per_lane = counts.hbm_bytes_per_lane(256)
    assert share == pytest.approx(100.0 * 512 * per_lane / 0.064 / 819e9)
    assert 0 < share < 1
    seen = said(capsys, "bn254_kernel_counts")
    assert seen["hbm_bytes_per_lane"] == pytest.approx(per_lane)
    assert seen["limb_multiplies_per_lane"] == counts.limb_multiplies_per_lane() > 2e7
    assert man.reader("bn254_bucket_fill_share.catchup")(obs) == pytest.approx(100 * 510 / 512)


def test_the_kernels_counts_follow_from_its_shapes():
    from kernel_counts import pallas_bn254 as counts

    assert counts.bytes_in_per_lane() == 4 * (64 + 4 + 15 * 8)
    assert counts.bytes_out_per_lane() == 4 * 10 * 17
    assert counts.N_TERMS == 15 and counts.N_SHARED == 7
    assert counts.hbm_bytes_per_lane(128) > counts.hbm_bytes_per_lane(256)


def test_the_provider_readers_read_the_flush_and_its_parts(obs, man, capsys):
    # two blocks, two flushes of 100 and 140 ms; host parts (prepare,
    # normalize, rehash, pairing) 10+8+2+5 and 10+8+2+65 ms; the second
    # pairing isolated 125 items; one pass
    assert man.reader("idemix_flush_wall_ms_per_block.catchup")(obs) == pytest.approx(120.0)
    assert man.reader("idemix_host_ms_per_block.catchup")(obs) == pytest.approx((25 + 85) / 2)
    parts = said(capsys, "idemix_shares")
    assert parts["idemix.pairing"] == pytest.approx(35.0)
    assert parts["idemix.device_wait"] == pytest.approx(32.0)
    assert man.reader("idemix_isolated_items_per_pass.catchup")(obs) == pytest.approx(125.0)
    # the second flush's 256 of the 510 items ran on the scan fallback
    assert man.reader("idemix_device_item_share.catchup")(obs) == pytest.approx(100 * 254 / 510)


@pytest.mark.parametrize("name,value", [
    ("idemix_flush_wall_ms_per_block", 0.0), ("idemix_host_ms_per_block", 0.0),
    ("idemix_isolated_items_per_pass", 0.0), ("bn254_kernel_ns_per_lane", None),
    ("bn254_kernel_hbm_share", None), ("bn254_bucket_fill_share", None),
    ("idemix_device_item_share", None),
])
def test_a_traced_window_without_idemix_spans(obs, man, name, value, capsys):
    """A time or a count that did not occur reads 0.0; a share or a
    time per lane of launches that were never made has nothing to be a
    share of, and an untraced run gives nothing at all."""
    empty = dict(obs, spans=[e for e in obs["spans"] if not e["name"].startswith("idemix.")])
    assert man.reader(name + ".catchup")(empty) == value
    assert man.reader(name + ".catchup")(dict(obs, spans=None, device_trace=None)) is None
